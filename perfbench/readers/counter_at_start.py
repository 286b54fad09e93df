"""What registry counters had accrued when the window opened: the sum of
`keys` at the window's first instant, times `scale`. Set-up's share of a
counter that runs for the life of the process (compile time, tracing and
lowering). A program without the counters gives None."""


def read(spec: dict, ctx: dict):
    counters = ctx["start"]["counters"]
    if not any(k in counters for k in spec["keys"]):
        return None
    return sum(float(counters.get(k, 0.0)) for k in spec["keys"]) \
        * float(spec.get("scale", 1.0))
