"""Ratio of registry counters over the window: the growth of `num` over the
growth of `den`, times `scale`."""


def _delta(ctx: dict, key: str) -> float:
    return float(ctx["end"]["counters"].get(key, 0.0)) \
        - float(ctx["start"]["counters"].get(key, 0.0))


def read(spec: dict, ctx: dict):
    den = sum(_delta(ctx, k) for k in spec["den"])
    if den <= 0:
        return None
    return sum(_delta(ctx, k) for k in spec["num"]) / den \
        * float(spec.get("scale", 1.0))
