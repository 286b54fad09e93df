"""The launcher's own counts over the window: compilations and persistent-
cache loads (`jax.monitoring`), collector pauses (`gc.callbacks`)."""


def read(spec: dict, ctx: dict):
    if spec["key"] == "compiles":
        return float(ctx["end"]["compiles"] - ctx["start"]["compiles"])
    if spec["key"] == "gc_pause_share":
        return 100.0 * (ctx["end"]["gc_pause_s"]
                        - ctx["start"]["gc_pause_s"]) / ctx["window_s"]
    raise KeyError(spec["key"])
