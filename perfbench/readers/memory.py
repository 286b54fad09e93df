"""Peak device memory over the limit, from `memory_stats()` on the chip."""


def read(spec: dict, ctx: dict):
    m = ctx.get("memory") or {}
    if not m.get("bytes_limit"):
        return None
    return 100.0 * m["peak_bytes_in_use"] / m["bytes_limit"]
