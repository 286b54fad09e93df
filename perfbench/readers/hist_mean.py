"""Mean of registry histograms over the window: the sums of `plus` less the
sums of `minus`, over the count of `per` (lifetime sum and count at the
window's end less those at its start; `lib/metrics.py` Histogram)."""


def _delta(ctx: dict, key: str, field: str) -> float:
    end = ctx["end"]["histograms"].get(key)
    if end is None:
        return 0.0
    start = ctx["start"]["histograms"].get(key) or {}
    return float(end[field]) - float(start.get(field, 0.0))


def read(spec: dict, ctx: dict):
    per = spec.get("per", spec["plus"][0])
    n = _delta(ctx, per, "count")
    if n <= 0:
        return None
    total = sum(_delta(ctx, k, "sum") for k in spec["plus"]) \
        - sum(_delta(ctx, k, "sum") for k in spec.get("minus", []))
    return total / n
