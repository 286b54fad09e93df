"""Numbers of the profiler trace (`xplane.py`), over the traced part of the
window. Nothing to read (no trace, no launch of a placement program) gives
None, never 0."""
import cluster
import work
import xplane


def _columns(cfg: dict) -> float:
    """Attribute columns a program of the mix names, mean over the mix."""
    total = n = 0
    for kind, share in cfg["mix"].items():
        spec = cluster.make_job(cfg, 0, 0, kind, 1)
        cols = {"${node.datacenter}"} | {c[0] for c in spec["constraints"]} \
            | {a[0] for a in spec["affinities"]}
        if spec["spread"]:
            cols.add(spec["spread"]["attribute"])
        if spec["distinct_property"]:
            cols.add(spec["distinct_property"][0])
        total += len(cols) * share
        n += share
    return total / n


def read(spec: dict, ctx: dict):
    tr = ctx.get("trace")
    if not tr or tr.get("error") or tr["window_s"] <= 0:
        return None
    if spec["key"] == "idle_share":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    dev_s, launches = xplane.placement_device_s(tr)
    if launches <= 0 or dev_s <= 0:
        return None
    if spec["key"] == "kernel_device_ms":
        return 1e3 * dev_s / launches
    if spec["key"] == "placement_roofline":
        programs = float(ctx["traced"]["counters"].get(
            "pipeline.programs", 0.0))
        if programs <= 0:
            return None
        w = work.placement_work(ctx["row_bucket"], programs,
                                ctx["traffic"]["count"],
                                _columns(ctx["config"]))
        least = work.least_seconds(w, ctx["device"]["kind"])
        ctx["notes"]["placement_roofline_bound"] = least["bound"]
        return 100.0 * least["seconds"] / dev_s
    raise KeyError(spec["key"])
