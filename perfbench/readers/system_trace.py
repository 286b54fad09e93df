"""Device numbers of the system scheduler's program (`jit_system_place`,
one launch a task group of a system eval) over the traced part of the
window: its device time a launch, and its share of the roofline.

The least work is what deciding a system task group needs, whatever
program does it, counted from shapes and from the program's own count of
the victim candidates it packed (`system.candidates`):

- bytes: per launch one pass over the node-axis view the decision reads —
  capacity and used f32[N, R], node_ok u8[N], dyn_free f32[N] and the
  attribute columns the system job's stanzas name i32[N, columns], N the
  row bucket — and per candidate its cpu, memory, disk and priority, f32
  each (`BYTES_PER_CANDIDATE`);
- operations: per launch and node the fit and the feasibility mask
  (`OPS_PER_NODE`), per candidate the greedy's arithmetic, once
  (`OPS_PER_CANDIDATE`): a lower bound, since the greedy scores a node's
  candidates once per victim it takes.

Nothing to read (no trace, no launch of the program: a tree without it)
gives None, never 0."""
import cluster
import work

PROGRAM = "jit_system_place"

OPS_PER_NODE = {
    "fit: used + ask, compare, and-reduce (R dims)": 3 * work.RESOURCES,
    "feasibility mask: and of the constraint columns, node_ok, extra": 3,
}

OPS_PER_CANDIDATE = {
    "distance to the need: subtract, divide, multiply (3 dims), add "
    "twice, square root": 12,
    "priority group and least-score compare": 2,
    "distance to the ask for the superset filter": 12,
    "freed resources added back, compared (3 dims)": 6,
}

BYTES_PER_CANDIDATE = 4 * 4


def _columns(cfg: dict) -> float:
    """Attribute columns a system job of the mix names, mean over them."""
    total = n = 0
    for kind, share in cfg["mix"].items():
        spec = cluster.make_job(cfg, 0, 0, kind, 1)
        if cluster.job_type(spec) != "system":
            continue
        cols = {"${node.datacenter}"} | {c[0] for c in spec["constraints"]}
        total += len(cols) * share
        n += share
    return total / n if n else 0.0


def system_work(node_bucket: int, launches: float, candidates: float,
                columns: float) -> dict:
    per_node_bytes = (2 * work.RESOURCES * 4) + 1 + 4 + columns * 4
    return {"bytes": launches * node_bucket * per_node_bytes
            + candidates * BYTES_PER_CANDIDATE,
            "ops": launches * node_bucket * sum(OPS_PER_NODE.values())
            + candidates * sum(OPS_PER_CANDIDATE.values())}


def read(spec: dict, ctx: dict):
    tr = ctx.get("trace")
    if not tr or tr.get("error") or tr["window_s"] <= 0:
        return None
    prog = tr["programs"].get(PROGRAM) or {}
    launches, dev_s = int(prog.get("count", 0)), prog.get("device_s", 0.0)
    if launches <= 0 or dev_s <= 0:
        return None
    if spec["key"] == "kernel_device_ms":
        return 1e3 * dev_s / launches
    if spec["key"] == "roofline":
        cands = float(ctx["traced"]["counters"].get("system.candidates", 0.0))
        w = system_work(ctx["row_bucket"], launches, cands,
                        _columns(ctx["config"]))
        least = work.least_seconds(w, ctx["device"]["kind"])
        ctx["notes"]["system_roofline_bound"] = least["bound"]
        return 100.0 * least["seconds"] / dev_s
    raise KeyError(spec["key"])
