#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served scheduling path still
starts, and is still right, on the attached chip.

One process (a chip belongs to one process at a time) that:

1. takes the device — `jax.devices()[0].platform` must be "tpu", or the
   script exits non-zero with a one-line reason and prints no result;
2. starts the agent the way `python -m nomad_tpu agent -dev` does
   (`nomad_tpu.agent.Agent`, server AND client, HTTP on an ephemeral port,
   dev semantics: no `data_dir`, state in memory);
3. loads a cluster of `BASELINE.json`'s size — 10,000 nodes through
   `Server.node_register` and 100,000 running allocs, all from `--seed`,
   hardened so that nothing in it is bf16-exact by construction;
4. submits a few dozen jobs over HTTP in three bursts and reads evals and
   allocations back over HTTP;
5. checks the outcome by the repo's own means: every eval complete,
   placements == requested, no nacks / partial / rejected plans, the
   device-resident table transport carried the dispatches, a committed
   chain's carry was adopted, the cached device view equals a cold upload
   of the host tensors bit for bit, the kernels' value-moving einsums are
   exact on this device, and the same programs on the same frozen state
   agree with the scalar oracle (`scheduler/oracle.py`) and
   the compiled core (`native/core.cpp`) where it covers the stanza;
6. prints the run's record (sizes, seed, check results, wall times) as
   one JSON line and then, as the LAST line of stdout, the verdict the
   driver reads — exactly `{"ok": true, "device": {"platform": ...,
   "kind": ..., "count": ...}}`, the device as JAX reports it — and
   exits 0. A failed check goes to stderr, the verdict says
   `"ok": false` and the exit code is 1. No accelerator, or no program
   beside the script: a one-line reason, no verdict, non-zero.

`--rehearsal` is the CPU dress run, asked for explicitly and never a
consequence of finding no chip: the same checks at a tiny size, with
`"rehearsal": true` in the output.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import random
import sys
import time

#: BASELINE.json's metric size; the rehearsal keeps more nodes than
#: CELLS so the wide meta key is as wide there
FULL_SIZE = {"nodes": 10_000, "allocs": 100_000}
REHEARSAL_SIZE = {"nodes": 600, "allocs": 2_000}
BURSTS = 3
COUNT = 8
#: distinct values of meta.cell: twice what bf16 can tell apart, and with
#: the missing slot still inside the program table's LUT-width ceiling
#: (server/program_table.py DIM_CEILINGS v=512) so these programs ride
#: the table transport like any other
CELLS = 500
PARITY_COUNT = 4     # placements per parity program (the oracle is scalar)
EVAL_TIMEOUT_S = 600.0


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def cache_entries(path: str) -> int:
    try:
        return sum(1 for e in os.scandir(path) if e.is_file())
    except FileNotFoundError:
        return 0


# ---- the cluster and the jobs: synth.py's shapes, hardened -----------------
# synth.py's own values (250/500/1000 MHz, 128/256/512 MiB, 20 racks, 3 dcs)
# are all exact in bfloat16, so a kernel that rounded them would still agree
# with every reference. These are not.

def smoke_node(rng: random.Random, i: int):
    from nomad_tpu.synth import synth_node

    node = synth_node(rng, i)
    node.meta["cell"] = f"c{i % CELLS}"
    node.compute_class()
    return node


def smoke_alloc(rng: random.Random, node, job):
    from nomad_tpu.mock import alloc_resources
    from nomad_tpu.synth import synth_alloc

    alloc = synth_alloc(rng, node, job)
    alloc.allocated_resources = alloc_resources(
        cpu=rng.choice((110, 330, 470)),
        memory_mb=rng.choice((70, 150, 300)), disk_mb=100)
    return alloc


def smoke_job(rng: random.Random, kind: str, n_nodes: int, count: int = COUNT):
    """One job of `kind` — the five BASELINE.json shapes the service path
    supports (binpack, constraint+affinity, spread+distinct_hosts, device
    asks, pinned dc) plus the two that only a wide vocabulary exposes."""
    from nomad_tpu.structs.job import Affinity, Constraint
    from nomad_tpu.synth import synth_service_job

    kw = {
        "binpack": {},
        "affinity": {},
        "spread": {"with_spread": True, "distinct_hosts": True},
        "devices": {"with_devices": True},
        "pinned-dc1": {"datacenter": "dc1"},
        "pinned-dc2": {"datacenter": "dc2"},
        "pinned-dc3": {"datacenter": "dc3"},
        "unique-name": {},
        "distinct-cell": {},
    }[kind]
    if kind == "unique-name":
        count = 1
    job = synth_service_job(rng, count=count, **kw)
    tg = job.task_groups[0]
    res = tg.tasks[0].resources
    res.cpu = rng.choice((1100, 1500, 3000))
    res.memory_mb = rng.choice((300, 700))
    if kind == "affinity":
        # weights that are not powers of two, one over the wide key
        job.constraints.append(Constraint(
            ltarget="${attr.cpu.numcores}", rtarget="4", operand=">="))
        job.affinities = [
            Affinity(ltarget="${node.class}", rtarget="linux-large",
                     operand="=", weight=37),
            Affinity(ltarget="${meta.cell}", rtarget=f"c{CELLS - 211}",
                     operand="=", weight=61),
        ]
    elif kind == "unique-name":
        # the highest-numbered linux-large node: its token (≈ n_nodes)
        # is far past the 256 integers bf16 can tell apart. Several more
        # constraints ride along: how XLA lowers the token-select einsum
        # (on the MXU or off it) depends on how many rows it has.
        hi = max(i for i in range(n_nodes) if i % 3 == 2)
        job.constraints += [
            Constraint(ltarget="${node.unique.name}", rtarget=f"node-{hi}",
                       operand="="),
            Constraint(ltarget="${attr.arch}", rtarget="amd64", operand="="),
            Constraint(ltarget="${attr.cpu.numcores}", rtarget="4",
                       operand=">="),
            Constraint(ltarget="${attr.rack}", rtarget="r99", operand="!="),
            Constraint(ltarget="${meta.cell}", rtarget="c9999",
                       operand="!="),
            Constraint(ltarget="${node.class}", rtarget="linux-tiny",
                       operand="!="),
        ]
    elif kind == "distinct-cell":
        job.constraints.append(Constraint(
            ltarget="${meta.cell}", rtarget="1",
            operand="distinct_property"))
    return job


#: the served window. No "unique-name" here: its LUT is as wide as the
#: cluster, past the table's ceiling BY DESIGN, and one such program sends
#: its whole dispatch down the legacy packed transport — which this smoke
#: counts as a failure. It is checked against the oracle below instead.
BURST_KINDS = ("binpack", "affinity", "spread", "devices", "pinned-dc1",
               "pinned-dc2", "pinned-dc3", "distinct-cell", "binpack",
               "affinity", "spread", "devices")
PARITY_KINDS = ("binpack", "affinity", "spread", "devices", "unique-name",
                "distinct-cell")


def load_cluster(server, rng: random.Random, n_nodes: int, n_allocs: int):
    nodes = []
    for i in range(n_nodes):
        node = smoke_node(rng, i)
        server.node_register(node)
        nodes.append(node)
    filler = [smoke_job(rng, "binpack", n_nodes)
              for _ in range(max(n_allocs // 200, 1))]
    for job in filler:
        server.state.upsert_job(job)
    for i in range(n_allocs):
        server.state.upsert_alloc(smoke_alloc(
            rng, nodes[rng.randrange(n_nodes)], filler[i % len(filler)]))
    return nodes


# ---- the served window -----------------------------------------------------

def run_burst(api, jobs) -> dict:
    """Submit `jobs` concurrently over HTTP (a burst, so the worker drains
    real batches), wait for every eval, read evals and allocations back."""
    t0 = time.time()
    with concurrent.futures.ThreadPoolExecutor(len(jobs)) as pool:
        eval_ids = list(pool.map(api.register_job, jobs))
    out = {"evals": len(jobs), "complete": 0, "requested": 0, "placed": 0,
           "problems": []}
    deadline = time.time() + EVAL_TIMEOUT_S
    for job, eid in zip(jobs, eval_ids):
        ev = api.wait_for_eval(eid, timeout=max(deadline - time.time(), 1.0))
        if ev.status == "complete":
            out["complete"] += 1
        else:
            out["problems"].append(f"eval {eid} of {job.id} is {ev.status}")
        want = job.task_groups[0].count
        got = len(api.job_allocations(job.id))
        out["requested"] += want
        out["placed"] += got
        if got != want:
            out["problems"].append(
                f"job {job.id}: {got} allocations, {want} requested")
    out["wall_s"] = round(time.time() - t0, 3)
    return out


# ---- checks after the window -----------------------------------------------

def view_fields_differing(cluster) -> list:
    """Fields in which the refreshed device view differs from a cold
    upload of the host tensors (the PR 6/15 adoption contract; this is
    where a rounded `delta_res` or carry would show)."""
    import numpy as np

    from nomad_tpu.scheduler.stack import TPUStack, drop_device_view

    def fetch(arrays):
        return {f: np.asarray(getattr(arrays, f)) for f in arrays._fields}

    warm = fetch(TPUStack(cluster).device_arrays())
    drop_device_view(cluster)
    cold = fetch(TPUStack(cluster).device_arrays())
    return [f for f in warm
            if warm[f].dtype != cold[f].dtype
            or not np.array_equal(warm[f], cold[f])]


def selectors_inexact(cluster, rng: random.Random) -> list:
    """The kernels move VALUES (token ids, resource units) through 0/1
    selectors as matmuls (`kernels/placement.py _EXACT`). Run the two
    that the chip's default precision demonstrably rounds — the
    attribute columns through `_select_tokens` at every program width,
    and 32 plan-relative resource deltas through the placement kernel —
    on the device, at this cluster's size, against NumPy. Returns what
    came back wrong."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nomad_tpu.kernels import placement
    from nomad_tpu.scheduler.stack import TPUStack
    from nomad_tpu.utils import bucket

    stack = TPUStack(cluster)
    arrays = stack.device_arrays()
    wrong = []
    k = cluster.attrs.shape[1]
    v = bucket(int(cluster.vocab.max_vocab) + 1)
    want = np.where(cluster.attrs < 0, v - 1, np.minimum(cluster.attrs, v - 1))
    select = jax.jit(placement._select_tokens, static_argnums=2)
    c = 1
    while c <= k:
        # every program width: whether XLA puts this einsum on the MXU
        # (where DEFAULT precision rounds) depends on its row count
        got = np.asarray(select(arrays.attrs,
                                jnp.arange(c, dtype=jnp.int32), v))
        if not np.array_equal(got, want[:, :c]):
            wrong.append(f"_select_tokens[{c} rows]: "
                         f"{int((got != want[:, :c]).sum())} of {got.size} "
                         f"tokens, max error "
                         f"{int(np.abs(got - want[:, :c]).max())}")
        c *= 2
    job = smoke_job(rng, "binpack", len(cluster.row_of), count=1)
    p, m = stack.compile_tg(job, job.task_groups[0], 1)
    rows = np.asarray(rng.sample(sorted(cluster.row_of.values()), 32),
                      dtype=np.int32)
    delta = np.zeros((32, cluster.used.shape[1]), dtype=np.float32)
    delta[:, 0] = [rng.choice((110, 330, 470, 1100, 1500, 3000))
                   for _ in rows]
    delta[:, 1] = [rng.choice((70, 150, 300, 700)) for _ in rows]
    p = p._replace(n_place=np.int32(0), delta_idx=rows, delta_res=delta)
    got = np.asarray(placement.place_task_group_jit(arrays, p, m).new_used)
    want = np.asarray(cluster.used, dtype=np.float32).copy()
    want[rows] -= delta
    if not np.array_equal(got, want):
        wrong.append(f"plan-relative deltas: {int((got != want).sum())} of "
                     f"{want.size} values, max error "
                     f"{float(np.abs(got - want).max())}")
    return wrong


def parity(state, nodes, rng: random.Random) -> dict:
    """The same programs on the same frozen state through the kernel,
    the scalar oracle and — where it covers the stanza — the compiled
    core. Node choices must agree (equal-score ties count) and scores
    must stay inside BASELINE.json's 1 %."""
    from nomad_tpu.scheduler.parity import compiled_parity, oracle_parity
    from nomad_tpu.scheduler.stack import TPUStack

    jobs = [smoke_job(rng, kind, len(nodes), count=PARITY_COUNT)
            for kind in PARITY_KINDS]
    for job in jobs:
        state.upsert_job(job)
    stack = TPUStack(state.cluster)
    stats = oracle_parity(state, nodes, jobs, stack, PARITY_COUNT)
    return {"kinds": list(PARITY_KINDS), **stats,
            **(compiled_parity(stack, jobs, PARITY_COUNT) or {})}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU dress run at a tiny size; never implied")
    args = ap.parse_args(argv)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"  # stated, not fallen back to
    size = REHEARSAL_SIZE if args.rehearsal else FULL_SIZE

    try:
        from nomad_tpu.lib import backend
    except ImportError as e:
        sys.exit(f"chip_smoke: the program is not here: {e}")
    t_start = time.time()
    cache_dir = backend.setup_compile_cache()
    cache_before = cache_entries(cache_dir)
    try:
        dev = backend.resolve()
    except RuntimeError as e:
        sys.exit(f"chip_smoke: {e}")
    if dev.platform != "tpu" and not args.rehearsal:
        sys.exit(f"chip_smoke: needs a TPU, JAX reports platform="
                 f"{dev.platform} (JAX_PLATFORMS="
                 f"{os.environ.get('JAX_PLATFORMS')}); a CPU dress run "
                 f"is --rehearsal")
    import jax

    log(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"devices={dev.count} jax={jax.__version__}"
        + (" REHEARSAL (cpu, tiny size)" if args.rehearsal else ""))
    log(f"compile cache {cache_dir}: {cache_before} entries")

    from nomad_tpu import native
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api.client import NomadClient
    from nomad_tpu.lib import hbm
    from nomad_tpu.lib.metrics import default_registry
    from nomad_tpu.lib.transfer import default_ledger

    native_core = native.status()  # builds core.cpp from source if needed
    log(f"native core: {native_core}")

    failures = []

    def check(name: str, ok: bool, detail: str = "") -> bool:
        log(f"check {name}: {'ok' if ok else 'FAILED'}"
            + (f" ({detail})" if detail else ""))
        if not ok:
            failures.append(f"{name}: {detail}" if detail else name)
        return ok

    rng = random.Random(args.seed)
    # -dev semantics: server + client in this process, no data_dir. The
    # client's own node sits in a datacenter no job names, so placements
    # land on the synthetic nodes only; the TTL outlasts the run.
    agent = Agent(AgentConfig(server=True, client=True, http_port=0,
                              data_dir=None, heartbeat_ttl=3600.0,
                              datacenter="smoke-host"))
    # set-up: the cluster goes in before the agent's threads start. Into
    # a running -dev agent the same load takes ~10x as long — the
    # client's blocking query re-snapshots the whole store on every
    # write (PERF.md "Open questions").
    server = agent.server
    t0 = time.time()
    nodes = load_cluster(server, rng, size["nodes"], size["allocs"])
    setup_s = round(time.time() - t0, 3)
    cl = server.state.cluster
    log(f"loaded {size['nodes']} nodes / {size['allocs']} allocs in "
        f"{setup_s}s; row bucket {cl.n_cap}, port bitmap "
        f"{cl.ports_used.nbytes >> 20} MiB")
    agent.start()
    try:
        host, port = agent.http_addr[0], agent.http_addr[1]
        log(f"agent up (-dev semantics: server+client, in-memory state) "
            f"http={host}:{port}")

        api = NomadClient(host, port, timeout=EVAL_TIMEOUT_S + 30.0)
        bursts = []
        for b in range(BURSTS):
            jobs = [smoke_job(rng, kind, size["nodes"])
                    for kind in BURST_KINDS]
            res = run_burst(api, jobs)
            bursts.append(res)
            log(f"burst {b}: {res['complete']}/{res['evals']} evals "
                f"complete, {res['placed']}/{res['requested']} placed, "
                f"{res['wall_s']}s")

        # ---- checks ----
        n_evals = sum(r["evals"] for r in bursts)
        problems = [p for r in bursts for p in r["problems"]]
        check("evals_complete",
              sum(r["complete"] for r in bursts) == n_evals,
              "; ".join(problems[:4]))
        placed = sum(r["placed"] for r in bursts)
        requested = sum(r["requested"] for r in bursts)
        check("placements", placed == requested,
              f"{placed}/{requested}")
        nacks = int(server.broker.stats.get("nacked", 0))
        plans = dict(server.planner.stats)
        check("no_nacks_partials_rejections",
              nacks == 0 and plans["partial"] == 0
              and plans["rejected_nodes"] == 0,
              f"nacked={nacks} partial={plans['partial']} "
              f"rejected_nodes={plans['rejected_nodes']} "
              f"applied={plans['applied']}")
        ledger = default_ledger().snapshot()
        dyn_rows = int(ledger.get("select_batch.dyn_rows",
                                  {}).get("count", 0))
        pack_buffers = int(ledger.get("select_batch.pack_buffers",
                                      {}).get("count", 0))
        batch = dict(server.workers[0].batch_stats)
        check("table_transport", dyn_rows > 0 and pack_buffers == 0
              and batch.get("batched", 0) > 0,
              f"dyn_rows={dyn_rows} pack_buffers={pack_buffers} "
              f"batched={batch.get('batched', 0)} "
              f"dispatches={batch.get('dispatches', 0)}")
        view = default_registry().counters(prefix="view.")
        # a committed dispatch's carry is adopted device-to-device either
        # as a plain dispatch carry or, when the successor was launched
        # speculatively, as the certified chain head — timing decides
        # which counter moves, so the check is on their sum
        check("carry_adopted", view.get("carry_adopts", 0)
              + view.get("chain_adopts", 0) >= 1,
              f"carry_adopts={view.get('carry_adopts', 0)} "
              f"carry_rejects={view.get('carry_rejects', 0)} "
              f"chain_adopts={view.get('chain_adopts', 0)}")
        differing = view_fields_differing(cl)
        check("view_equals_cold_upload", not differing,
              f"differs in {differing}" if differing else "bit-identical")
        inexact = selectors_inexact(cl, rng)
        check("kernel_selectors_exact", not inexact,
              "; ".join(inexact) if inexact else
              "token ids at every program width and 32 resource deltas "
              "came back bit for bit")
        limit, limit_src = hbm.device_limit_bytes()
        if not args.rehearsal:  # the CPU backend reports no memory stats
            check("hbm_limit_from_device", limit_src == "memory_stats",
                  f"{limit} bytes from {limit_src}")
        par = parity(server.state, nodes, rng)
        check("oracle_parity",
              par["node_agreement_pct"] == 100.0
              and par["score_deviation_max_pct"] <= 1.0,
              f"node agreement {par['node_agreement_pct']}%, score "
              f"deviation mean {par['score_deviation_pct']}% max "
              f"{par['score_deviation_max_pct']}% over "
              f"{par['parity_evals']} evals")
        if native_core["loaded"]:
            check("compiled_parity",
                  par["compiled_node_agreement_pct"] == 100.0
                  and par["compiled_score_deviation_max_pct"] <= 1.0,
                  f"node agreement {par['compiled_node_agreement_pct']}%,"
                  f" score deviation max "
                  f"{par['compiled_score_deviation_max_pct']}%")
        else:
            import shutil

            check("native_core_builds", shutil.which("g++") is None,
                  f"g++ is present but: {native_core['reason']}")
        # one process per chip: after all those dispatches the process
        # still holds the device, and the node's own chips are healthy
        end_platform = jax.devices()[0].platform
        check("platform_held", end_platform == dev.platform, end_platform)
        groups = [g for g in agent.client.device_manager.fingerprint_once()
                  or agent.client.node.node_resources.devices
                  if g.vendor == "google" and g.type == "tpu"]
        tpu_instances = [i for g in groups for i in g.instances]
        if dev.platform == "tpu":
            # asked of the live backend in-process on this very call
            # (lib/backend.py held_devices_silent), never of a child
            check("tpu_group_healthy",
                  len(tpu_instances) == dev.count
                  and all(i.healthy for i in tpu_instances),
                  f"{len(tpu_instances)} instances, "
                  f"{sum(i.healthy for i in tpu_instances)} healthy")
        coverage = hbm.reconcile()["coverage_pct"]
    finally:
        agent.shutdown()

    cache_after = cache_entries(cache_dir)
    log(f"compile cache {cache_dir}: {cache_after} entries "
        f"(+{cache_after - cache_before})")
    # the verdict: these keys and no others, the device as JAX reports it
    verdict = {"ok": not failures,
               "device": {"platform": dev.platform, "kind": dev.device_kind,
                          "count": dev.count}}
    if failures:
        for f in failures:
            print(f"chip_smoke: FAILED {f}", file=sys.stderr)
        print(json.dumps(verdict))
        return 1
    record = {
        "jax": jax.__version__,
        "seed": args.seed,
        "agent": "dev (server+client, no data_dir)",
        "nodes": size["nodes"], "allocs": size["allocs"],
        "row_bucket": int(cl.n_cap),
        "evals": n_evals, "evals_complete": n_evals,
        "placements": placed, "placements_requested": requested,
        "nacks": nacks, "plans_partial": plans["partial"],
        "plans_rejected_nodes": plans["rejected_nodes"],
        "dispatches": int(batch.get("dispatches", 0)),
        "batched_programs": int(batch.get("batched", 0)),
        "table_dyn_rows": dyn_rows, "pack_buffers": pack_buffers,
        "carry_adopts": int(view.get("carry_adopts", 0)),
        "chain_adopts": int(view.get("chain_adopts", 0)),
        "view_equals_cold_upload": True,
        "kernel_selectors_exact": True,
        "parity": par,
        "hbm_limit_bytes": int(limit), "hbm_limit_source": limit_src,
        "hbm_coverage_pct": coverage,
        "tpu_instances_healthy": len(tpu_instances),
        "native_core": native_core,
        "compile_cache": {"dir": cache_dir, "entries_before": cache_before,
                          "entries_after": cache_after},
        # wall times, labelled as such — these are not metrics
        "wall_s": {"setup_ingest": setup_s,
                   "burst_0_with_compiles": bursts[0]["wall_s"],
                   "bursts_after": [r["wall_s"] for r in bursts[1:]],
                   "total": round(time.time() - t_start, 3)},
    }
    if args.rehearsal:
        record["rehearsal"] = True
    print(json.dumps(record))
    print(json.dumps(verdict))  # the last line of stdout
    return 0


if __name__ == "__main__":
    sys.exit(main())
