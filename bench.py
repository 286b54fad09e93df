"""Benchmark: evaluation throughput of the TPU placement backend.

Workload (BASELINE.json): synthetic cluster, default 10K nodes / 100K running
allocs; each evaluation places 8 allocations of a fresh 1-task-group service
job (CPU+MiB bin-pack, mixed affinity/spread stanzas). The TPU path batches
evaluations (vmap) through the fused placement kernel; the baseline is the
scalar oracle (`nomad_tpu/scheduler/oracle.py`), a faithful Python
re-implementation of the reference's Go iterator chain
(`scheduler/stack.go:116`, `rank.go:188`, `feasible.go`) in exact (full-scan)
mode. No Go toolchain exists in this image, so the Go scheduler itself cannot
be timed here; the oracle is the measured stand-in (README "Baselines").

Every number comes from the device `jax.devices()` reports, and the output
names it (`platform`, `device_kind`, `device_count`). Without an
accelerator and without an explicit `JAX_PLATFORMS=cpu` the run exits
non-zero; a section that fails fails the run.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "evals/s", "vs_baseline": N}
"""
from __future__ import annotations

import json
import os
import random
import sys
import time
import uuid
from typing import Optional


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def build(n_nodes: int, n_allocs: int, n_evals: int, count: int, seed: int = 11):
    from nomad_tpu.scheduler.stack import TPUStack
    from nomad_tpu.synth import build_synthetic_state, synth_service_job

    t0 = time.time()
    state, nodes = build_synthetic_state(n_nodes, n_allocs, seed=seed)
    rng = random.Random(seed + 1)
    jobs = []
    for i in range(n_evals):
        # Eval mix over the BASELINE configs: 1 (plain bin-pack),
        # 2 (constraint+affinity), 3 (spread + distinct_hosts),
        # 5 (nvidia/gpu device asks). Config 4 (system+preemption) runs in
        # its own harness below — the system scheduler is per-node, not
        # ranked selection.
        job = synth_service_job(
            rng, count=count,
            with_affinity=(i % 2 == 0), with_spread=(i % 3 == 0),
            distinct_hosts=(i % 5 == 0), with_devices=(i % 4 == 0),
            distinct_property=(i % 7 == 0),
        )
        state.upsert_job(job)
        jobs.append(job)
    stack = TPUStack(state.cluster)
    log(f"build: {n_nodes} nodes / {n_allocs} allocs / {n_evals} eval jobs "
        f"in {time.time() - t0:.1f}s")
    return state, nodes, jobs, stack


def bench_tpu(state, jobs, stack, count: int, batch: int) -> float:
    """Batched kernel path: per-eval program compile (host, numpy) + one
    vmapped device dispatch per batch of evaluations. Dispatches are left
    async (JAX dispatch model) so batch i+1's host compile and transfer
    overlap batch i's device execution; one sync at the end.

    The single-device packed path is the default. A mesh is used only
    when asked for (NOMAD_TPU_MESH, as on the served path): the node axis
    is then sharded over the mesh's node ring and the eval batch over its
    batch axis (parallel/mesh.py)."""
    import numpy as np

    from nomad_tpu.kernels.placement import pack_params, place_packed_batch
    from nomad_tpu.parallel import (place_batch_sharded, shard_cluster,
                                    stack_params)
    from nomad_tpu.parallel.mesh import mesh_from_env

    mesh = mesh_from_env()
    if mesh is not None:
        if batch % mesh.devices.shape[0] != 0:
            raise ValueError(
                f"batch {batch} is not divisible by the mesh batch axis "
                f"{mesh.devices.shape[0]}")
        log(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    sharded_fns = {}
    sharded_cluster = {"version": -1, "arrays": None}

    def dispatch(job_batch):
        params = [
            stack.compile_tg(j, j.task_groups[0], count)[0] for j in job_batch
        ]
        batched, m = stack_params(params)
        if mesh is not None:
            if sharded_cluster["version"] != stack.cluster.version:
                sharded_cluster["arrays"] = shard_cluster(
                    stack.device_arrays(), mesh)
                sharded_cluster["version"] = stack.cluster.version
            fn = sharded_fns.get(m)
            if fn is None:
                fn = sharded_fns[m] = place_batch_sharded(mesh, m)
            return fn(sharded_cluster["arrays"], batched).sel_idx
        ibuf, fbuf, ubuf, spec = pack_params(batched)
        arrays = stack.device_arrays()
        sel, _scores = place_packed_batch(arrays, ibuf, fbuf, ubuf, spec, m)
        return sel

    # Warmup / compile
    t0 = time.time()
    sel = np.asarray(dispatch(jobs[:batch]))
    log(f"tpu: compile+warmup {time.time() - t0:.1f}s; "
        f"warmup placed {(sel >= 0).sum()}/{sel.size}")

    t0 = time.time()
    total = 0
    results = []
    for i in range(0, len(jobs), batch):
        job_batch = jobs[i : i + batch]
        if len(job_batch) < batch:
            break
        results.append(dispatch(job_batch))
        total += len(job_batch)
    sels = [np.asarray(r) for r in results]  # sync point
    dt = time.time() - t0
    placed = int(sum((s >= 0).sum() for s in sels))
    rate = total / dt
    log(f"tpu: {total} evals in {dt:.2f}s = {rate:.1f} evals/s "
        f"({placed}/{total * sels[-1].shape[1]} allocs placed)")
    return rate


def bench_explain(state, jobs, stack, count: int, batch: int = 32,
                  iters: int = 8):
    """Explain-overhead A/B on the production fused dispatch
    (place_packed_chain, the SelectCoordinator's kernel): same packed
    buffers, explain off vs on, warmed. Reports the wall overhead (the
    acceptance bar is ≤5%), the extra device→host fetch bytes the
    attribution leaves add, and whether sel_idx/sel_score stayed
    bit-identical — "free and honest", measured every round."""
    import numpy as np

    from nomad_tpu.kernels.placement import pack_params, place_packed_chain
    from nomad_tpu.parallel import stack_params

    b = min(batch, 32, len(jobs))
    params = [stack.compile_tg(j, j.task_groups[0], count)[0]
              for j in jobs[:b]]
    batched, m = stack_params(params)
    ibuf, fbuf, ubuf, spec = pack_params(batched)
    arrays = stack.device_arrays()

    def run(explain):
        out = place_packed_chain(arrays, ibuf, fbuf, ubuf, spec, m,
                                 explain=explain)
        return tuple(np.asarray(x) for x in out)

    base = run(False)  # compile + warm both variants
    ex = run(True)
    identical = (np.array_equal(base[0], ex[0])
                 and np.array_equal(base[1], ex[1]))
    t0 = time.time()
    for _ in range(iters):
        run(False)
    dt_off = time.time() - t0
    t0 = time.time()
    for _ in range(iters):
        run(True)
    dt_on = time.time() - t0
    overhead = 100.0 * (dt_on - dt_off) / dt_off if dt_off else 0.0
    extra = sum(x.nbytes for x in ex) - sum(x.nbytes for x in base)
    log(f"explain: {b}-program chain {dt_off / iters * 1e3:.2f} -> "
        f"{dt_on / iters * 1e3:.2f} ms/dispatch ({overhead:+.1f}%), "
        f"+{extra}B fetch, bit-identical={identical}")
    return {
        "explain_overhead_pct": round(overhead, 2),
        "explain_extra_fetch_bytes": int(extra),
        "explain_bit_identical": bool(identical),
    }


def bench_oracle(state, nodes, jobs, stack, count: int, n_evals: int,
                 parity: bool = True):
    """Scalar oracle path (the measured baseline): full-node-scan Select per
    alloc, sequential, exactly the per-node math of the reference chain.
    With `parity`, the same evals also run through the TPU kernel and are
    compared step by step — the loop is `nomad_tpu/scheduler/parity.py`,
    shared with `chip_smoke.py`. Kernel time is excluded from the rate."""
    from nomad_tpu.scheduler.parity import oracle_parity

    stats, total, dt = oracle_parity(state, nodes, jobs[:n_evals], stack,
                                     count, parity=parity)
    rate = total / dt
    log(f"oracle: {total} evals in {dt:.2f}s = {rate:.3f} evals/s")
    if stats:
        log(f"parity: {stats['parity_evals']} evals / "
            f"{stats['parity_placements']} placements: "
            f"mean score dev {stats['score_deviation_pct']}% "
            f"max {stats['score_deviation_max_pct']}% "
            f"node agreement {stats['node_agreement_pct']}%")
    return rate, stats


def bench_compiled_oracle(state, jobs, count: int, n_evals: int):
    """Compiled scalar baseline: the same select loop as the Python oracle,
    run through the C++ `nomad_select_eval` (native/core.cpp) — full-node
    scan, per-node constraint LUT evaluation, bin-pack + anti-affinity +
    affinity + spread-target scoring with in-loop accounting. This is the
    measured stand-in for the reference's compiled (Go) scheduler hot loop
    (scheduler/stack_test.go:14-55), replacing a "Go ≈ 100× Python"
    estimate with a number. Uses a FRESH program cache
    so per-eval LUT compilation is paid inside the timed loop, exactly as
    the kernel path pays it."""
    from nomad_tpu import native
    from nomad_tpu.scheduler.stack import TPUStack

    if not native.available():
        log("compiled oracle: native library unavailable; skipping")
        return None
    stack = TPUStack(state.cluster)  # fresh _static_program cache
    total = 0
    placed = 0
    score_sum = 0.0
    t0 = time.time()
    for job in jobs[:n_evals]:
        out = native.compiled_select(stack, job, job.task_groups[0], count)
        if out is None:
            return None
        sel, score = out
        placed += int((sel >= 0).sum())
        score_sum += float(score[sel >= 0].sum())
        total += 1
    dt = time.time() - t0
    rate = total / dt
    log(f"compiled oracle: {total} evals in {dt:.2f}s = {rate:.1f} evals/s "
        f"({placed}/{total * count} allocs placed)")

    # Sampled mode — the reference's ACTUAL algorithm shape
    # (scheduler/stack.go:10-18,77-89: ceil(log2 n) shuffled candidates,
    # maxSkip 3). Orders of magnitude fewer nodes scored per alloc, paid
    # for with placement quality; both the rate AND the mean-score delta
    # are reported so neither baseline is overstated (round-4 Weak #3).
    import numpy as np

    stack_s = TPUStack(state.cluster)
    rng = np.random.default_rng(11)
    total_s = 0
    placed_s = 0
    score_sum_s = 0.0
    t0 = time.time()
    for job in jobs[:n_evals]:
        order = rng.permutation(state.cluster.n_cap).astype(np.int32)
        out = native.compiled_select(stack_s, job, job.task_groups[0],
                                     count, order=order)
        if out is None:
            break
        sel, score = out
        placed_s += int((sel >= 0).sum())
        score_sum_s += float(score[sel >= 0].sum())
        total_s += 1
    dt_s = time.time() - t0
    rate_s = total_s / dt_s if total_s else None
    if rate_s:
        q_exact = score_sum / max(placed, 1)
        q_sampled = score_sum_s / max(placed_s, 1)
        log(f"compiled oracle (sampled log2(n)+maxSkip): {total_s} evals "
            f"in {dt_s:.2f}s = {rate_s:.1f} evals/s; mean score "
            f"{q_sampled:.4f} vs exact {q_exact:.4f} "
            f"({placed_s}/{total_s * count} placed)")
    return {"exact": rate, "sampled": rate_s,
            "mean_score_exact": score_sum / max(placed, 1),
            "mean_score_sampled": score_sum_s / max(placed_s, 1)}


def bench_profile(state, jobs, stack, count: int, batch: int) -> Optional[dict]:
    """NOMAD_TPU_BENCH_PROFILE=1: roofline accounting for the compiled
    placement + preemption kernels (lib/roofline.py). Runs AFTER the
    measured sections with its own dispatches, so the default bench path
    and numbers are untouched. Steps:

    - wrap a steady-state dispatch loop in a `jax.profiler` trace
      (NOMAD_TPU_BENCH_PROFILE_DIR, default <repo>/.profile — inspect
      with TensorBoard/XProf);
    - pull static FLOPs / bytes-accessed from `.cost_analysis()` on the
      compiled executables;
    - place achieved vs published per-chip peaks (bf16 MXU FLOP/s, HBM
      BW) on the roofline → compute- or memory-bound + headroom.
    """
    import jax
    import numpy as np

    from nomad_tpu.kernels.placement import pack_params, place_packed_batch
    from nomad_tpu.lib import roofline
    from nomad_tpu.parallel import stack_params

    dev = jax.devices()[0]
    # the same single-device packed dispatch bench_tpu measures
    params = [stack.compile_tg(j, j.task_groups[0], count)[0]
              for j in jobs[:batch]]
    batched, m = stack_params(params)
    ibuf, fbuf, ubuf, spec = pack_params(batched)
    arrays = stack.device_arrays()

    prof_dir = os.environ.get(
        "NOMAD_TPU_BENCH_PROFILE_DIR",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".profile"))
    out = {"device": str(dev), "profile_trace": prof_dir, "kernels": []}

    def timed(name, fn, lowered_fn, *args):
        sec = roofline.time_compiled(
            lambda: jax.block_until_ready(fn(*args)), iters=10, warmup=2)
        cost = roofline.kernel_cost(lowered_fn(*args).compile())
        summ = roofline.summarize(name, cost, sec, dev)
        log(f"profile: {name}: {sec * 1e3:.2f} ms/dispatch, "
            f"{cost['flops']:.3g} FLOPs, {cost['bytes_accessed']:.3g} B "
            f"→ bound={summ.get('bound')} "
            f"pct_peak_flops={summ.get('pct_of_peak_flops')} "
            f"pct_peak_bw={summ.get('pct_of_peak_hbm_bw')}")
        return summ

    with jax.profiler.trace(prof_dir):
        out["kernels"].append(timed(
            f"place_packed_batch[b={batch}]",
            place_packed_batch, place_packed_batch.lower,
            arrays, ibuf, fbuf, ubuf, spec, m))

        # preemption ranking kernel on the same cluster, synthetic
        # victim table (bench workloads rarely trigger real preemption)
        import jax.numpy as jnp

        from nomad_tpu.kernels.preemption import (INF_PRIO,
                                                  PreemptionCandidates,
                                                  preempt_rank_jit)
        from nomad_tpu.scheduler.stack import _to_device
        from nomad_tpu.tensor.cluster import R_TOTAL

        n = int(arrays.capacity.shape[0])
        a_cap = 8
        prio = np.full((n, a_cap), INF_PRIO, dtype=np.float32)
        prio[:, :2] = 50.0  # two eligible victims per node
        usage = np.zeros((n, a_cap, R_TOTAL), dtype=np.float32)
        usage[:, :2, 0] = 100.0
        cands = PreemptionCandidates(prio=jnp.asarray(prio),
                                     usage=jnp.asarray(usage))
        dev_p = _to_device(params[0])
        out["kernels"].append(timed(
            "preempt_rank", preempt_rank_jit, preempt_rank_jit.lower,
            arrays, dev_p, cands))

    return out


def bench_system(state, nodes, n_evals: int):
    """BASELINE config 4: system scheduler with priority-based preemption.
    Each eval places one alloc per eligible node (system_sched.go:45);
    parity check = the kernel-masked placement set must equal a scalar
    recomputation of per-node feasibility+fit, and every preemption-backed
    placement must name only lower-priority victims that actually free
    enough capacity. Runs LAST: processing mutates the shared state."""
    from nomad_tpu.mock import alloc_resources
    from nomad_tpu.scheduler.harness import Harness
    from nomad_tpu.scheduler.oracle import driver_ok, meets_constraints
    from nomad_tpu.structs import Allocation, Evaluation, allocs_fit
    from nomad_tpu.synth import synth_system_job

    rng = random.Random(97)
    h = Harness(state)
    agree = 0
    checked = 0
    preempt_placements = 0
    preempt_ok = 0
    sched_dt = 0.0  # scheduler time only — the scalar cross-check is
    # instrumentation, not workload (same exclusion as the service parity)
    for i in range(n_evals):
        job = synth_system_job(rng)
        tg = job.task_groups[0]
        ask = job.combined_task_resources(tg)

        # scalar expectation BEFORE the plan mutates state
        feasible, fit = set(), set()
        for n in nodes:
            if not n.ready() or n.datacenter not in job.datacenters:
                continue
            if not all(driver_ok(n, t.driver) for t in tg.tasks):
                continue
            if not meets_constraints(n, list(job.constraints)
                                     + list(tg.constraints)):
                continue
            feasible.add(n.id)
            probe = Allocation(
                id="probe", job_id=job.id, job=job, task_group=tg.name,
                node_id=n.id,
                allocated_resources=alloc_resources(
                    cpu=ask.cpu, memory_mb=ask.memory_mb,
                    disk_mb=ask.disk_mb),
                desired_status="run", client_status="pending")
            if allocs_fit(n, state.allocs_by_node(n.id) + [probe])[0]:
                fit.add(n.id)

        state.upsert_job(job)
        n_plans = len(h.plans)
        t0 = time.time()
        h.process(Evaluation(id=uuid.uuid4().hex, namespace="default",
                             job_id=job.id, type="system", priority=job.priority,
                             triggered_by="job-register", status="pending"))
        sched_dt += time.time() - t0
        if len(h.plans) == n_plans:
            # no-op plan is not submitted (system.py): zero placements
            plain, with_victims = set(), []
        else:
            plan = h.plans[-1]
            plain = {a.node_id for allocs in plan.node_allocation.values()
                     for a in allocs if not a.preempted_allocations}
            with_victims = [a for allocs in plan.node_allocation.values()
                            for a in allocs if a.preempted_allocations]
        checked += 1
        if plain == fit:
            agree += 1
        preempt_placements += len(with_victims)
        for a in with_victims:
            vids = set(a.preempted_allocations)
            victims = [v for vs in plan.node_preemptions.values()
                       for v in vs if v.id in vids]
            node = next((n for n in nodes if n.id == a.node_id), None)
            # valid = node was feasible-but-full, victims are strictly
            # lower priority, AND evicting them actually makes the
            # placement fit. The plan is already applied: state holds the
            # new alloc and the victims are terminal (evicted), so
            # allocs_fit over the node's current allocs IS the
            # post-eviction fit check.
            if (a.node_id in feasible - fit
                    and victims and node is not None
                    and all((v.job.priority if v.job else 50) < job.priority
                            for v in victims)
                    and allocs_fit(node, state.allocs_by_node(a.node_id))[0]):
                preempt_ok += 1
    rate = checked / sched_dt if sched_dt else 0.0
    total_placed = sum(
        len(allocs) for p in h.plans for allocs in p.node_allocation.values())
    placement_rate = total_placed / sched_dt if sched_dt else 0.0
    log(f"system: {checked} evals in {sched_dt:.2f}s = {rate:.2f} evals/s "
        f"({total_placed} placements = {placement_rate:.0f}/s); "
        f"node-set agreement {agree}/{checked}; preemption placements "
        f"{preempt_placements} (valid {preempt_ok})")
    return {
        "system_evals_per_sec": round(rate, 2),
        "system_placements_per_sec": round(placement_rate, 1),
        "system_node_agreement_pct": round(100.0 * agree / max(checked, 1),
                                           2),
        "system_preemption_placements": preempt_placements,
        "system_preemption_valid": preempt_ok,
    }


def bench_e2e(n_nodes: int, n_allocs: int, n_evals: int, count: int,
              workers: int, seed: int = 23):
    """End-to-end scheduler benchmark: the same synthetic workload driven
    through the REAL control plane — Server → EvalBroker → Worker →
    GenericScheduler → PlanQueue → plan-apply per-node verification
    (reference nomad/worker.go:105 → plan_apply.go:437). Measures
    evals-to-complete throughput and the optimistic-concurrency cost
    (partial commits / rejected nodes) that the kernel-path number
    excludes (SURVEY §7 hard-part (e))."""
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.synth import synth_node, synth_alloc, synth_service_job

    rng = random.Random(seed)
    s = Server(ServerConfig(num_schedulers=workers, heartbeat_ttl=3600.0))
    t0 = time.time()
    nodes = []
    for i in range(n_nodes):
        node = synth_node(rng, i)
        nodes.append(node)
        s.state.upsert_node(node)
    filler = [synth_service_job(rng) for _ in range(max(n_allocs // 200, 1))]
    for j in filler:
        s.state.upsert_job(j)
    for i in range(n_allocs):
        s.state.upsert_alloc(
            synth_alloc(rng, nodes[rng.randrange(n_nodes)],
                        filler[i % len(filler)]))
    log(f"e2e: ingested {n_nodes} nodes / {n_allocs} allocs "
        f"in {time.time() - t0:.1f}s")
    s.start()
    try:
        warm_n = min(32, max(n_evals // 8, 1))

        def _scenario(i: int) -> str:
            tags = []
            if i % 2 == 0:
                tags.append("affinity")
            if i % 3 == 0:
                tags.append("spread")
            if i % 5 == 0:
                tags.append("distinct-hosts")
            if i % 4 == 0:
                tags.append("devices")
            if i % 2 == 1:
                tags.append("pinned-dc")
            return "+".join(tags) or "binpack"

        # half the feed pins each job to ONE datacenter (r07+): pinned
        # jobs in different dcs have disjoint node footprints, so the
        # drain's conflict partition yields multi-lane wave dispatches —
        # without them the e2e_drain wave read would be vacuously zero
        jobs = [(synth_service_job(
            rng, count=count,
            with_affinity=(i % 2 == 0), with_spread=(i % 3 == 0),
            distinct_hosts=(i % 5 == 0), with_devices=(i % 4 == 0),
            datacenter=(f"dc{1 + (i // 2) % 3}" if i % 2 == 1
                        else None)),
            _scenario(i))
            for i in range(n_evals + warm_n)]
        # warmup: pays the XLA compiles / persistent-cache loads for the
        # program shape buckets so the measured window is steady-state.
        # BURST-registered: the worker must drain real batches here, or
        # the CHAIN kernel's shapes (one per program-axis bucket) would
        # compile inside the measured window
        t0 = time.time()
        warm_evs = [s.job_register(job) for job, _scen in jobs[:warm_n]]
        for ev in warm_evs:
            if ev is not None:
                s.wait_for_eval(ev.id,
                                statuses=("complete", "failed", "blocked",
                                          "cancelled"),
                                timeout=600.0)
        log(f"e2e: warmup {warm_n} evals in {time.time() - t0:.1f}s")
        jobs = jobs[warm_n:]
        # device-view upload counters (scheduler/stack.py device_arrays):
        # snapshot before the measured window so the tail reports the
        # steady-state full-vs-delta breakdown, not warmup cold uploads
        from nomad_tpu.lib.metrics import default_registry
        from nomad_tpu.lib.transfer import default_ledger

        view0 = default_registry().counters(prefix="view.")
        led0 = default_ledger().snapshot()
        pipe0 = _pipeline_totals(s.metrics)
        drain0 = _drain_totals(s.metrics)
        spec0 = s.metrics.counters(prefix="spec.")
        events0 = s.metrics.counters(prefix="events.")
        t0 = time.time()
        evals = []
        for job, scen in jobs:
            ev = s.job_register(job)
            if ev is not None:
                evals.append((ev.id, scen, job.namespace, job.id))
        deadline = time.time() + max(120.0, n_evals * 2.0)
        done = 0
        for eid, _scen, _ns, _jid in evals:
            ev = s.wait_for_eval(
                eid, statuses=("complete", "failed", "blocked", "cancelled"),
                timeout=max(deadline - time.time(), 0.1))
            if ev is not None:
                done += 1
        dt = time.time() - t0
        if done < len(evals):
            raise RuntimeError(
                f"e2e: only {done}/{len(evals)} evals reached a terminal "
                f"status inside the window's deadline")
        # attribution reads state per eval — OUTSIDE the measured
        # window, or the round that adds it reads as an e2e regression
        attribution = _e2e_attribution(s, evals)
        stats = dict(s.planner.stats)
        view1 = default_registry().counters(prefix="view.")
        pipeline = _pipeline_section(pipe0, _pipeline_totals(s.metrics),
                                     led0, default_ledger().snapshot())
        # D2D plan-delta counters ride the pipeline section so the r06
        # artifact is self-attributing: how many dispatches fed their
        # carry back device-to-device (adopts), how many rows never
        # re-crossed the host↔device link (carry_rows), and how often
        # the proof obligations failed back to host uploads (rejects)
        pipeline["d2d"] = {
            k: round(view1.get(k, 0) - view0.get(k, 0), 1)
            for k in ("carry_adopts", "carry_rows", "carry_rejects",
                      "ports_words", "copy_slots")}
        view = {k: round(view1.get(k, 0) - view0.get(k, 0), 1)
                for k in ("upload_bytes", "full_uploads",
                          "ports_full_uploads", "delta_uploads",
                          "delta_rows", "carry_adopts", "carry_rows",
                          "carry_rejects", "ports_words", "copy_slots")}
        log("e2e: view uploads "
            + ", ".join(f"{k}={v}" for k, v in sorted(view.items())))
        wstats = dict(s.workers[0].batch_stats) if s.workers else {}
        if wstats:
            log(f"e2e: worker batch stats {{{', '.join(f'{k}={round(v, 1) if isinstance(v, float) else v}' for k, v in sorted(wstats.items()))}}}")
        # per-phase latency distributions (lib/trace.py span taxonomy):
        # the breakdown that locates the e2e bottleneck — carried in the
        # JSON tail so BENCH rounds record WHERE the time went
        phases = {}
        for name, summ in (s.metrics.snapshot().get("histograms")
                           or {}).items():
            if name.startswith("eval.phase."):
                phases[name[len("eval.phase."):]] = {
                    k: summ[k] for k in ("count", "mean", "p50", "p95",
                                         "p99")}
        if phases:
            log("e2e: phase p50/p95 ms: " + ", ".join(
                f"{k[:-3]}={v['p50']:.2f}/{v['p95']:.2f}"
                for k, v in sorted(phases.items())))
        log(f"e2e: pipeline overlap {pipeline['overlap_pct']:.1f}% "
            f"bubble {pipeline['bubble_ms_mean']:.2f}ms/dispatch "
            f"transfer {pipeline['transfer_bytes_per_dispatch']:.0f}B/"
            f"{pipeline['transfer_count_per_dispatch']:.1f}x per dispatch; "
            "top sites "
            + ", ".join(f"{e['site']}={e['bytes']}"
                        for e in pipeline["top_sites"][:3]))
        log("e2e: d2d " + ", ".join(
            f"{k}={v}" for k, v in sorted(pipeline["d2d"].items())))
        # HBM residency tail (lib/hbm.py): the memory trajectory next
        # to the speed one — what the device-resident loop keeps live
        # per site, the lease high-water, the allocator cross-check,
        # and the ROADMAP item-3 projection (does 100k nodes / 1M
        # allocs fit one HBM, measured per-row costs)
        hbm_tail = _e2e_hbm()
        log(f"e2e: hbm live {hbm_tail['live_bytes']}B "
            f"peak {hbm_tail['peak_bytes']}B "
            f"leases hw {hbm_tail['lease_high_water']} "
            f"(oldest {hbm_tail['lease_age_high_water_s']}s); "
            f"100k-node plan "
            f"{hbm_tail['plan_100k']['projected_bytes']}B "
            + ("fits" if hbm_tail["plan_100k"]["fits"] else
               f"needs {hbm_tail['plan_100k']['shards_needed']} shards"))
        # drain-cadence tail (ISSUE 12): fused-dispatch width, wave
        # structure, and the amortized per-eval dispatch overhead —
        # the BENCH_r07 steering read for the mega-batch path
        # control-plane tail (ISSUE 13): queue depth/age, plan-apply
        # latency + partial rate, leadership stability, heartbeat/flight
        # counts — ALWAYS emitted so BENCH_r07+ carries a control-plane
        # trajectory next to the speed/memory ones (the 3-server soak
        # and failover gates of ROADMAP item 4 read this section)
        control_tail = _e2e_control(s)
        log(f"e2e: control broker ready={control_tail['broker']['ready_total']} "
            f"unacked={control_tail['broker']['unacked']} "
            f"oldest={control_tail['broker']['oldest_eval_age_s']:.2f}s; "
            f"plan apply p50/p99 "
            f"{control_tail['plan_apply']['apply_ms']['p50']:.2f}/"
            f"{control_tail['plan_apply']['apply_ms']['p99']:.2f}ms "
            f"partial_rate={control_tail['plan_apply']['partial_rate']}; "
            f"leadership gained={control_tail['leadership']['gained']} "
            f"lost={control_tail['leadership']['lost']}; "
            f"flight events={control_tail['flight_events']}")
        # speculative-dispatch tail (ISSUE 15): launch/certify/rollback
        # outcomes of the measured window, the wasted-kernel cost of
        # mispredictions, and a short bubble-trajectory A/B against
        # NOMAD_TPU_SPECULATE=0 — did taking plan-apply latency off the
        # dispatch path actually close the bubble on THIS host?
        spec_tail = _e2e_spec(s, spec0, rng, count)
        log(f"e2e: spec launches={spec_tail['launches']} "
            f"certified={spec_tail['certified']} "
            f"rolled_back={spec_tail['rolled_back']} "
            f"redispatch={spec_tail['redispatch_programs']} "
            f"wasted {spec_tail['wasted_kernel_ms']:.1f}ms; A/B bubble "
            f"on={spec_tail['ab']['on']['bubble_ms_mean']} "
            f"off={spec_tail['ab']['off']['bubble_ms_mean']}")
        drain_tail = _e2e_drain(s, drain0)
        log(f"e2e: drain width {drain_tail['batch_width_mean']:.1f} mean"
            f"/{drain_tail['batch_width_max_recent']:.0f} max "
            f"({drain_tail['window_occupancy_pct']:.0f}% of eval_batch="
            f"{s.workers[0].eval_batch if s.workers else s.config.eval_batch}), "
            f"groups {drain_tail['conflict_groups_mean']:.1f}, "
            f"window {drain_tail['window_ms']:.1f}ms "
            f"({drain_tail['window_source']}); wave "
            f"{drain_tail['wave']['dispatches']} dispatches x "
            f"{drain_tail['wave']['lanes_mean']:.1f} lanes, "
            f"{drain_tail['wave']['collisions']} collisions; "
            f"overhead {drain_tail['dispatch_overhead_ms_per_eval']:.3f}"
            f"ms/eval")
        # scheduling-SLO tail (ISSUE 17): per-band latency/attainment/
        # budget over the measured window, ALWAYS emitted
        slo_tail = _e2e_slo(s, evals)
        log("e2e: slo " + "; ".join(
            f"{b}: n={v['total']} att={v['attainment']} "
            f"budget={v['budget_remaining']}"
            for b, v in slo_tail["bands"].items() if v["total"])
            + f"; burn events={len(slo_tail['burn_events'])}")
        # distributed-trace tail (ISSUE 17): span completeness per
        # placement + the tracing-overhead A/B
        trace_tail = _e2e_trace(s, rng, count)
        log(f"e2e: trace stitch {trace_tail['stitched']}/"
            f"{trace_tail['traces']} "
            f"(rate={trace_tail['stitch_rate']}) "
            f"spans/placement={trace_tail['spans_per_placement_mean']}; "
            f"A/B evals/s on={trace_tail['ab']['on']['evals_per_sec']} "
            f"off={trace_tail['ab']['off']['evals_per_sec']} "
            f"overhead={trace_tail['overhead_pct']}%")
        # event-stream tail (ISSUE 18): broker fan-out under 100+
        # subscribers — delivery lag, the no-lost/no-dup ledger, and
        # the publish-hook A/B vs NOMAD_TPU_EVENTS=0
        events_tail = _e2e_events(s, events0, rng, count)
        if events_tail.get("enabled", True):
            log(f"e2e: events {events_tail['published']} published to "
                f"{events_tail['subscribers']} subs "
                f"({events_tail['deliveries']} deliveries) lag p50/p99 "
                f"{events_tail['lag_ms']['p50']}/"
                f"{events_tail['lag_ms']['p99']}ms "
                f"lost={events_tail['lost_non_evicted']} "
                f"dup={events_tail['dups']} "
                f"evictions={events_tail['subscriber_evictions']}; "
                f"A/B evals/s on={events_tail['ab']['on']['evals_per_sec']} "
                f"off={events_tail['ab']['off']['evals_per_sec']} "
                f"overhead={events_tail['publish_overhead_pct']}%")
        else:
            log("e2e: events disabled (NOMAD_TPU_EVENTS=0)")
    finally:
        s.shutdown()
    rate = done / dt if dt else 0.0
    applied = max(stats.get("applied", 0), 1)
    partial_rate = stats.get("partial", 0) / applied
    log(f"e2e: {done}/{len(evals)} evals in {dt:.2f}s = {rate:.1f} evals/s; "
        f"plans applied {stats.get('applied', 0)} partial "
        f"{stats.get('partial', 0)} rejected-nodes "
        f"{stats.get('rejected_nodes', 0)}")
    return {
        "e2e_evals_per_sec": round(rate, 2),
        "e2e_evals_done": done,
        "e2e_plan_partial_rate": round(partial_rate, 4),
        "e2e_rejected_nodes": stats.get("rejected_nodes", 0),
        "e2e_phase_ms": phases,
        # measured-window device-view upload breakdown: with the delta
        # path healthy, full uploads stay ~0 and upload_bytes is row
        # deltas, not whole hot tensors (the BENCH_r05 view_ms gap)
        "e2e_view_upload_bytes": view["upload_bytes"],
        "e2e_view_full_uploads": view["full_uploads"]
        + view["ports_full_uploads"],
        "e2e_view_delta_uploads": view["delta_uploads"],
        "e2e_view_delta_rows": view["delta_rows"],
        # dispatch-pipeline + transfer-ledger attribution for the
        # measured window (lib/transfer.py): does batch k+1's pack hide
        # under batch k's kernel, what does each dispatch move over the
        # host↔device link, and WHICH call sites moved it
        "e2e_pipeline": pipeline,
        # per-scenario placement attribution (kernel-native AllocMetric,
        # ISSUE 8): which scenario regresses, and WHY — filtered vs
        # exhausted, by constraint label and resource dimension
        "e2e_attribution": attribution,
        # device-buffer residency (lib/hbm.py): live/peak per site,
        # lease high-water, allocator cross-check, 100k-node capacity
        # projection — BENCH_r06+ carries a memory trajectory alongside
        # the speed one (ROADMAP item 3's steering read)
        "e2e_hbm": hbm_tail,
        # drain-cadence + wave structure (ISSUE 12): mega-batch width,
        # occupancy, lanes, and amortized per-eval dispatch overhead.
        # Sweep NOMAD_TPU_DRAIN_WINDOW_MS (worker hold window, ms; unset
        # = adaptive from pipeline.host_ms; 0 = never hold) to find the
        # BENCH_r07 cadence frontier
        "e2e_drain": drain_tail,
        # control-plane health (ISSUE 13): broker queue depth/age,
        # plan-apply queue/latency/partial-rate, leadership stability
        # and flight-event counts — read next to e2e_drain: depth/age
        # climbing while drain width is flat means the broker, not the
        # kernel, is the frontier
        "e2e_control": control_tail,
        # speculative dispatch (ISSUE 15): certification outcomes,
        # wasted-kernel cost, and the bubble A/B vs
        # NOMAD_TPU_SPECULATE=0 — `bubble_ms` should approach 0 with
        # speculation on while `wave.collisions` and
        # `e2e_plan_partial_rate` stay flat
        "e2e_spec": spec_tail,
        # scheduling SLOs (ISSUE 17): per-priority-band latency
        # histograms, attainment, error-budget remaining, and any burn
        # events over the measured window — read next to e2e_control:
        # budget draining while broker depth/age is flat means the
        # regression is downstream of the queue
        "e2e_slo": slo_tail,
        # distributed tracing (ISSUE 17): spans per placement, trace
        # stitch rate (target >= 0.99), and the tracing-overhead A/B
        # vs NOMAD_TPU_TRACE=0
        "e2e_trace": trace_tail,
        # FSM-sourced event stream (ISSUE 18): publish→deliver lag
        # p50/p99 under 112 mixed-filter subscribers, the
        # no-lost/no-dup ledger (identity tuples — a plan entry emits
        # its whole batch at one apply index), and the publish-hook
        # overhead A/B vs NOMAD_TPU_EVENTS=0 (target <= 2%)
        "e2e_events": events_tail,
    }


def _e2e_spec(s, spec0: dict, rng, count: int) -> dict:
    """bench tail `e2e_spec` (ISSUE 15): speculative-dispatch outcomes
    over the measured window (launch/certify/rollback counts, exact
    re-dispatched program count, wasted kernel ms) plus a short
    bubble-trajectory A/B — the same dc-pinned feed run once with
    speculation on and once with NOMAD_TPU_SPECULATE=0, bubble_ms
    measured per-arm from the dispatch timeline records (rolled-back
    kernels excluded: wasted device time must not read as overlap)."""
    import os

    from nomad_tpu.server.select_batch import SPECULATE_ENV
    from nomad_tpu.synth import synth_service_job

    c1 = s.metrics.counters(prefix="spec.")

    def delta(k: str) -> float:
        # counters(prefix=) returns keys with the prefix STRIPPED
        return round(c1.get(k, 0) - spec0.get(k, 0), 3)

    out = {
        "launches": int(delta("launches")),
        "certified": int(delta("certified")),
        "rolled_back": int(delta("rolled_back")),
        "redispatch_programs": int(delta("redispatch_programs")),
        "wasted_kernel_ms": delta("wasted_kernel_ms"),
    }

    def arm(enabled: bool, n: Optional[int] = None,
            adopt: Optional[bool] = None) -> dict:
        from nomad_tpu.lib.metrics import default_registry
        from nomad_tpu.server.select_batch import SPEC_PARK_ENV

        ADOPT_ENV = "NOMAD_TPU_SPEC_CHAIN_ADOPT"
        prev = os.environ.get(SPECULATE_ENV)
        prev_park = os.environ.get(SPEC_PARK_ENV)
        prev_adopt = os.environ.get(ADOPT_ENV)
        os.environ[SPECULATE_ENV] = "1" if enabled else "0"
        if adopt is not None:
            os.environ[ADOPT_ENV] = "1" if adopt else "0"
        # a loaded bench host parks slower than the 30ms default; the
        # A/B instrument should measure speculation's EFFECT, not
        # whether the rendezvous won a scheduling race
        os.environ[SPEC_PARK_ENV] = "200"
        try:
            idx0 = s.timeline.last_index()
            # view/resync counters live in the PROCESS registry
            # (scheduler/stack.py), not the server's
            v0 = default_registry().counters(prefix="view.")
            sp0 = default_registry().counters(prefix="spec.")
            t0 = time.time()
            done = 0
            # two waves per arm, each 1.5× the drain cap: every wave
            # overflows into a pipelined successor batch (the one that
            # can launch speculatively), and the SECOND wave's opening
            # refresh adopts the first wave's chain carry (or pays the
            # resync with adoption off) — the adoption cost/saving
            # lands inside the arm that caused it
            eb = (s.workers[0].eval_batch if s.workers
                  else s.config.eval_batch)
            wave_n = eb + max(eb // 2, 1)
            total = n if n is not None else 2 * wave_n
            for w0 in range(0, total, wave_n):
                evs = []
                for i in range(w0, min(w0 + wave_n, total)):
                    ev = s.job_register(synth_service_job(
                        rng, count=count, datacenter=f"dc{1 + i % 3}"))
                    if ev is not None:
                        evs.append(ev.id)
                for eid in evs:
                    got = s.wait_for_eval(
                        eid, statuses=("complete", "failed", "blocked",
                                       "cancelled"), timeout=120.0)
                    if got is not None:
                        done += 1
            dt = time.time() - t0
            _idx, recs = s.timeline.records_after(idx0, timeout=0.0)
            bub = [r["bubble_ms"] for r in recs
                   if r["bubble_ms"] is not None
                   and r.get("spec_outcome") != "rolled_back"]
            v1 = default_registry().counters(prefix="view.")
            sp1 = default_registry().counters(prefix="spec.")

            def vd(k: str) -> int:
                return int(v1.get(k, 0) - v0.get(k, 0))

            return {
                "evals": done,
                "evals_per_sec": round(done / dt, 2) if dt else 0.0,
                "dispatches": len(recs),
                "speculative": sum(1 for r in recs
                                   if r.get("speculative")),
                "bubble_ms_mean": round(sum(bub) / len(bub), 3)
                if bub else None,
                "upload_bytes": vd("upload_bytes"),
                "chain_adopts": vd("chain_adopts"),
                "resync_bytes_saved": int(
                    sp1.get("resync_bytes_saved", 0)
                    - sp0.get("resync_bytes_saved", 0)),
            }
        finally:
            if prev is None:
                os.environ.pop(SPECULATE_ENV, None)
            else:
                os.environ[SPECULATE_ENV] = prev
            if prev_park is None:
                os.environ.pop(SPEC_PARK_ENV, None)
            else:
                os.environ[SPEC_PARK_ENV] = prev_park
            if prev_adopt is None:
                os.environ.pop(ADOPT_ENV, None)
            elif adopt is not None:
                os.environ[ADOPT_ENV] = prev_adopt

    # shared warmup (discarded), SAME width as the arms: the program
    # shapes AND the batch-width chain bucket compile here, so neither
    # arm pays cold XLA compiles — the A/B compares speculation, not
    # compile order
    arm(True)
    out["ab"] = {"on": arm(True), "off": arm(False)}
    # chain-resync A/B (ISSUE 20): speculation ON in both arms, the
    # certified chain-carry ADOPTION toggled — the delta is the view
    # resync bytes the refresh after each chain no longer uploads
    out["chain_ab"] = {"on": arm(True, adopt=True),
                       "off": arm(True, adopt=False)}
    return out


def _e2e_slo(s, evals) -> dict:
    """bench tail `e2e_slo` (ISSUE 17): per-priority-band scheduling-SLO
    state over the measured window. The bench harness runs no clients,
    so the observed latency is submit→eval-complete (plan committed) —
    the control-plane share of the production submit→alloc-start SLO.
    Objectives/targets come from the same NOMAD_TPU_SLO_* knobs the
    server tracker reads, so a sweep tunes both at once."""
    from nomad_tpu.lib.metrics import MetricsRegistry
    from nomad_tpu.lib.tracectx import SLO_BANDS, SloTracker

    reg = MetricsRegistry()
    trk = SloTracker(reg, flight=None, source="bench")
    burns = []
    for eid, _scen, _ns, _jid in evals:
        ev = s.state.eval_by_id(eid)
        if ev is None or ev.status != "complete":
            continue
        if not ev.create_time or not ev.modify_time:
            continue
        latency_ms = max(ev.modify_time - ev.create_time, 0.0) * 1e3
        res = trk.observe(ev.priority, latency_ms, now=ev.modify_time)
        for b in res["fired"]:
            burns.append({"band": res["band"], **b})
    hist = reg.snapshot().get("histograms") or {}
    latency = {}
    for b in SLO_BANDS:
        h = hist.get(f"slo.latency.{b}_ms") or {}
        if h.get("count"):
            latency[b] = {k: h[k] for k in ("count", "mean", "p50",
                                            "p95", "p99")}
    return {
        "latency_source": "submit_to_eval_complete",
        "objective": trk.objective,
        "target_ms": dict(trk.target_ms),
        "bands": trk.snapshot(),
        "latency_ms": latency,
        "burn_events": burns,
    }


def _e2e_trace(s, rng, count: int) -> dict:
    """bench tail `e2e_trace` (ISSUE 17): a short traced arm — every
    submit minted under its own root context, the resulting span trees
    read back from the SpanStore — reporting spans-per-placement and
    the stitch rate (a trace counts as stitched when its eval span is
    present and every span's parent resolves inside the tree; target
    >= 0.99), plus a throughput A/B against NOMAD_TPU_TRACE=0 pricing
    the instrumentation itself."""
    import os

    from nomad_tpu.lib import tracectx
    from nomad_tpu.synth import synth_service_job

    def arm(enabled: bool, n: int = 32) -> dict:
        prev = os.environ.get("NOMAD_TPU_TRACE")
        os.environ["NOMAD_TPU_TRACE"] = "1" if enabled else "0"
        try:
            roots = []
            t0 = time.time()
            for i in range(n):
                root = tracectx.mint()
                with tracectx.use(root):
                    ev = s.job_register(synth_service_job(
                        rng, count=count, datacenter=f"dc{1 + i % 3}"))
                if ev is not None:
                    roots.append((root, ev.id))
            done = 0
            for _root, eid in roots:
                got = s.wait_for_eval(
                    eid, statuses=("complete", "failed", "blocked",
                                   "cancelled"), timeout=120.0)
                if got is not None:
                    done += 1
            dt = time.time() - t0
            return {"roots": roots, "evals": done,
                    "evals_per_sec": round(done / dt, 2) if dt else 0.0}
        finally:
            if prev is None:
                os.environ.pop("NOMAD_TPU_TRACE", None)
            else:
                os.environ["NOMAD_TPU_TRACE"] = prev

    on = arm(True)
    off = arm(False)
    # late spans (ack-side eval emit, plan.apply) land asynchronously
    # with the eval-status read — give the store a beat before stitching
    time.sleep(0.25)
    store = tracectx.default_spans()
    stitched = 0
    with_plan = 0
    span_counts = []
    for root, _eid in on["roots"]:
        spans = store.for_trace(root.trace_id)
        span_counts.append(len(spans))
        ids = {sp["span_id"] for sp in spans}
        names = {sp["name"] for sp in spans}
        orphans = [sp for sp in spans
                   if sp["parent_span_id"]
                   and sp["parent_span_id"] != root.span_id
                   and sp["parent_span_id"] not in ids]
        if spans and "eval" in names and not orphans:
            stitched += 1
        if "plan.apply" in names:
            with_plan += 1
    n = len(on["roots"])
    over = None
    if on["evals_per_sec"] and off["evals_per_sec"]:
        over = round((off["evals_per_sec"] / on["evals_per_sec"] - 1.0)
                     * 100.0, 2)
    return {
        "traces": n,
        "stitched": stitched,
        "stitch_rate": round(stitched / n, 4) if n else None,
        "with_plan_apply": with_plan,
        "spans_per_placement_mean": round(
            sum(span_counts) / len(span_counts), 2) if span_counts else 0.0,
        "ab": {
            "on": {k: on[k] for k in ("evals", "evals_per_sec")},
            "off": {k: off[k] for k in ("evals", "evals_per_sec")},
        },
        "overhead_pct": over,
    }


def _e2e_events(s, events0: dict, rng, count: int) -> dict:
    """bench tail `e2e_events` (ISSUE 18): the FSM-sourced event stream
    under fan-out — 112 concurrent subscribers (mixed topic filters)
    each draining in its own thread while a registration window drives
    the apply path, reporting publish→deliver lag p50/p99, the
    no-lost/no-dup ledger for non-evicted indexes (identity tuples —
    one apply index carries a whole batch), and a throughput A/B
    pricing the publish hook against NOMAD_TPU_EVENTS=0."""
    import os
    import threading

    from nomad_tpu.server.event_broker import GAP_TYPE
    from nomad_tpu.synth import synth_service_job

    broker = s.events
    if broker is None:
        return {"enabled": False}
    ev0 = s.metrics.counters(prefix="events.")

    # -- fan-out window: 112 subscribers, publish-side perf_counter
    # stamps via a bench-side wrap of broker.publish (the product hot
    # path stays clock-free), delivery stamped in each drain thread
    cycles = [None, ["Job"], ["Eval"], ["Alloc"], ["Node"],
              ["Eval:*", "Alloc"], ["Deployment", "Plan"]]
    n_subs = 112
    pub_stamp = {}            # apply index -> perf_counter at publish
    pub_tuples = []           # (index, topic, type, key) in pub order
    pub_lock = threading.Lock()
    real_publish = broker.publish

    def stamped_publish(events):
        now = time.perf_counter()
        with pub_lock:
            for e in events:
                pub_stamp.setdefault(e.index, now)
                pub_tuples.append((e.index, e.topic, e.type, e.key))
        real_publish(events)

    recs = []
    stop = threading.Event()

    def drain(sub, rec):
        while True:
            batch = sub.poll(timeout=0.05)
            now = time.perf_counter()
            if batch:
                for e in batch:
                    if e.type == GAP_TYPE:
                        rec["lost_through"] = max(rec["lost_through"],
                                                  e.index)
                        continue
                    key = (e.index, e.topic, e.type, e.key)
                    if key in rec["seen"]:
                        rec["dups"] += 1
                    rec["seen"].add(key)
                    t0 = pub_stamp.get(e.index)
                    if t0 is not None:
                        rec["lags"].append((now - t0) * 1000.0)
            elif stop.is_set():
                return

    subs, threads = [], []
    broker.publish = stamped_publish
    try:
        for i in range(n_subs):
            topics = cycles[i % len(cycles)]
            sub = broker.subscribe(topics)
            rec = {"topics": topics, "seen": set(), "dups": 0,
                   "lags": [], "lost_through": 0}
            th = threading.Thread(target=drain, args=(sub, rec),
                                  daemon=True)
            th.start()
            subs.append(sub)
            recs.append(rec)
            threads.append(th)
        evs = []
        for i in range(40):
            ev = s.job_register(synth_service_job(
                rng, count=count, datacenter=f"dc{1 + i % 3}"))
            if ev is not None:
                evs.append(ev.id)
        for eid in evs:
            s.wait_for_eval(eid, statuses=("complete", "failed",
                                           "blocked", "cancelled"),
                            timeout=120.0)
        # account lost/dup only through the index the window reached —
        # background applies landing after the drain stops would read
        # as false losses otherwise
        cut = broker.last_index()
        time.sleep(0.5)
    finally:
        broker.publish = real_publish
        stop.set()
        for th in threads:
            th.join(timeout=5.0)
        for sub in subs:
            sub.close()

    lags = sorted(x for rec in recs for x in rec["lags"])

    def _pctl(q: float) -> float:
        if not lags:
            return 0.0
        return round(lags[min(int(q * len(lags)), len(lags) - 1)], 3)

    lost = 0
    dups = 0
    gap_subs = 0
    with pub_lock:
        window = [t for t in pub_tuples if t[0] <= cut]
    for rec in recs:
        dups += rec["dups"]
        if rec["lost_through"]:
            gap_subs += 1
        allowed = (None if rec["topics"] is None else
                   {t.split(":")[0] for t in rec["topics"]})
        for t in window:
            if t[0] <= rec["lost_through"]:
                continue  # evicted-and-gap-marked: not "lost"
            if allowed is not None and t[1] not in allowed:
                continue
            if t not in rec["seen"]:
                lost += 1

    # -- publish-overhead A/B: the env gate NOMAD_TPU_EVENTS=0 leaves
    # state.event_broker unset at construction; the live equivalent is
    # detaching the broker from the store (the per-entry gate in
    # state._emit_entry), restored after the arm
    def arm(enabled: bool, n: int = 32) -> dict:
        prev = os.environ.get("NOMAD_TPU_EVENTS")
        os.environ["NOMAD_TPU_EVENTS"] = "1" if enabled else "0"
        saved = s.state.event_broker
        s.state.event_broker = broker if enabled else None
        try:
            ids = []
            t0 = time.time()
            for i in range(n):
                ev = s.job_register(synth_service_job(
                    rng, count=count, datacenter=f"dc{1 + i % 3}"))
                if ev is not None:
                    ids.append(ev.id)
            done = 0
            for eid in ids:
                got = s.wait_for_eval(
                    eid, statuses=("complete", "failed", "blocked",
                                   "cancelled"), timeout=120.0)
                if got is not None:
                    done += 1
            dt = time.time() - t0
            return {"evals": done,
                    "evals_per_sec": round(done / dt, 2) if dt else 0.0}
        finally:
            s.state.event_broker = saved
            if prev is None:
                os.environ.pop("NOMAD_TPU_EVENTS", None)
            else:
                os.environ["NOMAD_TPU_EVENTS"] = prev

    arm(True, n=16)  # shared warmup arm, discarded (the _e2e_spec
    # precedent: the first arm otherwise pays cache/queue warmup and
    # the A/B reads as publish overhead it isn't)
    on = arm(True)
    off = arm(False)
    over = None
    if on["evals_per_sec"] and off["evals_per_sec"]:
        over = round((off["evals_per_sec"] / on["evals_per_sec"] - 1.0)
                     * 100.0, 2)
    ev1 = s.metrics.counters(prefix="events.")
    return {
        "subscribers": n_subs,
        "published": len(window),
        "published_e2e_window": int(
            ev0.get("published", 0) - events0.get("published", 0)),
        "deliveries": len(lags),
        "lag_ms": {"p50": _pctl(0.50), "p99": _pctl(0.99),
                   "max": round(lags[-1], 3) if lags else 0.0},
        "lost_non_evicted": lost,
        "dups": dups,
        "gap_marked_subs": gap_subs,
        "subscriber_evictions": int(
            ev1.get("subscriber_evictions", 0)
            - ev0.get("subscriber_evictions", 0)),
        "ab": {"on": on, "off": off},
        "publish_overhead_pct": over,
    }


def _drain_totals(reg) -> dict:
    """Snapshot of the drain/wave/pipeline instruments the `e2e_drain`
    tail windows over (lifetime counts/sums — deltas isolate the
    measured window from warmup)."""
    snap = reg.snapshot()
    hist = snap.get("histograms") or {}
    ctr = snap.get("counters") or {}
    out = {"counters": {k: ctr.get(k, 0) for k in (
        "drain.drains", "wave.dispatches", "wave.programs",
        "wave.collisions", "pipeline.dispatches", "pipeline.programs")}}
    for name in ("drain.batch_width", "drain.groups", "drain.hold_ms",
                 "wave.lanes", "pipeline.host_ms"):
        h = hist.get(name) or {}
        out[name] = {"count": h.get("count", 0), "sum": h.get("sum", 0.0)}
    return out


def _e2e_control(s) -> dict:
    """bench tail `e2e_control` (ISSUE 13): the control-plane health
    read next to the speed/memory tails. Queue depth + oldest-eval age
    are the broker backpressure signal; plan-apply latency + partial
    rate the leader-serialization cost; leadership/flight counts the
    stability read (zeros on a single-process bench, non-zero in the
    ROADMAP item-4 3-server soak)."""
    from nomad_tpu.lib.flight import default_flight

    cs = s.control_plane_stats()
    broker = cs["broker"]
    plan = cs["plan_apply"]
    counts = default_flight().counts()
    return {
        "broker": {
            "ready_total": broker["ready_total"],
            "unacked": broker["unacked"],
            "pending_jobs": broker["pending_jobs"],
            "blocked": broker["blocked"],
            "oldest_eval_age_s": broker["oldest_eval_age_s"],
            "nacked": int(s.broker.stats.get("nacked", 0)),
            "requeued": int(s.broker.stats.get("requeued", 0)),
            "failed": int(s.broker.stats.get("failed", 0)),
        },
        "plan_apply": {
            "queue_depth": plan["queue_depth"],
            "partial_rate": plan["partial_rate"],
            "apply_ms": plan["apply_ms"],
            "inline": plan.get("inline", 0),
            "applied": plan.get("applied", 0),
        },
        "heartbeat_expired": cs["heartbeat_expired"],
        "leadership": {
            "gained": counts.get("leadership.gained", 0),
            "lost": counts.get("leadership.lost", 0),
            "terms": counts.get("raft.term", 0),
        },
        "flight_events": sum(counts.values()),
        "flight_counts": dict(sorted(counts.items())),
    }


def _e2e_drain(s, d0: dict) -> dict:
    """bench tail `e2e_drain` (ISSUE 12): is the drain cadence doing its
    job — fused-dispatch width (the mega-batch), window occupancy, wave
    lane structure, and the amortized per-eval dispatch overhead the
    mega-batch exists to shrink. Steer BENCH_r07 by it: width stuck at
    ~1 with a deep queue means the cadence controller is the bottleneck
    (sweep NOMAD_TPU_DRAIN_WINDOW_MS, threaded straight through to the
    workers); width high but amortized overhead flat means the residual
    cost is per-PROGRAM, i.e. the kernel — stop tuning the drain."""
    d1 = _drain_totals(s.metrics)
    snap = s.metrics.snapshot()
    gauges = snap.get("gauges") or {}

    def wmean(name):
        c = d1[name]["count"] - d0[name]["count"]
        return round((d1[name]["sum"] - d0[name]["sum"]) / c, 3) \
            if c else 0.0

    def wcount(name):
        return d1["counters"][name] - d0["counters"][name]

    programs = wcount("pipeline.programs")
    dispatches = wcount("pipeline.dispatches")
    host_ms = d1["pipeline.host_ms"]["sum"] - d0["pipeline.host_ms"]["sum"]
    width_mean = wmean("drain.batch_width")
    width_hist = snap.get("histograms", {}).get("drain.batch_width", {})
    return {
        "drains": wcount("drain.drains"),
        # fused-dispatch width: the mega-batch acceptance read. The
        # mean is an EXACT measured-window delta; the quantiles read
        # the histogram's sliding sample window (last ≤1024 drains),
        # which still contains warmup drains on short runs — hence the
        # _recent suffix, so nobody steers by a warmup-polluted p50
        "batch_width_mean": width_mean,
        "batch_width_p50_recent": width_hist.get("p50", 0.0),
        "batch_width_p95_recent": width_hist.get("p95", 0.0),
        "batch_width_max_recent": width_hist.get("max", 0.0),
        # share of the eval_batch ceiling each drain actually fills
        # (the worker's EFFECTIVE cap — NOMAD_TPU_EVAL_BATCH outranks
        # ServerConfig.eval_batch)
        "window_occupancy_pct": round(
            100.0 * width_mean / max(
                (s.workers[0].eval_batch if s.workers
                 else s.config.eval_batch), 1), 1),
        "conflict_groups_mean": wmean("drain.groups"),
        "hold_ms_mean": wmean("drain.hold_ms"),
        "window_ms": gauges.get("drain.window_ms", 0.0),
        "window_source": ("env" if os.environ.get(
            "NOMAD_TPU_DRAIN_WINDOW_MS") is not None else "adaptive"),
        "wave": {
            "dispatches": wcount("wave.dispatches"),
            "programs": wcount("wave.programs"),
            "collisions": wcount("wave.collisions"),
            "lanes_mean": wmean("wave.lanes"),
        },
        # the amortization itself: pre-kernel host overhead per eval —
        # (dispatch_ms − kernel_ms) / evals in timeline terms. The
        # ≥5× acceptance compares this against an eval_batch-capped run
        # at the same feed (sweep the env knob).
        "dispatch_overhead_ms_per_eval": round(
            host_ms / programs, 4) if programs else 0.0,
        "dispatch_overhead_ms_per_dispatch": round(
            host_ms / dispatches, 3) if dispatches else 0.0,
    }


def _e2e_hbm() -> dict:
    """bench tail `e2e_hbm`: per-site residency + lease lifetime
    high-water + the 100k-node / 1M-alloc capacity projection from the
    per-row costs this very run measured."""
    from nomad_tpu.lib import hbm as hbm_mod

    ledger = hbm_mod.default_hbm()
    summ = ledger.summary()
    rec = hbm_mod.reconcile(ledger)
    return {
        "sites": {site: {k: v[k] for k in ("live_bytes", "peak_bytes",
                                           "buffers")}
                  for site, v in sorted(ledger.snapshot().items())},
        "live_bytes": summ["live_bytes"],
        "peak_bytes": summ["peak_bytes"],
        "outstanding_leases": summ["outstanding_leases"],
        "lease_high_water": summ["lease_high_water"],
        "lease_age_high_water_s": summ["lease_age_high_water_s"],
        "device_bytes_in_use": rec["device_bytes_in_use"],
        "coverage_pct": rec["coverage_pct"],
        "plan_100k": hbm_mod.plan_capacity(100_000, 1_000_000, ledger),
    }


def _e2e_attribution(s, evals) -> dict:
    """bench tail `e2e_attribution`: per-scenario rollup of the
    kernel-native AllocMetric carried on every device-path placement and
    failed task group (the ROADMAP item-4 regression-attribution read).
    `evals` is [(eval_id, scenario, namespace, job_id)]."""
    out = {}
    for eid, scen, ns, jid in evals:
        agg = out.setdefault(scen, {
            "evals": 0, "placements": 0, "failed_groups": 0,
            "blocked": 0, "nodes_evaluated": 0, "nodes_filtered": 0,
            "nodes_exhausted": 0, "dimension_exhausted": {},
            "constraint_filtered": {}})
        agg["evals"] += 1
        ev = s.state.eval_by_id(eid)
        metrics = []
        if ev is not None:
            if ev.status == "blocked" or ev.blocked_eval:
                agg["blocked"] += 1
            metrics.extend((ev.failed_tg_allocs or {}).values())
            agg["failed_groups"] += len(ev.failed_tg_allocs or {})
        for a in s.state.allocs_by_job(ns, jid):
            if a.eval_id != eid:
                continue
            agg["placements"] += 1
            metrics.append(a.metrics)
        for m in metrics:
            agg["nodes_evaluated"] += m.nodes_evaluated
            agg["nodes_filtered"] += m.nodes_filtered
            agg["nodes_exhausted"] += m.nodes_exhausted
            for dim, n in (m.dimension_exhausted or {}).items():
                agg["dimension_exhausted"][dim] = \
                    agg["dimension_exhausted"].get(dim, 0) + n
            for lab, n in (m.constraint_filtered or {}).items():
                agg["constraint_filtered"][lab] = \
                    agg["constraint_filtered"].get(lab, 0) + n
    for scen, agg in sorted(out.items()):
        log(f"e2e attribution [{scen}]: {agg['evals']} evals, "
            f"{agg['placements']} placed, {agg['failed_groups']} failed "
            f"groups, filtered {agg['nodes_filtered']} exhausted "
            f"{agg['nodes_exhausted']} "
            f"dims {agg['dimension_exhausted'] or '{}'}")
    return out


def _pipeline_totals(reg) -> dict:
    """Monotonic pipeline totals from a server registry (counters +
    histogram lifetime sums) — snapshot before/after the measured
    window and difference, exactly like the view.* counters."""
    snap = reg.snapshot()
    c = snap.get("counters", {})
    h = snap.get("histograms", {})

    def hsum(name):
        return float((h.get(name) or {}).get("sum", 0.0))

    return {
        "dispatches": int(c.get("pipeline.dispatches", 0)),
        "transfer_bytes": float(c.get("pipeline.transfer_bytes", 0)),
        "transfer_count": float(c.get("pipeline.transfer_count", 0)),
        "host_ms": hsum("pipeline.host_ms"),
        "overlap_ms": hsum("pipeline.overlap_ms"),
        "bubble_ms": hsum("pipeline.bubble_ms"),
        "bubbles": int((h.get("pipeline.bubble_ms") or {}).get("count", 0)),
    }


def _pipeline_section(p0: dict, p1: dict, led0: dict, led1: dict) -> dict:
    """bench tail `e2e_pipeline`: window deltas of the pipeline metrics
    plus the transfer ledger's top call sites. overlap_pct uses the
    pre-kernel host-time sum (pack + buffer upload + view) as
    denominator (overlap is only computed for dispatches with a
    retained predecessor — with hundreds of dispatches per window the
    first-dispatch skew is noise)."""
    d = {k: p1[k] - p0[k] for k in p0}
    sites = {}
    for site, vals in led1.items():
        prev = led0.get(site, {})
        delta_b = vals["bytes"] - prev.get("bytes", 0)
        if delta_b > 0:
            sites[site] = {
                "site": site, "bytes": delta_b,
                "count": vals["count"] - prev.get("count", 0),
                "ms": round(vals["ms"] - prev.get("ms", 0.0), 3)}
    top = sorted(sites.values(), key=lambda e: -e["bytes"])[:5]
    n = max(d["dispatches"], 1)
    return {
        "dispatches": d["dispatches"],
        "overlap_pct": round(100.0 * d["overlap_ms"] / d["host_ms"], 2)
        if d["host_ms"] else 0.0,
        "overlap_ms_total": round(d["overlap_ms"], 2),
        "bubble_ms_total": round(d["bubble_ms"], 2),
        "bubble_ms_mean": round(d["bubble_ms"] / max(d["bubbles"], 1), 3),
        "transfer_bytes_per_dispatch": round(d["transfer_bytes"] / n, 1),
        "transfer_count_per_dispatch": round(d["transfer_count"] / n, 2),
        "transfer_bytes_total": int(d["transfer_bytes"]),
        "top_sites": top,
    }


def main() -> None:
    from nomad_tpu.lib import backend

    cache_dir = backend.setup_compile_cache()
    # no accelerator and no explicit JAX_PLATFORMS=cpu → raises: a
    # measurement path that finds no chip fails, it does not fall back
    try:
        dev = backend.resolve()
    except RuntimeError as e:
        sys.exit(f"bench: {e}")  # one line, no metric line, non-zero
    log(f"device: platform={dev.platform} device_kind={dev.device_kind} "
        f"count={dev.count}; compile cache {cache_dir}")
    n_nodes = int(os.environ.get("NOMAD_TPU_BENCH_NODES", 10_000))
    n_allocs = int(os.environ.get("NOMAD_TPU_BENCH_ALLOCS", 100_000))
    n_evals = int(os.environ.get("NOMAD_TPU_BENCH_EVALS", 16384))
    batch = int(os.environ.get("NOMAD_TPU_BENCH_BATCH", 4096))
    count = int(os.environ.get("NOMAD_TPU_BENCH_COUNT", 8))
    # the scalar Python oracle runs ~0.12 evals/s at full size; 32 evals
    # (256 placements) keeps the parity sample meaningful at ~4.5 min
    oracle_evals = int(os.environ.get("NOMAD_TPU_BENCH_ORACLE_EVALS", 32))
    parity = os.environ.get("NOMAD_TPU_BENCH_PARITY", "1") != "0"

    state, nodes, jobs, stack = build(n_nodes, n_allocs, n_evals + batch, count)

    tpu_rate = bench_tpu(state, jobs, stack, count, batch)
    explain_stats = bench_explain(state, jobs, stack, count)
    oracle_rate, parity_stats = bench_oracle(
        state, nodes, jobs, stack, count, oracle_evals, parity=parity)
    compiled_evals = int(os.environ.get(
        "NOMAD_TPU_BENCH_COMPILED_EVALS", min(n_evals, 256)))
    compiled_rate = (bench_compiled_oracle(state, jobs, count, compiled_evals)
                     if compiled_evals else None)

    out = {
        "metric": f"service_evals_per_sec_{n_nodes}_nodes",
        "value": round(tpu_rate, 2),
        "unit": "evals/s",
        "vs_baseline": round(tpu_rate / oracle_rate, 2) if oracle_rate else None,
        # the device every number in this line was measured on
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": dev.count,
        "workload": {"nodes": n_nodes, "allocs": n_allocs,
                     "evals": n_evals, "batch": batch},
    }
    if compiled_rate:
        out["compiled_oracle_evals_per_sec"] = round(compiled_rate["exact"],
                                                     2)
        out["vs_compiled_oracle"] = round(tpu_rate / compiled_rate["exact"],
                                          2)
        if compiled_rate.get("sampled"):
            # the reference's actual log2(n)+maxSkip shape: faster per
            # eval at lower placement quality — both ratios + the
            # mean-score delta reported
            out["compiled_oracle_sampled_evals_per_sec"] = round(
                compiled_rate["sampled"], 2)
            out["vs_compiled_oracle_sampled"] = round(
                tpu_rate / compiled_rate["sampled"], 2)
            out["placement_quality_exact_vs_sampled"] = [
                round(compiled_rate["mean_score_exact"], 4),
                round(compiled_rate["mean_score_sampled"], 4)]
    if parity_stats:
        out.update(parity_stats)
    out.update(explain_stats)

    if os.environ.get("NOMAD_TPU_BENCH_PROFILE", "0") == "1":
        # roofline/profiling mode: extra dispatches AFTER the measured
        # sections; never touches the default numbers. Runs before
        # bench_system, which mutates state.
        out["roofline"] = bench_profile(state, jobs, stack, count, batch)

    system_evals = int(os.environ.get("NOMAD_TPU_BENCH_SYSTEM_EVALS", 8))
    if system_evals:
        out.update(bench_system(state, nodes, system_evals))

    # 1024: a 256-eval window holds only ~8 steady-state chain batches
    e2e_evals = int(os.environ.get("NOMAD_TPU_BENCH_E2E_EVALS", 1024))
    if e2e_evals:
        e2e_nodes = min(n_nodes, int(os.environ.get(
            "NOMAD_TPU_BENCH_E2E_NODES", 2000)))
        e2e_allocs = min(n_allocs, 10_000)
        # workers default 1: the select path is kernel-dispatched, so
        # extra Python workers only fight the GIL and inflate optimistic
        # plan conflicts (worker.py's batched-dispatch design note)
        e2e_workers = int(os.environ.get("NOMAD_TPU_BENCH_E2E_WORKERS", 1))
        out.update(bench_e2e(e2e_nodes, e2e_allocs, e2e_evals, count,
                             workers=e2e_workers))
    print(json.dumps(out))


def _lint_preflight() -> None:
    """nomadlint gate before burning accelerator time: a hot-path
    purity regression (NLJ0x) invalidates the numbers this bench
    produces. Pure-ast, no jax import, <5s. NOMAD_TPU_BENCH_LINT=0
    skips; =strict aborts the run on new findings (pre-commit mode);
    default warns."""
    mode = os.environ.get("NOMAD_TPU_BENCH_LINT", "warn")
    if mode == "0":
        return
    from nomad_tpu.analysis import (compare_to_baseline, load_baseline,
                                    run_tree)
    from nomad_tpu.analysis.core import default_baseline_path, default_root

    new = compare_to_baseline(run_tree(default_root()),
                              load_baseline(default_baseline_path()))
    for f in new:
        log(f"LINT: {f.render()}")
    if new and mode == "strict":
        log(f"lint preflight: {len(new)} new finding(s) — aborting "
            "(NOMAD_TPU_BENCH_LINT=strict)")
        sys.exit(3)


if __name__ == "__main__":
    _lint_preflight()
    main()
