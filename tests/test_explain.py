"""Kernel-native placement explainability (ISSUE 8).

Three contracts:

1. FREE — `sel_idx`/`sel_score` are bit-identical with explain on vs
   off, on both the direct jit path and the production packed-chain
   dispatch (the attribution is reductions of masks the kernel already
   computes; turning it on must not perturb selection).
2. HONEST — the kernel's PlacementExplain counts agree with the scalar
   oracle's stage walk (`oracle.explain_select`) on the kernel-parity
   scenarios: per-stage filtered counts, per-dimension exhaustion in
   column order, rank-time port exhaustion split dyn/reserved.
3. SURFACED — every device-path placement and blocked eval carries a
   real AllocMetric end to end: scheduler harness, blocked tracker,
   HTTP `/v1/evaluation/:id/placement`, SDK, CLI (`eval placement`),
   and the scheduler.filter.*/scheduler.exhausted.* counters.
"""
import random
import threading
import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.oracle import (OracleContext, explain_select,
                                        select_option)
from nomad_tpu.scheduler.stack import TPUStack
from nomad_tpu.structs import Constraint, NetworkResource, Port

from test_kernel_parity import make_cluster, placed_alloc, seed_allocs

SEED = 7


# ---- 1. free: bit-identity ------------------------------------------------


class TestBitIdentity:
    def _setup(self, n_nodes=24, n_place=3):
        rng = random.Random(SEED)
        cl, nodes = make_cluster(n_nodes, rng)
        job = mock.job()
        other = mock.job()
        seed_allocs(cl, nodes, [job, other], rng, 16)
        stack = TPUStack(cl)
        return cl, stack, job, n_place

    def test_direct_jit_bit_identical(self):
        from nomad_tpu.kernels.placement import place_task_group_jit
        from nomad_tpu.parallel.mesh import pad_params

        cl, stack, job, n_place = self._setup()
        params, m = stack.compile_tg(job, job.task_groups[0], n_place)
        (params,), _ = pad_params([params])
        arrays = stack.device_arrays()
        off = place_task_group_jit(arrays, params, m)
        on = place_task_group_jit(arrays, params, m, explain=True)
        assert np.array_equal(np.asarray(off.sel_idx),
                              np.asarray(on.sel_idx))
        # bit-identical, not allclose: same float words
        assert np.asarray(off.sel_score).tobytes() == \
            np.asarray(on.sel_score).tobytes()
        assert off.explain is None and on.explain is not None

    def test_packed_chain_bit_identical(self):
        from nomad_tpu.kernels.placement import (pack_params,
                                                 place_packed_chain)
        from nomad_tpu.parallel.mesh import stack_params

        cl, stack, job, n_place = self._setup()
        jobs = [job, mock.job(), mock.job()]
        params = [stack.compile_tg(j, j.task_groups[0], n_place)[0]
                  for j in jobs]
        batched, m = stack_params(params)
        ibuf, fbuf, ubuf, spec = pack_params(batched)
        arrays = stack.device_arrays()
        off = place_packed_chain(arrays, ibuf, fbuf, ubuf, spec, m)
        on = place_packed_chain(arrays, ibuf, fbuf, ubuf, spec, m,
                                explain=True)
        assert np.asarray(off[0]).tobytes() == np.asarray(on[0]).tobytes()
        assert np.asarray(off[1]).tobytes() == np.asarray(on[1]).tobytes()
        assert len(off) == 4 and len(on) > 4
        # explain leaves carry the chained program axis
        from nomad_tpu.kernels.placement import PlacementExplain

        ex = PlacementExplain(*on[4:])
        assert ex.nodes_evaluated.shape[0] == len(jobs)

    def test_topk_matches_final_scores(self):
        """topk_score must be the descending top-K of the masked score
        vector the kernel already returns (final_scores0)."""
        from nomad_tpu.kernels.placement import place_task_group_jit
        from nomad_tpu.parallel.mesh import pad_params

        cl, stack, job, n_place = self._setup()
        params, m = stack.compile_tg(job, job.task_groups[0], n_place)
        (params,), _ = pad_params([params])
        on = place_task_group_jit(stack.device_arrays(), params, m,
                                  explain=True)
        finals = np.asarray(on.final_scores0)
        want = np.sort(finals)[::-1][: on.explain.topk_score.shape[1]]
        got = np.asarray(on.explain.topk_score)[0]
        np.testing.assert_allclose(got, want, atol=1e-6)


# ---- 2. honest: kernel vs oracle ------------------------------------------


def _oracle_ctx(cl, nodes, seeded):
    abn = {}
    for a in seeded:
        abn.setdefault(a.node_id, []).append(a)
    return OracleContext(nodes=nodes, allocs_by_node=abn)


class TestExplainOracleParity:
    """Kernel PlacementExplain vs oracle explain_select — same stage
    taxonomy, same counts (device-path AllocMetric == host oracle's)."""

    def _compare(self, ex_host, want, step=0):
        assert ex_host["nodes_evaluated"] == want["nodes_evaluated"]
        assert ex_host["filtered_constraint"] == want["filtered_constraint"]
        assert ex_host["filtered_device_plugin"] == 0
        for key in ("filtered_distinct_hosts", "filtered_distinct_property",
                    "dimension_exhausted", "nodes_exhausted"):
            assert ex_host[key][step] == want[key], key

    def _run(self, job, n_nodes=24, n_seed=16, n_place=1, mutate=None):
        rng = random.Random(SEED)
        cl, nodes = make_cluster(n_nodes, rng)
        if mutate:
            mutate(nodes, cl)
        other = mock.job()
        seeded = seed_allocs(cl, nodes, [job, other], rng, n_seed)
        stack = TPUStack(cl)
        tg = job.task_groups[0]
        res = stack.select(job, tg, n_place)
        assert res.explain is not None
        ctx = _oracle_ctx(cl, nodes, seeded)
        for i in range(n_place):
            want = explain_select(ctx, job, tg)
            self._compare(res.explain, want, step=i)
            # feed the kernel's choice so later steps see the same
            # evolving plan (the parity-suite idiom)
            got = res.node_ids[i]
            if got is not None:
                ctx.plan_node_alloc.setdefault(got, []).append(
                    placed_alloc(job, tg, got))
        return res

    def test_no_filtering(self):
        self._run(mock.job())

    def test_constraint_filtered(self):
        job = mock.job()
        job.constraints.append(Constraint("${attr.rack}", "r1", "="))
        self._run(job)

    def test_datacenter_filtered(self):
        job = mock.job()
        job.datacenters = ["dc2"]

        def mutate(nodes, cl):
            for n in nodes[:5]:
                n.datacenter = "dc2"
                cl.upsert_node(n)

        self._run(job, mutate=mutate)

    def test_cpu_exhaustion_multi_step(self):
        job = mock.job()
        job.task_groups[0].tasks[0].resources.cpu = 3500

        def mutate(nodes, cl):
            for n in nodes:
                n.node_resources.cpu = 4000
                cl.upsert_node(n)

        self._run(job, n_place=3, mutate=mutate)

    def test_memory_exhaustion(self):
        job = mock.job()
        job.task_groups[0].tasks[0].resources.memory_mb = 100_000
        self._run(job)

    def test_distinct_hosts_filtered(self):
        job = mock.job()
        job.constraints.append(Constraint("", "", "distinct_hosts"))
        self._run(job, n_nodes=8, n_seed=20, n_place=2)

    def test_distinct_property_filtered(self):
        job = mock.job()
        job.constraints.append(
            Constraint("${attr.rack}", "", "distinct_property"))
        self._run(job, n_nodes=12, n_seed=0, n_place=3)

    def test_reserved_port_exhaustion(self):
        rng = random.Random(SEED)
        cl, nodes = make_cluster(4, rng)
        other = mock.job()
        held = []
        for n in nodes[:3]:
            a = mock.alloc(job=other)
            a.job_id = other.id
            a.node_id = n.id
            a.client_status = "running"
            a.allocated_resources = mock.alloc_resources(
                networks=[NetworkResource(
                    ip=n.node_resources.networks[0].ip, mbits=1,
                    reserved_ports=[Port("http", 8080)])])
            cl.upsert_alloc(a)
            held.append(a)
        job = mock.job()
        tg = job.task_groups[0]
        tg.tasks[0].resources.networks = [NetworkResource(
            mbits=1, reserved_ports=[Port("http", 8080)])]
        res = TPUStack(cl).select(job, tg, 1)
        ctx = _oracle_ctx(cl, nodes, held)
        want = explain_select(ctx, job, tg)
        assert want["dimension_exhausted"] == {"reserved-ports": 3}
        self._compare(res.explain, want)

    def test_dynamic_port_exhaustion(self):
        rng = random.Random(SEED)
        cl, nodes = make_cluster(2, rng)
        nodes[0].reserved_resources.reserved_ports = "20000-32000"
        cl.upsert_node(nodes[0])
        job = mock.job()
        tg = job.task_groups[0]
        tg.tasks[0].resources.networks = [NetworkResource(
            mbits=1, dynamic_ports=[Port("rpc", 0)])]
        res = TPUStack(cl).select(job, tg, 1)
        ctx = _oracle_ctx(cl, nodes, [])
        want = explain_select(ctx, job, tg)
        assert want["dimension_exhausted"] == {"dynamic-ports": 1}
        self._compare(res.explain, want)

    def test_constraint_labels_name_the_filter(self):
        job = mock.job()
        job.constraints.append(Constraint("${attr.rack}", "r1", "="))
        res = self._run(job)
        labels = set(res.explain["constraint_filtered"])
        assert "${attr.rack} = r1" in labels
        total = sum(res.explain["constraint_filtered"].values())
        assert total >= res.explain["filtered_constraint"]


# ---- 2b. coordinator path -------------------------------------------------


class TestCoordinatorExplain:
    def test_fused_batch_carries_explain(self):
        """The batched SelectCoordinator dispatch returns per-program
        explain slices identical in shape/meaning to the direct path."""
        from nomad_tpu.server.select_batch import SelectCoordinator

        rng = random.Random(SEED)
        cl, nodes = make_cluster(12, rng)
        jobs = [mock.job() for _ in range(3)]
        jobs[1].constraints.append(Constraint("${attr.rack}", "r1", "="))
        coord = SelectCoordinator()
        results = {}

        def one(i, job):
            stack = TPUStack(cl)
            stack.coordinator = coord
            stack.coordinator_order = i
            try:
                results[i] = stack.select(job, job.task_groups[0], 1)
            finally:
                coord.thread_done()

        threads = []
        for i, j in enumerate(jobs):
            coord.add_thread()
            threads.append(threading.Thread(target=one, args=(i, j),
                                            daemon=True))
        for t in threads:
            t.start()
        coord.run()
        for t in threads:
            t.join(30.0)
        assert coord.stats["batched"] == 3
        for i, job in enumerate(jobs):
            ex = results[i].explain
            assert ex is not None
            assert ex["nodes_evaluated"] == 12
        # the constrained program sees its own filtering, siblings none
        assert results[1].explain["filtered_constraint"] > 0
        assert results[0].explain["filtered_constraint"] == 0
        # and the batched counts agree with a solo dispatch of the same
        # program against the same snapshot
        solo = TPUStack(cl).select(jobs[1], jobs[1].task_groups[0], 1)
        assert solo.explain["filtered_constraint"] == \
            results[1].explain["filtered_constraint"]
        assert solo.explain["constraint_filtered"] == \
            results[1].explain["constraint_filtered"]

    def test_opted_out_program_gets_no_explain_in_mixed_batch(self):
        """A program that opted out must not receive attribution just
        because a batch-mate asked for it (its scheduler would record
        counters the caller explicitly disabled)."""
        from nomad_tpu.server.select_batch import SelectCoordinator

        rng = random.Random(SEED)
        cl, nodes = make_cluster(8, rng)
        jobs = [mock.job(), mock.job()]
        coord = SelectCoordinator()
        results = {}

        def one(i, job, want):
            stack = TPUStack(cl, explain=want)
            stack.coordinator = coord
            stack.coordinator_order = i
            try:
                results[i] = stack.select(job, job.task_groups[0], 1)
            finally:
                coord.thread_done()

        threads = []
        for i, (j, want) in enumerate(zip(jobs, (True, False))):
            coord.add_thread()
            threads.append(threading.Thread(target=one, args=(i, j, want),
                                            daemon=True))
        for t in threads:
            t.start()
        coord.run()
        for t in threads:
            t.join(30.0)
        assert coord.stats["batched"] == 2
        assert results[0].explain is not None
        assert results[1].explain is None


# ---- 3. surfaced: AllocMetric end to end ----------------------------------


class TestAllocMetricPopulation:
    def _harness(self, n_nodes=8, n_allocs=4, seed=5):
        from nomad_tpu.scheduler.harness import Harness
        from nomad_tpu.synth import build_synthetic_state

        state, nodes = build_synthetic_state(n_nodes, n_allocs, seed=seed)
        return Harness(state=state), state, nodes

    def _eval(self, job):
        from nomad_tpu.structs import Evaluation

        return Evaluation(namespace=job.namespace, job_id=job.id,
                          type="service", triggered_by="job-register",
                          status="pending")

    def test_placed_alloc_carries_score_breakdown(self):
        import random as _r

        from nomad_tpu.synth import synth_service_job

        h, state, nodes = self._harness()
        job = synth_service_job(_r.Random(1), count=2, with_affinity=True)
        state.upsert_job(job)
        h.process(self._eval(job))
        allocs = [a for v in h.plans[-1].node_allocation.values()
                  for a in v]
        assert allocs
        for a in allocs:
            m = a.metrics
            assert m.nodes_evaluated == 8
            assert m.score_meta, "top-K score breakdown missing"
            # descending, selected node present with normalized-score
            norms = [sm.norm_score for sm in m.score_meta]
            assert norms == sorted(norms, reverse=True)
            assert any("binpack" in sm.scores for sm in m.score_meta)

    def test_failed_placement_reports_dimension(self):
        import random as _r

        from nomad_tpu.synth import synth_service_job

        h, state, nodes = self._harness()
        job = synth_service_job(_r.Random(2), count=1)
        job.task_groups[0].tasks[0].resources.cpu = 10**7
        state.upsert_job(job)
        h.process(self._eval(job))
        failed = {}
        for e in h.evals:
            failed.update(e.failed_tg_allocs or {})
        assert failed
        m = next(iter(failed.values()))
        assert m.nodes_exhausted == 8
        assert m.dimension_exhausted == {"cpu": 8}
        assert m.nodes_filtered == 0

    def test_scheduler_counters_recorded(self):
        import random as _r

        from nomad_tpu.lib.metrics import default_registry
        from nomad_tpu.synth import synth_service_job

        reg = default_registry()
        before = reg.counters(prefix="scheduler.exhausted.").get("cpu", 0)
        h, state, nodes = self._harness()
        job = synth_service_job(_r.Random(3), count=1)
        job.task_groups[0].tasks[0].resources.cpu = 10**7
        state.upsert_job(job)
        h.process(self._eval(job))
        after = reg.counters(prefix="scheduler.exhausted.").get("cpu", 0)
        assert after - before == 8

    def test_explain_off_keeps_legacy_counts(self, monkeypatch):
        import random as _r

        from nomad_tpu.synth import synth_service_job

        monkeypatch.setenv("NOMAD_TPU_EXPLAIN", "0")
        h, state, nodes = self._harness()
        job = synth_service_job(_r.Random(4), count=1)
        job.task_groups[0].tasks[0].resources.cpu = 10**7
        state.upsert_job(job)
        h.process(self._eval(job))
        failed = {}
        for e in h.evals:
            failed.update(e.failed_tg_allocs or {})
        m = next(iter(failed.values()))
        # coarse counts survive the opt-out; attribution dicts are empty
        assert m.nodes_exhausted == 8
        assert not m.dimension_exhausted


# ---- 3b. the bulk conversion against the plain one --------------------------
#
# The reference below is the element-by-element conversion this repo had
# before the bulk one (scheduler/stack.py explain_columns +
# GenericScheduler._group_metrics + AllocMetric.score_selected): one NumPy
# scalar read at a time into a dict of dicts, walked back into the
# AllocMetric through a linear search for every score. It imports nothing
# of the bulk pass — only the data classes and the kernel's leaf names.

_SCORE_NAMES = ("binpack", "job-anti-affinity", "node-reschedule-penalty",
                "node-affinity", "allocation-spread")
_DIM_NAMES = ["cpu", "memory", "disk", "network"] + [
    f"resource[{i}]" for i in range(4, 8)]


def _plain_explain_host(ex, labels, n_place, dim_names, rows):
    cfilt = {}
    for c, label in enumerate(labels):
        v = int(ex.filt_constraint[c])
        if v:
            cfilt[label] = cfilt.get(label, 0) + v
    steps = []
    for i in range(min(n_place, int(ex.filt_distinct.shape[0]))):
        dims = {}
        for r, name in enumerate(dim_names):
            v = int(ex.exh_dim[i, r])
            if v:
                dims[name] = v
        if int(ex.exh_dyn_ports[i]):
            dims["dynamic-ports"] = int(ex.exh_dyn_ports[i])
        if int(ex.exh_res_ports[i]):
            dims["reserved-ports"] = int(ex.exh_res_ports[i])
        top = []
        for k in range(ex.topk_idx.shape[1]):
            score = float(ex.topk_score[i, k])
            row = int(ex.topk_idx[i, k])
            if score <= -1e29 or row < 0 or row >= len(rows):
                continue
            nid = rows[row]
            if nid is None:
                continue
            top.append({
                "node_id": nid,
                "norm_score": score,
                "scores": {name: float(ex.topk_parts[i, k, j])
                           for j, name in enumerate(_SCORE_NAMES)},
            })
        steps.append({
            "filtered_distinct_hosts": int(ex.filt_distinct[i]),
            "filtered_distinct_property": int(ex.filt_dp[i]),
            "nodes_exhausted": sum(dims.values()),
            "dimension_exhausted": dims,
            "top_nodes": top,
        })
    return {
        "nodes_evaluated": int(ex.nodes_evaluated),
        "filtered_constraint": int(ex.filt_lut),
        "filtered_device_plugin": int(ex.filt_extra),
        "nodes_filtered": int(ex.filt_lut) + int(ex.filt_extra),
        "constraint_filtered": cfilt,
        "steps": steps,
    }


def _plain_score_node(metrics, node_id, name, score):
    from nomad_tpu.structs import NodeScoreMeta

    for sm in metrics.score_meta:
        if sm.node_id == node_id:
            sm.scores[name] = score
            return
    metrics.score_meta.append(
        NodeScoreMeta(node_id=node_id, scores={name: score}))


def _plain_populate_score_meta(metrics, k=5):
    from nomad_tpu.lib import KHeap

    for sm in metrics.score_meta:
        if "normalized-score" in sm.scores:
            sm.norm_score = sm.scores["normalized-score"]
    if len(metrics.score_meta) <= k:
        metrics.score_meta.sort(key=lambda sm: -sm.norm_score)
        return
    h = KHeap(k)
    for sm in metrics.score_meta:
        h.push(sm.norm_score, sm)
    metrics.score_meta = h.items_desc()


def _plain_apply_explain(metrics, ex, step):
    metrics.nodes_evaluated = ex["nodes_evaluated"]
    metrics.nodes_filtered = ex["nodes_filtered"]
    for label, n in ex["constraint_filtered"].items():
        metrics.constraint_filtered[label] = (
            metrics.constraint_filtered.get(label, 0) + n)
    if ex["filtered_device_plugin"]:
        metrics.constraint_filtered["device-plugin/host checks"] = \
            ex["filtered_device_plugin"]
    if step < len(ex["steps"]):
        s = ex["steps"][step]
        if s["filtered_distinct_hosts"]:
            metrics.nodes_filtered += s["filtered_distinct_hosts"]
            metrics.constraint_filtered["distinct_hosts"] = \
                s["filtered_distinct_hosts"]
        if s["filtered_distinct_property"]:
            metrics.nodes_filtered += s["filtered_distinct_property"]
            metrics.constraint_filtered["distinct_property"] = \
                s["filtered_distinct_property"]
        metrics.nodes_exhausted = s["nodes_exhausted"]
        for dim, n in s["dimension_exhausted"].items():
            metrics.dimension_exhausted[dim] = (
                metrics.dimension_exhausted.get(dim, 0) + n)
        for entry in s["top_nodes"]:
            for name, v in entry["scores"].items():
                if v != 0.0:
                    _plain_score_node(metrics, entry["node_id"], name, v)
            _plain_score_node(metrics, entry["node_id"], "normalized-score",
                              entry["norm_score"])


def _plain_group(ex_np, labels, dim_names, rows, sel, n_ready, by_dc):
    """The placement loop's metrics as the parent made them: one
    AllocMetric a placed allocation (None where the placement failed)
    and the group's failed metric. `sel` is what select answered:
    (node ids, scores, nodes_feasible, nodes_fit)."""
    from nomad_tpu.structs import AllocMetric

    node_ids, scores, n_feas, n_fit = sel
    ex = None if ex_np is None else _plain_explain_host(
        ex_np, labels, len(node_ids), dim_names, rows)
    placed, failed = [], None
    for i, (node_id, score) in enumerate(zip(node_ids, scores)):
        metrics = AllocMetric()
        metrics.nodes_evaluated = n_ready
        metrics.nodes_available = dict(by_dc)
        if ex is not None:
            _plain_apply_explain(metrics, ex, i)
        if node_id is None:
            if failed is not None:
                failed.coalesced_failures += 1
            else:
                if ex is None:
                    metrics.nodes_filtered = n_ready - n_feas
                    metrics.nodes_exhausted = n_feas - n_fit[i]
                _plain_populate_score_meta(metrics)
                failed = metrics
            placed.append(None)
            continue
        _plain_score_node(metrics, node_id, "normalized-score", score)
        _plain_populate_score_meta(metrics)
        placed.append(metrics)
    return placed, failed


def _bulk_group(ex_np, labels, dim_names, rows, sel, n_ready, by_dc):
    """The same loop over the bulk pass (GenericScheduler.
    _compute_placements' sequence; the Server test below runs the
    scheduler's own)."""
    from nomad_tpu.scheduler.generic import GenericScheduler
    from nomad_tpu.scheduler.stack import explain_columns

    node_ids, scores, n_feas, n_fit = sel
    ex = None if ex_np is None else explain_columns(
        ex_np, labels, len(node_ids), dim_names, rows)
    group = GenericScheduler._group_metrics(ex, len(node_ids), n_ready,
                                            by_dc)
    placed, failed = [], None
    for i, (metrics, node_id, score) in enumerate(
            zip(group, node_ids, scores)):
        if node_id is None:
            if failed is not None:
                failed.coalesced_failures += 1
            else:
                if ex is None:
                    metrics.nodes_filtered = n_ready - n_feas
                    metrics.nodes_exhausted = n_feas - n_fit[i]
                metrics.populate_score_meta()
                failed = metrics
            placed.append(None)
            continue
        metrics.score_selected(node_id, score)
        placed.append(metrics)
    return placed, failed


_PLAIN_TYPES = (int, float, str, bool, bytes, type(None))


def _assert_plain(tree, path="metric"):
    """Only the codec's own scalar types, exactly: a NumPy float64 IS a
    float to `isinstance`, and must not pass for one here."""
    if type(tree) is dict:
        for k, v in tree.items():
            assert type(k) is str, (path, k)
            _assert_plain(v, f"{path}.{k}")
    elif type(tree) is list:
        for i, v in enumerate(tree):
            _assert_plain(v, f"{path}[{i}]")
    else:
        assert type(tree) in _PLAIN_TYPES, (path, type(tree), tree)


def _wire_bytes(metric):
    import msgpack

    from nomad_tpu.structs.codec import to_wire

    tree = to_wire(metric)
    _assert_plain(tree)
    return msgpack.packb(tree, use_bin_type=True)


def _assert_same_metric(got, want, where=""):
    """Field for field, dict order and score_meta order included, and
    byte for byte on the wire (msgpack writes a dict in its order)."""
    import dataclasses

    assert (got is None) == (want is None), where
    if want is None:
        return
    for f in dataclasses.fields(want):
        g, w = getattr(got, f.name), getattr(want, f.name)
        if f.name == "score_meta":
            assert [(sm.node_id, sm.norm_score, list(sm.scores.items()))
                    for sm in g] == \
                [(sm.node_id, sm.norm_score, list(sm.scores.items()))
                 for sm in w], (where, f.name)
        elif isinstance(w, dict):
            assert list(g.items()) == list(w.items()), (where, f.name)
        else:
            assert g == w and type(g) is type(w), (where, f.name)
    assert _wire_bytes(got) == _wire_bytes(want), where


def _random_explain(seed, n_place, *, k=5, n_rows=64, labels=("a", "b"),
                    pad_constraints=2, tail=0, stray_rows=False,
                    holes=False, zero_parts=False, distinct=False,
                    device_pool=False, ports=False, device_plugin=False,
                    ties=False):
    """Seeded PlacementExplain leaves as the kernel shapes them (M ≥
    n_place placements, the rest padding that nothing may read; top-K
    rows distinct and in descending score order), the node table and
    the dimension names."""
    from nomad_tpu.kernels.placement import PlacementExplain

    rng = np.random.default_rng(seed)
    m = n_place + 3                      # the bucket's padding
    rows = [f"node-{i:03d}" for i in range(n_rows)]
    if holes:                            # removed nodes leave None rows
        for i in rng.choice(n_rows, n_rows // 3, replace=False):
            rows[i] = None
    dim_names = list(_DIM_NAMES)
    if device_pool:
        dim_names[4] = "devices: nvidia/gpu"
    c = len(labels) + pad_constraints
    filt_constraint = rng.integers(0, 4, c).astype(np.int32)
    filt_constraint[len(labels):] = 7    # padding: must never be read
    exh_dim = np.zeros((m, 8), np.int32)
    exh_dim[:, 0] = rng.integers(0, 3, m)
    exh_dim[:, 1] = rng.integers(0, 2, m) * 5
    if device_pool:
        exh_dim[:, 4] = rng.integers(0, 3, m)
    topk_idx = np.stack([rng.permutation(n_rows)[:k] for _ in range(m)]
                        ).astype(np.int32)
    raw = rng.random((m, k)).astype(np.float32)
    if ties:
        raw = np.round(raw * 4) / 4
    topk_score = -np.sort(-raw, axis=1)
    topk_parts = (rng.random((m, k, 5)).astype(np.float32) - 0.5) * \
        (rng.random((m, k, 5)) < 0.6)
    if zero_parts:                       # exactly 0.0, both signs
        topk_parts[:, :, 1] = 0.0
        topk_parts[:, :, 3] = -0.0
        topk_parts[::2] = 0.0
    if tail:                             # the infeasible tail of the top-K
        topk_score[:, k - tail:] = np.float32(-1e30)
        topk_idx[:, k - 1] = -1
    if stray_rows:                       # before the table and past it
        topk_idx[::3, 1] = -1
        topk_idx[1::3, 2] = n_rows
        topk_idx[2::3, 0] = n_rows + 40
    ex = PlacementExplain(
        nodes_evaluated=np.asarray(n_rows, np.int32),
        filt_constraint=filt_constraint,
        filt_lut=np.asarray(int(filt_constraint[:len(labels)].sum()),
                            np.int32),
        filt_extra=np.asarray(3 if device_plugin else 0, np.int32),
        filt_distinct=(rng.integers(0, 3, m) if distinct
                       else np.zeros(m)).astype(np.int32),
        filt_dp=(rng.integers(0, 2, m) * 4 if distinct
                 else np.zeros(m)).astype(np.int32),
        exh_dim=exh_dim,
        exh_dyn_ports=(rng.integers(0, 3, m) if ports
                       else np.zeros(m)).astype(np.int32),
        exh_res_ports=(rng.integers(0, 2, m) * 2 if ports
                       else np.zeros(m)).astype(np.int32),
        topk_idx=topk_idx, topk_score=topk_score,
        topk_parts=topk_parts.astype(np.float32))
    return ex, list(labels), dim_names, rows


def _select_answer(ex, rows, n_place, seed, mode="leader"):
    """What select answered for these placements: (node ids, scores,
    nodes_feasible, nodes_fit). `leader`: the top-K's first node under
    its own score, as the kernel picks; the other modes are the cases
    the preemption pass and the retry selection make."""
    rng = np.random.default_rng(seed + 1)
    node_ids, scores = [], []
    named = [r for r in rows if r is not None]
    for i in range(n_place):
        row, score = int(ex.topk_idx[i, 0]), float(ex.topk_score[i, 0])
        nid = rows[row] if 0 <= row < len(rows) else None
        if mode == "outside":            # a node the top-K does not name
            top = {rows[r] for r in ex.topk_idx[i].tolist()
                   if 0 <= r < len(rows)}
            nid = next(r for r in named if r not in top)
            score = float(np.float32(rng.random()))
        elif mode == "demoted":          # first, but under a lower score
            score = float(ex.topk_score[i, 2]) - 0.125
        elif mode == "third":            # not the first of the top-K
            row = int(ex.topk_idx[i, 2])
            nid, score = rows[row], float(np.float32(rng.random() + 1.0))
        elif mode == "failed" or (mode == "fail-tail" and i >= 2):
            nid, score = None, 0.0
        node_ids.append(nid)
        scores.append(score)
    fit = rng.integers(0, 9, n_place).tolist()
    return node_ids, scores, 40, fit


_BULK_CASES = {
    "one-placement": (dict(n_place=1), "leader"),
    "eight-placements": (dict(n_place=8), "leader"),
    "thousand-placements": (dict(n_place=1000, n_rows=512), "leader"),
    "infeasible-tail": (dict(n_place=8, tail=2), "leader"),
    "whole-top-k-infeasible": (dict(n_place=4, tail=5), "failed"),
    "rows-outside-the-table": (dict(n_place=9, stray_rows=True), "leader"),
    "rows-without-a-node": (dict(n_place=12, holes=True), "leader"),
    "parts-exactly-zero": (dict(n_place=8, zero_parts=True), "leader"),
    "distinct-hosts-and-property": (dict(n_place=8, distinct=True),
                                    "leader"),
    "device-pool-dimension": (dict(n_place=8, device_pool=True), "leader"),
    "port-exhaustion": (dict(n_place=8, ports=True), "leader"),
    "device-plugin-filtered": (dict(n_place=3, device_plugin=True),
                               "leader"),
    "chosen-outside-top-k": (dict(n_place=8), "outside"),
    "chosen-outside-short-top-k": (dict(n_place=8, tail=2), "outside"),
    "chosen-demoted-by-its-score": (dict(n_place=8), "demoted"),
    "chosen-third-of-top-k": (dict(n_place=8), "third"),
    "tied-scores": (dict(n_place=16, ties=True), "demoted"),
    "failed-then-coalesced": (dict(n_place=6, distinct=True, ports=True),
                              "fail-tail"),
    "every-placement-failed": (dict(n_place=5, ports=True), "failed"),
    "padded-constraint-columns": (dict(n_place=2, pad_constraints=6,
                                       labels=("x",)), "leader"),
    "one-label-on-two-rows": (dict(n_place=2, labels=("dup", "b", "dup")),
                              "leader"),
    "no-constraint-rows": (dict(n_place=2, labels=(), pad_constraints=4),
                           "leader"),
    "top-k-of-seven": (dict(n_place=8, k=7), "outside"),
    "top-k-of-seven-failed": (dict(n_place=3, k=7), "failed"),
    "everything-at-once": (dict(n_place=40, tail=1, stray_rows=True,
                                holes=True, zero_parts=True, distinct=True,
                                device_pool=True, ports=True,
                                device_plugin=True, ties=True), "fail-tail"),
}


class TestBulkConversion:
    """explain_columns + _group_metrics + score_selected give the
    AllocMetric the element-by-element conversion gave."""

    @pytest.mark.parametrize("case", sorted(_BULK_CASES))
    def test_equals_plain_conversion(self, case):
        knobs, mode = _BULK_CASES[case]
        by_dc = {"dc1": 30, "dc2": 34}
        for seed in (SEED, SEED + 1, 2**31 + 5):
            ex, labels, dims, rows = _random_explain(seed, **knobs)
            sel = _select_answer(ex, rows, knobs["n_place"], seed, mode)
            want, want_failed = _plain_group(ex, labels, dims, rows, sel,
                                             64, by_dc)
            got, got_failed = _bulk_group(ex, labels, dims, rows, sel, 64,
                                          by_dc)
            assert len(got) == len(want) == knobs["n_place"]
            for i, (g, w) in enumerate(zip(got, want)):
                _assert_same_metric(g, w, (case, seed, i))
            _assert_same_metric(got_failed, want_failed, (case, seed))
            # no two placements share a container
            live = [m for m in got if m is not None]
            for attr in ("nodes_available", "constraint_filtered",
                         "dimension_exhausted", "score_meta"):
                assert len({id(getattr(m, attr)) for m in live}) == len(live)

    @pytest.mark.parametrize("mode", ["leader", "failed"])
    def test_without_attribution_keeps_legacy_counts(self, mode):
        """NOMAD_TPU_EXPLAIN=0 and the opted-out program: no leaves, the
        host's ready counts, and the coarse counts on a failure."""
        ex, labels, dims, rows = _random_explain(SEED, n_place=4)
        sel = _select_answer(ex, rows, 4, SEED, mode)
        want, want_failed = _plain_group(None, labels, dims, rows, sel, 64,
                                         {"dc1": 64})
        got, got_failed = _bulk_group(None, labels, dims, rows, sel, 64,
                                      {"dc1": 64})
        for i, (g, w) in enumerate(zip(got, want)):
            _assert_same_metric(g, w, (mode, i))
        _assert_same_metric(got_failed, want_failed, mode)
        if mode == "failed":
            assert got_failed.coalesced_failures == 3
            assert got_failed.nodes_filtered == 64 - 40

    def test_the_pass_stays_bulk(self):
        """The leaves are read whole: the number of NumPy element and
        slice reads does not grow with the group (the conversion before
        made ~65 scalar reads an allocation)."""

        class Counting(np.ndarray):
            reads = 0

            def __getitem__(self, key):
                Counting.reads += 1
                return super().__getitem__(key)

        def reads(n_place):
            from nomad_tpu.kernels.placement import PlacementExplain

            ex, labels, dims, rows = _random_explain(
                SEED, n_place=n_place, distinct=True, ports=True, tail=1)
            sel = _select_answer(ex, rows, n_place, SEED)
            ex = PlacementExplain(*(np.asarray(leaf).view(Counting)
                                    for leaf in ex))
            Counting.reads = 0
            got, _ = _bulk_group(ex, labels, dims, rows, sel, 64, {"dc1": 64})
            assert len(got) == n_place and all(m.score_meta for m in got)
            return Counting.reads

        from nomad_tpu.kernels.placement import PlacementExplain

        small, large = reads(8), reads(1000)
        assert small == large, (small, large)
        assert 0 < large <= 2 * len(PlacementExplain._fields), large


@pytest.fixture(scope="module")
def mixed_batch():
    """One batched drain through a Server: a program opted out of
    explain, a group that cannot be placed, a 1,000-allocation group.
    Returns, per job, the leaves its select read (None for the
    opted-out), what the select answered, the ready counts its metrics
    were made with, the eval and the stored allocations."""
    import random as _r

    from nomad_tpu.scheduler import stack as stack_mod
    from nomad_tpu.scheduler.generic import GenericScheduler
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.synth import synth_node, synth_service_job

    rng = _r.Random(SEED)
    jobs = {"opted-out": synth_service_job(rng, count=2),
            "failed": synth_service_job(rng, count=3),
            "thousand": synth_service_job(rng, count=1000)}
    for job in jobs.values():
        res = job.task_groups[0].tasks[0].resources
        res.cpu, res.memory_mb = 1, 1
    jobs["failed"].task_groups[0].tasks[0].resources.cpu = 10**7
    by_id = {job.id: name for name, job in jobs.items()}
    seen = {name: {} for name in jobs}
    leaves = threading.local()
    mp = pytest.MonkeyPatch()
    columns, select = stack_mod.explain_columns, stack_mod.TPUStack.select
    group = GenericScheduler._group_metrics

    def spy_columns(ex, labels, n_place, dim_names, rows):
        leaves.read = (ex, list(labels), list(dim_names), list(rows))
        return columns(ex, labels, n_place, dim_names, rows)

    def spy_select(self, job, tg, n_place, *args, **kwargs):
        if by_id.get(job.id) == "opted-out":
            kwargs["explain"] = False
        leaves.read = None
        res = select(self, job, tg, n_place, *args, **kwargs)
        if job.id in by_id:
            seen[by_id[job.id]].update(
                leaves=leaves.read, batched=self.coordinator is not None,
                sel=(res.node_ids, res.scores, res.nodes_feasible,
                     res.nodes_fit))
            leaves.job = by_id[job.id]
        return res

    def spy_group(ex, n, n_ready, by_dc):
        seen[leaves.job].update(n_ready=n_ready, by_dc=dict(by_dc))
        return group(ex, n, n_ready, by_dc)

    mp.delenv("NOMAD_TPU_EVAL_BATCH", raising=False)
    mp.delenv("NOMAD_TPU_EXPLAIN", raising=False)
    mp.setattr(stack_mod, "explain_columns", spy_columns)
    mp.setattr(stack_mod.TPUStack, "select", spy_select)
    mp.setattr(GenericScheduler, "_group_metrics", staticmethod(spy_group))
    s = Server(ServerConfig(num_schedulers=1, heartbeat_ttl=3600.0,
                            eval_batch=8))
    try:
        for i in range(64):
            s.state.upsert_node(synth_node(rng, i))
        evs = {name: s.job_register(job) for name, job in jobs.items()}
        s.start()
        for name, ev in evs.items():
            got = s.wait_for_eval(
                ev.id, statuses=("complete", "failed", "blocked",
                                 "cancelled"), timeout=120.0)
            assert got is not None and got.status == "complete", (name, got)
            seen[name]["eval"] = got
            seen[name]["allocs"] = list(
                s.state.allocs_by_job("default", jobs[name].id))
    finally:
        s.shutdown()
        mp.undo()
    return seen


class TestBulkConversionServed:
    """The scheduler's own placement loop on the served, batched path
    against the plain conversion of the leaves it read."""

    def _want(self, run):
        ex = labels = dims = rows = None
        if run["leaves"] is not None:
            ex, labels, dims, rows = run["leaves"]
        return _plain_group(ex, labels, dims, rows, run["sel"],
                            run["n_ready"], run["by_dc"])

    def test_one_drain_took_the_batch(self, mixed_batch):
        assert all(run["batched"] for run in mixed_batch.values())
        assert mixed_batch["opted-out"]["leaves"] is None
        assert mixed_batch["thousand"]["leaves"] is not None

    @pytest.mark.parametrize("name", ["opted-out", "thousand"])
    def test_stored_metrics_equal_plain_conversion(self, mixed_batch, name):
        run = mixed_batch[name]
        want, failed = self._want(run)
        assert failed is None and not run["eval"].failed_tg_allocs
        assert len(run["allocs"]) == len(want) == \
            {"opted-out": 2, "thousand": 1000}[name]
        # an allocation's name carries its place in the group
        index = {a.name.rsplit("[", 1)[1].rstrip("]"): a
                 for a in run["allocs"]}
        order = sorted(index, key=int)
        assert [index[i].node_id for i in order] == run["sel"][0]
        for i, w in zip(order, want):
            _assert_same_metric(index[i].metrics, w, (name, i))
        if name == "thousand":
            assert all(len(a.metrics.score_meta) == 5 for a in run["allocs"])
        else:
            # no attribution: the select's own score is all there is
            assert all([sm.node_id for sm in a.metrics.score_meta]
                       == [a.node_id] for a in run["allocs"])

    def test_failed_placement_equals_plain_conversion(self, mixed_batch):
        run = mixed_batch["failed"]
        want, failed = self._want(run)
        assert want == [None] * 3 and not run["allocs"]
        got = run["eval"].failed_tg_allocs["web"]
        _assert_same_metric(got, failed, "failed")
        assert got.coalesced_failures == 2
        assert got.dimension_exhausted == {"cpu": got.nodes_exhausted}

    def test_encoded_plan_holds_no_numpy_scalar(self, mixed_batch):
        """The 1,000-allocation group as the codec writes it (raft log,
        RPC): `to_wire` refuses a NumPy scalar, msgpack packs the rest."""
        import msgpack

        from nomad_tpu.structs.codec import to_wire

        allocs = mixed_batch["thousand"]["allocs"]
        assert len(allocs) == 1000
        for a in allocs:
            job, a.job = a.job, None     # the one tree, once is enough
            try:
                tree = to_wire(a)
            finally:
                a.job = job
            _assert_plain(tree, "alloc")
            assert msgpack.packb(tree, use_bin_type=True)


def _wait(cond, timeout=15.0, every=0.05):
    dl = time.time() + timeout
    while time.time() < dl:
        if cond():
            return True
        time.sleep(every)
    return cond()


@pytest.fixture()
def agent(tmp_path):
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api import NomadClient

    a = Agent(AgentConfig(data_dir=str(tmp_path / "data"),
                          heartbeat_ttl=60.0))
    a.start()
    api = NomadClient(a.http_addr[0], a.http_addr[1])
    assert _wait(lambda: len(api.nodes()) == 1)
    yield a, api
    a.shutdown()


def _mock_job(cpu=100, count=1):
    job = mock.job()
    tg = job.task_groups[0]
    tg.count = count
    t = tg.tasks[0]
    t.driver = "mock_driver"
    t.config = {"run_for": 0.05}
    t.resources.cpu = cpu
    return job


class TestBlockedEvalExhaustionE2E:
    """Satellite: a saturated cluster blocks an eval whose status
    reports the exhausted dimension end-to-end — broker → scheduler →
    blocked tracker → HTTP/SDK/CLI."""

    def test_blocked_eval_reports_dimension(self, agent):
        a, api = agent
        job = _mock_job(cpu=10**7)
        eval_id = api.register_job(job)
        ev = api.wait_for_eval(eval_id)
        assert ev.status == "complete"
        assert ev.failed_tg_allocs
        m = next(iter(ev.failed_tg_allocs.values()))
        assert m.dimension_exhausted.get("cpu") == 1
        assert ev.blocked_eval

        # blocked eval carries the attribution too (broker → blocked)
        blocked = api.evaluation(ev.blocked_eval)
        assert blocked.status == "blocked"
        bm = next(iter(blocked.failed_tg_allocs.values()))
        assert bm.dimension_exhausted.get("cpu") == 1

        # blocked tracker live diagnostics + metrics surface
        assert a.server.blocked.dimension_stats().get("cpu", 0) >= 1
        metrics = api.metrics()
        assert metrics["blocked_dimensions"].get("cpu", 0) >= 1
        # monotonic counter families with Prometheus exposition
        text = api.metrics_prometheus()
        assert "nomad_scheduler_exhausted_cpu" in text
        assert "nomad_scheduler_blocked_cpu" in text

        # /placement endpoint (SDK decode): failure attribution
        out = api.evaluation_placement(eval_id)
        fm = next(iter(out["failed_tg_allocs"].values()))
        assert fm.dimension_exhausted.get("cpu") == 1
        assert out["blocked_eval"] == ev.blocked_eval
        assert out["placements"] == []

    def test_placement_endpoint_for_successful_eval(self, agent):
        a, api = agent
        job = _mock_job(cpu=50, count=2)
        eval_id = api.register_job(job)
        ev = api.wait_for_eval(eval_id)
        assert ev.status == "complete"
        out = api.evaluation_placement(eval_id)
        assert len(out["placements"]) == 2
        for p in out["placements"]:
            m = p["metrics"]
            assert m.nodes_evaluated == 1
            assert m.score_meta
            assert m.score_meta[0].norm_score == pytest.approx(
                m.score_meta[0].scores["normalized-score"])

    def test_placement_endpoint_404(self, agent):
        from nomad_tpu.api import ApiError

        a, api = agent
        with pytest.raises(ApiError):
            api.evaluation_placement("no-such-eval")


class TestCliRobustness:
    """Satellite: `eval trace`, `eval placement`, `operator timeline`
    exit 1 with a one-line error on unknown/missing ids or an
    unreachable agent — never a traceback."""

    def _run(self, addr, *argv):
        import io
        import sys as _sys

        from nomad_tpu.cli import main

        out, err = io.StringIO(), io.StringIO()
        old = _sys.stdout, _sys.stderr
        _sys.stdout, _sys.stderr = out, err
        try:
            rc = main(["-address", addr, *argv])
        finally:
            _sys.stdout, _sys.stderr = old
        return rc, out.getvalue(), err.getvalue()

    def test_unknown_ids_exit_one(self, agent):
        a, api = agent
        addr = f"{a.http_addr[0]}:{a.http_addr[1]}"
        for argv in (("eval", "trace", "nope"),
                     ("eval", "placement", "nope")):
            rc, out, err = self._run(addr, *argv)
            assert rc == 1, argv
            assert err.startswith("Error:"), argv
            assert "Traceback" not in err

    def test_unreachable_agent_exits_one(self):
        # nothing listens on this port: connection errors must be a
        # one-line error, not an OSError traceback
        for argv in (("eval", "trace", "x"),
                     ("eval", "placement", "x"),
                     ("operator", "timeline"),
                     ("operator", "hbm")):
            rc, out, err = self._run("127.0.0.1:1", *argv)
            assert rc == 1, argv
            assert err.startswith("Error:"), argv

    def test_eval_placement_happy_path(self, agent):
        a, api = agent
        addr = f"{a.http_addr[0]}:{a.http_addr[1]}"
        job = _mock_job(cpu=10**7)
        eval_id = api.register_job(job)
        api.wait_for_eval(eval_id)
        rc, out, err = self._run(addr, "eval", "placement", eval_id)
        assert rc == 0, err
        assert "cpu=1" in out
        assert "Failed placements:" in out
