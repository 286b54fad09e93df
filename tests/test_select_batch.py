"""Conflict-aware eval batching (round-5 VERDICT #1).

Covers the three layers of the batched control plane:
- kernel: `place_task_group_chain` threads (used, dyn_free) across the
  program axis, so programs in one batch cannot over-commit a node
  (SURVEY §7 hard-part (e); reference analog: the optimistic worker race
  of nomad/server.go:1419 resolved at plan_apply.go:437 — here resolved
  BEFORE the plan exists).
- coordinator: concurrent selects fuse into one dispatch.
- server: a batched server places identically to a sequential one and
  meets plan-apply with zero partials when capacity suffices.
"""
import collections
import os
import random
import threading
import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.kernels.placement import (place_task_group_chain,
                                         place_task_group_jit)
from nomad_tpu.parallel.mesh import stack_params
from nomad_tpu.scheduler.stack import TPUStack
from nomad_tpu.tensor import ClusterTensors


def _mini_cluster(n_nodes=8, cpu=1000.0, mem=1024.0):
    cl = ClusterTensors()
    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.id = f"node-{i}"
        n.node_resources.cpu = int(cpu)
        n.node_resources.memory_mb = int(mem)
        cl.upsert_node(n)
        nodes.append(n)
    return cl, nodes


def _compile_one(cl, job, n_place):
    stack = TPUStack(cl)
    params, m = stack.compile_tg(job, job.task_groups[0], n_place, None)
    return stack, params, m


class TestChainKernel:
    def test_chain_accounts_across_programs(self):
        """Two programs each placing one 600-cpu alloc on nodes with 1000
        cpu: vmap (racing workers) would stack both onto the same best
        node; the chain must move program 2 to a different node."""
        cl, _ = _mini_cluster(n_nodes=4, cpu=1000.0)
        job_a, job_b = mock.job(), mock.job()
        for j in (job_a, job_b):
            j.task_groups[0].tasks[0].resources.cpu = 600
            j.task_groups[0].tasks[0].resources.memory_mb = 64
            j.task_groups[0].networks = []
        stack, pa, _ = _compile_one(cl, job_a, 1)
        _, pb, _ = _compile_one(cl, job_b, 1)
        batched, m = stack_params([pa, pb])
        arrays = stack.device_arrays()
        res = place_task_group_chain(arrays, batched, m)
        sel = np.asarray(res.sel_idx)
        a_row, b_row = int(sel[0][0]), int(sel[1][0])
        assert a_row >= 0 and b_row >= 0
        assert a_row != b_row, "chained programs over-committed one node"

    def test_chain_matches_sequential_single_dispatches(self):
        """Chain(programs) == loop of single dispatches with used folded
        in between — the chain is exactly sequential placement, fused."""
        cl, _ = _mini_cluster(n_nodes=8)
        jobs = []
        for i in range(3):
            j = mock.job()
            j.task_groups[0].tasks[0].resources.cpu = 350 + 100 * i
            j.task_groups[0].tasks[0].resources.memory_mb = 64
            j.task_groups[0].networks = []
            jobs.append(j)
        stack = TPUStack(cl)
        progs = []
        for j in jobs:
            p, _ = stack.compile_tg(j, j.task_groups[0], 2, None)
            progs.append(p)
        batched, m = stack_params(progs)
        arrays = stack.device_arrays()
        chain = np.asarray(place_task_group_chain(arrays, batched, m).sel_idx)

        # sequential oracle: single dispatches, fold new_used forward
        from nomad_tpu.parallel.mesh import pad_params

        padded, m2 = pad_params(progs)
        cur = arrays
        seq = []
        for p in padded:
            r = place_task_group_jit(cur, p, m2)
            seq.append(np.asarray(r.sel_idx))
            placed = np.asarray(r.sel_idx)
            n = np.asarray(cur.used).shape[0]
            dyn_delta = np.zeros(n, np.float32)
            for row in placed:
                if row >= 0:
                    dyn_delta[row] += float(np.asarray(p.n_dyn))
            cur = cur._replace(used=r.new_used,
                               dyn_free=np.asarray(cur.dyn_free) - dyn_delta)
        for i in range(len(progs)):
            assert list(chain[i][:2]) == list(seq[i][:2]), (
                f"program {i}: chain {chain[i][:2]} != seq {seq[i][:2]}")

    def test_inert_pad_program_passes_carry_through(self):
        """Bucket padding appends n_place=0 programs; they must leave the
        (used, dyn) carry untouched so real programs after the pad (next
        dispatch reusing the compile) place exactly as unpadded."""
        from nomad_tpu.server.select_batch import _inert_program

        cl, _ = _mini_cluster(n_nodes=4, cpu=1000.0)
        j = mock.job()
        j.task_groups[0].tasks[0].resources.cpu = 600
        j.task_groups[0].networks = []
        stack, p, _ = _compile_one(cl, j, 1)
        pad = _inert_program(p)
        batched, m = stack_params([p, pad, p])
        arrays = stack.device_arrays()
        res = place_task_group_chain(arrays, batched, m)
        sel = np.asarray(res.sel_idx)
        assert int(sel[1][0]) == -1, "pad program placed something"
        # program 3 (same ask) still accounts program 1's placement
        assert int(sel[0][0]) != int(sel[2][0])


class TestCoordinator:
    def test_concurrent_selects_fuse_into_one_dispatch(self):
        from nomad_tpu.server.select_batch import SelectCoordinator

        cl, _ = _mini_cluster(n_nodes=8)
        jobs = []
        for i in range(4):
            j = mock.job()
            j.task_groups[0].tasks[0].resources.cpu = 400
            j.task_groups[0].tasks[0].resources.memory_mb = 64
            j.task_groups[0].networks = []
            jobs.append(j)
        coord = SelectCoordinator()
        results = {}

        def one(i, job):
            stack = TPUStack(cl)
            stack.coordinator = coord
            try:
                r = stack.select(job, job.task_groups[0], 1, None)
                results[i] = r.node_ids
            finally:
                coord.thread_done()

        threads = []
        for i, j in enumerate(jobs):
            coord.add_thread()
            t = threading.Thread(target=one, args=(i, j), daemon=True)
            threads.append(t)
        for t in threads:
            t.start()
        coord.run()
        for t in threads:
            t.join(5.0)
        assert len(results) == 4
        assert all(r[0] is not None for r in results.values())
        # everything fused: far fewer dispatches than programs
        assert coord.stats["programs"] == 4
        assert coord.stats["dispatches"] <= 2

    def test_error_propagates_to_waiter(self):
        from nomad_tpu.server.select_batch import SelectCoordinator

        coord = SelectCoordinator()
        coord.add_thread()
        err = {}

        def one():
            try:
                coord.select(object(), "not-params", 1)
            except Exception as e:  # noqa: BLE001
                err["e"] = e
            finally:
                coord.thread_done()

        t = threading.Thread(target=one, daemon=True)
        t.start()
        coord.run()
        t.join(5.0)
        assert "e" in err


class TestServerBatchedPath:
    def _run_server(self, eval_batch, n_jobs=12, seed=7):
        from nomad_tpu.server import Server, ServerConfig
        from nomad_tpu.synth import synth_node, synth_service_job

        rng = random.Random(seed)
        s = Server(ServerConfig(num_schedulers=1, heartbeat_ttl=3600.0,
                                eval_batch=eval_batch))
        for i in range(32):
            s.state.upsert_node(synth_node(rng, i))
        jobs = [synth_service_job(rng, count=2) for _ in range(n_jobs)]
        # register BEFORE starting workers so the first drain sees a
        # deep queue and the batch path engages
        evs = [s.job_register(j) for j in jobs]
        s.start()
        try:
            for ev in evs:
                got = s.wait_for_eval(
                    ev.id, statuses=("complete", "failed", "blocked",
                                     "cancelled"), timeout=60.0)
                assert got is not None and got.status == "complete", got
            node_names = {n.id: n.name
                          for n in s.state.nodes_iter()} \
                if hasattr(s.state, "nodes_iter") else {}
            if not node_names:
                node_names = {nid: nd.name
                              for nid, nd in s.state._nodes.items()}
            placements = {}
            for ji, j in enumerate(jobs):
                for a in s.state.allocs_by_job("default", j.id):
                    # key by (job index, alloc index) and compare node
                    # NAMES: job/alloc ids are uuid-fresh per run, node
                    # names are deterministic from the seeded synth
                    placements[(ji, a.name.rsplit("[", 1)[1])] = \
                        node_names.get(a.node_id, a.node_id)
            stats = dict(s.planner.stats)
            wstats = dict(s.workers[0].batch_stats) if s.workers else {}
        finally:
            s.shutdown()
        return placements, stats, wstats

    def test_batched_equals_sequential_placements(self):
        seq, seq_stats, _ = self._run_server(eval_batch=1)
        bat, bat_stats, wstats = self._run_server(eval_batch=8)
        assert seq and set(seq) == set(bat)
        diffs = {k for k in seq if seq[k] != bat[k]}
        assert not diffs, f"{len(diffs)} placements differ: {sorted(diffs)[:5]}"
        # batch path actually engaged and fused programs
        assert wstats.get("batched", 0) > 0, wstats
        # ...with no optimistic-concurrency cost (roomy cluster)
        assert bat_stats.get("partial", 0) == 0

    def test_contended_batch_no_overcommit(self):
        """Jobs that collectively exceed one node's capacity must spread
        without partial plans: the chain resolves contention pre-plan."""
        from nomad_tpu.server import Server, ServerConfig
        from nomad_tpu.structs import Node

        s = Server(ServerConfig(num_schedulers=1, heartbeat_ttl=3600.0,
                                eval_batch=8))
        for i in range(6):
            n = mock.node()
            n.id = f"n-{i}"
            n.node_resources.cpu = 1000
            n.node_resources.memory_mb = 1024
            s.state.upsert_node(n)
        jobs = []
        for i in range(6):
            j = mock.job()
            j.id = f"contend-{i}"
            j.task_groups[0].count = 1
            j.task_groups[0].tasks[0].resources.cpu = 700
            j.task_groups[0].tasks[0].resources.memory_mb = 128
            j.task_groups[0].networks = []
            jobs.append(j)
        evs = [s.job_register(j) for j in jobs]
        s.start()
        try:
            for ev in evs:
                got = s.wait_for_eval(
                    ev.id, statuses=("complete", "failed", "blocked",
                                     "cancelled"), timeout=60.0)
                assert got is not None and got.status == "complete", got
            used_nodes = []
            for j in jobs:
                for a in s.state.allocs_by_job("default", j.id):
                    used_nodes.append(a.node_id)
            # 700-cpu allocs on 1000-cpu nodes: one per node, all placed
            assert len(used_nodes) == 6
            assert len(set(used_nodes)) == 6, used_nodes
            assert s.planner.stats.get("partial", 0) == 0
        finally:
            s.shutdown()

    def test_poisoned_eval_does_not_sink_its_batch(self, monkeypatch):
        """One scheduler crashing mid-batch must not stall the
        rendezvous: its thread dies before parking at the coordinator
        (live-count drops), the rest dispatch and complete, and the
        poisoned eval is nacked for redelivery (worker.go:105's
        per-eval error isolation, here across a fused batch)."""
        import nomad_tpu.server.worker as worker_mod
        from nomad_tpu.server import Server, ServerConfig
        from nomad_tpu.synth import synth_node, synth_service_job

        real = worker_mod.GenericScheduler
        poison_jobs = set()

        class Exploding(real):
            def process(self, eval):
                if eval.job_id in poison_jobs:
                    raise RuntimeError("poisoned eval (test)")
                return real.process(self, eval)

        monkeypatch.setattr(worker_mod, "GenericScheduler", Exploding)
        # the env knob outranks ServerConfig.eval_batch — without this a
        # stray NOMAD_TPU_EVAL_BATCH=1 would green-light the test on the
        # single-eval path without ever touching the rendezvous
        monkeypatch.delenv("NOMAD_TPU_EVAL_BATCH", raising=False)
        # pin the drain hold window: the adaptive window (capped at
        # 50ms) can close before the restore loop finishes enqueuing on
        # a loaded machine, draining the 8 evals as singles — then the
        # batched>0 assertion below tests a rendezvous that never formed
        monkeypatch.setenv("NOMAD_TPU_DRAIN_WINDOW_MS", "300")
        rng = random.Random(11)
        s = Server(ServerConfig(num_schedulers=1, heartbeat_ttl=3600.0,
                                eval_batch=8))
        for i in range(16):
            s.state.upsert_node(synth_node(rng, i))
        jobs = [synth_service_job(rng, count=2) for _ in range(8)]
        poison_jobs.add(jobs[3].id)
        evs = [s.job_register(j) for j in jobs]
        s.start()
        try:
            for i, ev in enumerate(evs):
                if i == 3:
                    continue
                got = s.wait_for_eval(
                    ev.id, statuses=("complete", "failed", "blocked",
                                     "cancelled"), timeout=60.0)
                assert got is not None and got.status == "complete", \
                    (i, got)
            # every healthy job fully placed
            for i, j in enumerate(jobs):
                want = 0 if i == 3 else 2
                assert len(s.state.allocs_by_job("default", j.id)) == want
            # the poisoned eval was redelivered (nack -> dequeue again),
            # never completed
            deadline = time.time() + 30.0
            while time.time() < deadline:
                if s.broker._dequeues.get(evs[3].id, 0) >= 2:
                    break
                time.sleep(0.05)
            assert s.broker._dequeues.get(evs[3].id, 0) >= 2
            got = s.state.eval_by_id(evs[3].id)
            assert got is None or got.status != "complete"
            # the batch path actually engaged (fused programs ran).
            # Polled: evals flip to complete inside sched.process,
            # BEFORE finish_batch collects the futures and writes the
            # worker.*.batch.* counters — an immediate read here races
            # that write by a few milliseconds on a loaded machine
            deadline = time.time() + 10.0
            while time.time() < deadline:
                if s.workers[0].batch_stats.get("batched", 0) > 0:
                    break
                time.sleep(0.05)
            assert s.workers[0].batch_stats.get("batched", 0) > 0
        finally:
            s.shutdown()


# ---- the one table-dispatch body: chain and wave ----

class _P(collections.namedtuple(
        "_P", "tag n_place delta_idx delta_res pclr_idx pclr_port "
        "pset_idx pset_port")):
    """The fields `_inert_program` touches, plus a tag."""


class _R:
    def __init__(self, order):
        self.order = order
        self.params = _P(order, np.int32(1), np.array([3]), np.ones((1, 4)),
                         np.array([2]), np.array([80]), np.array([1]),
                         np.array([81]))


def _lanes_of(sizes):
    """Lanes as `_wave_lanes` cuts them from conflict groups of `sizes`
    (one group: the chain's single lane)."""
    from nomad_tpu.server.select_batch import SelectCoordinator

    coord = SelectCoordinator()
    reqs, gid_of = [], {}
    for gid, n in enumerate(sizes):
        for _ in range(n):
            gid_of[len(reqs)] = gid
            reqs.append(_R(len(reqs)))
    if len(sizes) > 1:
        coord.group_ids = gid_of
    return coord._wave_lanes(reqs), reqs, gid_of


class TestTableLayout:
    @pytest.mark.parametrize("sizes,shape,lane_lens", [
        ((3,), None, [3]),
        ((3, 1), (2, 4), [3, 1]),
        # nine groups onto 8 lanes, longest first onto the least loaded:
        # the ninth joins a one-program lane, none grows past the longest
        ((3, 2, 2, 1, 1, 1, 1, 1, 1), (8, 4), [3, 2, 2, 2, 1, 1, 1, 1]),
    ], ids=["one-lane", "two-unequal-lanes", "nine-groups-eight-lanes"])
    def test_layout(self, sizes, shape, lane_lens):
        from nomad_tpu.server.select_batch import _table_layout

        lanes, reqs, gid_of = _lanes_of(sizes)
        assert sorted(map(len, lanes), reverse=True) == lane_lens
        out, params, idxs, got_shape, lanes_idx = _table_layout(lanes)
        assert got_shape == shape
        assert sorted(r.order for r in out) == list(range(len(reqs)))
        assert [len(l) for l in lanes_idx] == [len(l) for l in lanes]
        assert [j for l in lanes_idx for j in l] == list(range(len(out)))
        if shape is None:
            assert idxs is None and out == reqs
            slots = list(range(len(out)))
            assert len(params) == 4          # _bucket(3, lo=2)
        else:
            n_lanes, lane_len = shape
            assert len(params) == n_lanes * lane_len
            slots = idxs
            for li, lane in enumerate(lanes_idx):
                assert [idxs[j] for j in lane] == \
                    [li * lane_len + p for p in range(len(lane))]
                # a conflict group never straddles two lanes
                for j in lane:
                    assert all(gid_of[out[k].order] != gid_of[out[j].order]
                               for k in range(len(out)) if k not in lane)
        for j, r in enumerate(out):
            assert params[slots[j]] is r.params
        for s in set(range(len(params))) - set(slots):
            pad = params[s]
            assert pad.n_place == 0 and pad.tag == out[0].params.tag
            assert (pad.delta_idx == -1).all() and not pad.delta_res.any()
            assert (pad.pclr_idx == -1).all() and (pad.pset_idx == -1).all()


    @pytest.mark.parametrize("sizes", [
        (2, 2) + (1,) * 28,                  # 32 evals, 30 partitions
        (3, 2, 2, 2, 2) + (1,) * 21,         # 26 groups, 32 evals
        (5, 3, 3) + (1,) * 21,               # one long group: 24 groups
        (9,) + (1,) * 23,                    # a lane to itself, bucket 16
        (1,) * 30,                           # 30 evals, 30 partitions
    ], ids=["30-groups", "26-groups", "24-groups", "one-long-group",
            "30-singles"])
    def test_many_groups_share_eight_lanes(self, sizes):
        """ISSUE 36: more disjoint groups than `_MAX_WAVE_LANES`. The
        groups go longest first onto the least loaded of 8 lanes, so the
        longest lane — the wave's serial depth — is LPT's, and every
        program gets a slot of its own in the (8, bucket) axis."""
        import heapq

        from nomad_tpu.server.select_batch import _bucket, _table_layout

        lanes, reqs, gid_of = _lanes_of(sizes)
        assert len(lanes) == 8
        loads = [0] * 8
        heapq.heapify(loads)
        for n in sorted(sizes, reverse=True):   # LPT, on its own
            heapq.heappush(loads, heapq.heappop(loads) + n)
        assert sorted(map(len, lanes)) == sorted(loads)
        longest = max(loads)
        assert longest <= max(max(sizes), -(-sum(sizes) // 8) + 1)
        out, params, idxs, shape, lanes_idx = _table_layout(lanes)
        assert shape == (8, _bucket(longest, lo=2))
        assert len(params) == shape[0] * shape[1]
        # a permutation into the slots: none twice, none outside
        assert len(set(idxs)) == len(idxs) == len(reqs)
        assert all(0 <= i < len(params) for i in idxs)
        assert sorted(r.order for r in out) == list(range(len(reqs)))
        for li, lane in enumerate(lanes_idx):
            assert [idxs[j] for j in lane] == \
                [li * shape[1] + p for p in range(len(lane))]
            # whole groups: none straddles two lanes, and inside a lane
            # a group's programs keep their order
            orders = [out[j].order for j in lane]
            for gid in {gid_of[o] for o in orders}:
                mine = [o for o in orders if gid_of[o] == gid]
                assert mine == sorted(mine)
                assert len(mine) == sizes[gid]
        for j, r in enumerate(out):
            assert params[idxs[j]] is r.params
        assert sum(1 for p in params if p.n_place == 0) \
            == len(params) - len(reqs)


class _SpanLog:
    """The tracer half a coordinator uses: no dispatch records a span in
    it (the timeline's record holds the intervals, once a dispatch)."""

    def __init__(self):
        self.phases = []

    def record(self, tid, phase, start=None, end=None):
        self.phases.append((tid, phase))

    def binding(self, tid):
        return None


def _table_dispatch(wave, monkeypatch=None, miss=None):
    """Two dc-pinned programs (disjoint footprints) handed straight to
    `_dispatch_table`, as a chain or — with their conflict groups
    known — as a wave. Returns (ok, coord, reqs, cluster, registry)."""
    from nomad_tpu.lib.metrics import MetricsRegistry
    from nomad_tpu.lib.transfer import DispatchTimeline, default_ledger
    from nomad_tpu.server.program_table import table_for
    from nomad_tpu.server.select_batch import SelectCoordinator, _SelectReq
    from tests.test_spec import _dc_cluster, _dc_job

    cl = _dc_cluster()
    reqs = []
    for i, dc in enumerate(("dc1", "dc2")):
        job = _dc_job(dc)
        stack = TPUStack(cl)
        params, _m = stack.compile_tg(job, job.task_groups[0], 1, None)
        reqs.append(_SelectReq(stack.device_arrays, params, 1, i))
    reg = MetricsRegistry()
    coord = SelectCoordinator(tracer=_SpanLog(), registry=reg,
                              timeline=DispatchTimeline(reg))
    coord.trace_ids = {0: "e0", 1: "e1"}
    if wave:
        coord.group_ids = {0: 0, 1: 1}
    if miss == "prepare":
        monkeypatch.setattr(table_for(cl), "prepare", lambda pl: None)
    elif miss == "commit":
        monkeypatch.setattr(table_for(cl), "commit", lambda prep, led: None)
    led = default_ledger()
    ok = coord._dispatch_table(reqs, cl, False, led,
                               coord._kernel_done_factory(led),
                               spec=miss == "spec-view")
    return ok, coord, reqs, cl, reg


def _leases(cl):
    from nomad_tpu.scheduler import stack as stack_mod

    return set((stack_mod._DEV_CACHE.get(cl) or {}).get("leases", ()))


class TestOneTableDispatchBody:
    @pytest.mark.parametrize("wave", [False, True], ids=["chain", "wave"])
    def test_dispatch_leaves_the_same_record(self, wave):
        from nomad_tpu.scheduler import stack as stack_mod

        ok, coord, reqs, cl, reg = _table_dispatch(wave)
        assert ok == "launched"
        assert coord.stats["batched"] == 2 and coord.stats["pack_bytes"] > 0
        # the dispatch's intervals are on its record, once, with the ids
        # of the evals that rode it — none was copied to each eval
        assert coord.tracer.phases == []
        for e in ("e0", "e1"):
            assert [p for p, _s, _e in coord.timeline.eval_spans(e)] == \
                ["pack", "delta_apply"]
        assert coord.timeline.eval_spans("e2") == []
        holder, _i, token = reqs[0].out
        assert all(r.event.is_set() and r.out[0] is holder
                   and r.out[2] == token for r in reqs)
        # released at launch: the view stays leased, and the carry note
        # under the dispatch's token is not adoptable yet
        assert _leases(cl) == {token}
        note = stack_mod._DEV_CACHE[cl]["carry"]
        assert note["token"] == token and note["predicted"] is None
        assert note["evals"] == {"e0", "e1"}
        sel = holder.resolve()[0]
        # the first resolve lands the kernel: prediction in, lease out
        assert _leases(cl) == set()
        assert {int(sel[r.out[1]][0]) for r in reqs} == \
            set().union(*note["predicted"].values())
        assert all(int(sel[r.out[1]][0]) >= 0 for r in reqs)
        _, (rec,) = coord.timeline.records_after(0)
        assert (rec["programs"], rec["batched"], rec["speculative"]) == \
            (2, True, False)
        assert rec["transfer_count"] >= 4 + len(holder.resolve())
        # read: the kernel's interval joins the evals' traces too
        for e in ("e0", "e1"):
            assert [p for p, _s, _e in coord.timeline.eval_spans(e)] == \
                ["pack", "delta_apply", "kernel"]
        for k in ("pack_ms", "upload_ms", "view_ms", "host_ms", "kernel_ms",
                  "launch_ms", "release_ms", "wake_ms", "fetch_block_ms"):
            assert rec[k] is not None and rec[k] >= 0.0, k
        c = reg.counters()
        assert c.get("pipeline.dispatches") == 1
        assert c.get("pipeline.programs") == 2
        assert c.get("wave.dispatches", 0) == (1 if wave else 0)
        assert c.get("wave.programs", 0) == (2 if wave else 0)

    @pytest.mark.parametrize("miss,why", [("prepare", "pack"),
                                          ("commit", "pack"),
                                          ("spec-view", "view")])
    @pytest.mark.parametrize("wave", [False, True], ids=["chain", "wave"])
    def test_miss_says_why_untouched(self, wave, miss, why, monkeypatch):
        ok, coord, reqs, cl, reg = _table_dispatch(wave, monkeypatch, miss)
        assert ok == why
        assert coord.tracer.phases == []
        assert coord.stats == {"dispatches": 0, "programs": 0,
                               "batched": 0, "pack_bytes": 0}
        assert _leases(cl) == set()
        assert coord._spec is None
        assert not any(r.event.is_set() or r.out for r in reqs)
        assert coord.timeline.records_after(0)[1] == []
        assert not reg.counters()
