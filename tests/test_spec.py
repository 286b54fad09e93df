"""Speculative wave dispatch against the predicted carry (ISSUE 15).

Covers the four layers of the speculation path:

- chain bookkeeping (`scheduler/stack.py spec_chain_*`): predicted-view
  construction from the head carry, fold-on-advance, the cumulative
  stale-row certification math (covered windows vs foreign mutations vs
  phantom placements vs port mutations), unprovability (node churn,
  unresolved dispatches), ring-wrap immunity via the commit-window
  observer, and reset hygiene;
- coordinator state machine (`server/select_batch.py`): the
  certification → per-lane-prefix rollback mapping (exact
  `spec.redispatch_programs` counting), the adaptive gate, and the env
  opt-outs;
- dispatch parity: a speculative dispatch certified clean is
  BIT-IDENTICAL (node ids + scores) to the same batch dispatched
  sequentially against the committed view, and a forced conflict rolls
  back ONLY the affected lanes while still converging to the
  sequential run's placements;
- timeline honesty (`lib/transfer.py`): a rolled-back speculative
  kernel counts as wasted device time, never as useful overlap;
- server e2e: the worker-pipelined feed with speculation on vs off
  places identically, with launches/certifications observed.
"""
import random
import threading
import time
import uuid

import numpy as np
import pytest

import tests.test_program_table as tpt
from nomad_tpu import mock
from nomad_tpu.lib.metrics import MetricsRegistry
from nomad_tpu.scheduler import stack as stack_mod
from nomad_tpu.scheduler.stack import TPUStack
from nomad_tpu.server.select_batch import (SelectCoordinator, SpecGate,
                                           spec_enabled)
from nomad_tpu.structs import Allocation
from nomad_tpu.mock import alloc_resources


def _seed_chain(cl, token=9101, evals=("e1",), predicted=None,
                stops=()):
    """Populate the device cache + a carry note the chain can seed
    from; fabricated carry buffers (values are irrelevant to the
    bookkeeping under test — certification is host-side row math)."""
    import jax.numpy as jnp

    stack = TPUStack(cl)
    arrays = stack.device_arrays()
    u = jnp.asarray(np.asarray(arrays.used))
    d = jnp.asarray(np.asarray(arrays.dyn_free))
    stack_mod.note_dispatch_carry(cl, token, arrays, list(evals),
                                  set(stops), u, d)
    if predicted is not None:
        stack_mod.carry_predicted(cl, token, predicted)
    return arrays, u, d


def _commit_window(cl, eid, rows, token, clean=True, exact=True):
    """Mimic one plan commit: hot-log the rows, bump, mark the window
    (tests own the cluster — no concurrency, no mutation lock)."""
    v0 = cl.version
    if rows:
        cl._log_hot(*rows)
    cl.version += 1
    cl.mark_plan_window(eid, v0, cl.version, clean=clean, exact=exact,
                        token=token)


class TestSpecGate:
    def test_enabled_env(self, monkeypatch):
        monkeypatch.delenv("NOMAD_TPU_SPECULATE", raising=False)
        assert spec_enabled()
        monkeypatch.setenv("NOMAD_TPU_SPECULATE", "0")
        assert not spec_enabled()
        monkeypatch.setenv("NOMAD_TPU_SPECULATE", "off")
        assert not spec_enabled()

    def test_storm_disarms_and_cooldown_rearms(self):
        g = SpecGate(threshold=0.5)
        assert g.armed()
        for _ in range(SpecGate.MIN_SAMPLES):
            g.record(True)
        assert not g.armed()
        # disarmed for COOLDOWN opportunities, then re-arms clean
        for _ in range(SpecGate.COOLDOWN):
            assert not g.armed()
        assert g.armed()

    def test_healthy_stream_stays_armed(self):
        g = SpecGate(threshold=0.5)
        for _ in range(64):
            g.record(False)
            assert g.armed()

    def test_consecutive_misses_disarm(self):
        """A host whose successor batches never park in time must stop
        paying the rendezvous wait — consecutive launch-attempt misses
        disarm exactly like a rollback storm."""
        g = SpecGate(threshold=0.5)
        for _ in range(SpecGate.MISS_LIMIT - 1):
            g.record_miss()
            assert g.armed()
        g.record_miss()
        assert not g.armed()
        # a real launch clears the miss streak
        g2 = SpecGate(threshold=0.5)
        for _ in range(SpecGate.MISS_LIMIT - 1):
            g2.record_miss()
        g2.record(False)
        for _ in range(SpecGate.MISS_LIMIT - 1):
            g2.record_miss()
            assert g2.armed()

    def test_env_threshold(self, monkeypatch):
        monkeypatch.setenv("NOMAD_TPU_SPEC_ROLLBACK_MAX", "1.0")
        g = SpecGate()
        for _ in range(32):
            g.record(True)
        assert g.armed()  # ratio can never exceed 1.0


class TestSpecChain:
    def test_view_built_from_head_carry_and_leased(self):
        cl = tpt._mini_cluster()
        arrays, u, d = _seed_chain(cl, predicted={"e1": {2}})
        view = stack_mod.spec_chain_view(cl, lease_token=505)
        assert view is not None
        assert view.used is u and view.dyn_free is d
        assert view.capacity is arrays.capacity
        assert view.node_ok is arrays.node_ok
        # lease registered atomically with the build
        with stack_mod._DEV_CACHE_LOCK:
            assert 505 in stack_mod._DEV_CACHE[cl]["leases"]
        stack_mod.release_view(cl, 505)
        assert stack_mod.spec_chain_head_token(cl) == 9101
        stack_mod.release_view(cl, 1)
        stack_mod.spec_chain_reset(cl)

    def test_no_carry_note_no_view(self):
        cl = tpt._mini_cluster()
        TPUStack(cl).device_arrays()
        assert stack_mod.spec_chain_view(cl, lease_token=1) is None

    def test_certify_clean_commit_is_empty(self):
        cl = tpt._mini_cluster()
        _seed_chain(cl, predicted={"e1": {2}})
        assert stack_mod.spec_chain_view(cl, lease_token=1) is not None
        import jax.numpy as jnp

        u2 = jnp.zeros_like(jnp.asarray(np.asarray(cl.used),
                                        dtype=np.float32))
        stack_mod.spec_chain_advance(cl, 9202, ["e2"], set(), u2, u2)
        _commit_window(cl, "e1", {2}, 9101)
        assert stack_mod.spec_chain_certify(cl) == frozenset()
        stack_mod.release_view(cl, 1)
        stack_mod.spec_chain_reset(cl)

    def test_certify_accumulates_foreign_ports_and_stops(self):
        cl = tpt._mini_cluster()
        _seed_chain(cl, predicted={"e1": {2}}, stops={7})
        assert stack_mod.spec_chain_view(cl, lease_token=1) is not None
        import jax.numpy as jnp

        z = jnp.zeros(1)
        stack_mod.spec_chain_advance(cl, 9202, ["e2"], set(), z, z)
        _commit_window(cl, "e1", {2}, 9101)
        # foreign mutation: hot rows with no covering window
        cl._log_hot(3)
        cl.version += 1
        # port flip: never modeled by the carry
        cl._log_ports(4, word=1)
        cl.ports_version += 1
        stale = stack_mod.spec_chain_certify(cl)
        # stop row 7 went stale at fold; 3 foreign; 4 ports
        assert stale == frozenset({3, 4, 7})
        # stale is CUMULATIVE: a later certify still reports them
        stack_mod.carry_predicted(cl, 9202, {"e2": set()})
        stack_mod.spec_chain_advance(cl, 9303, ["e3"], set(), z, z)
        _commit_window(cl, "e2", set(), 9202)
        assert stack_mod.spec_chain_certify(cl) == frozenset({3, 4, 7})
        stack_mod.release_view(cl, 1)
        stack_mod.spec_chain_reset(cl)

    def test_uncommitted_predictions_go_stale(self):
        cl = tpt._mini_cluster()
        _seed_chain(cl, predicted={"e1": {5, 6}})
        assert stack_mod.spec_chain_view(cl, lease_token=1) is not None
        import jax.numpy as jnp

        z = jnp.zeros(1)
        stack_mod.spec_chain_advance(cl, 9202, ["e2"], set(), z, z)
        # e1's plan never committed (no window): its predicted rows are
        # phantom usage baked into the chain view
        stale = stack_mod.spec_chain_certify(cl)
        assert stale is not None and {5, 6} <= set(stale)
        stack_mod.release_view(cl, 1)
        stack_mod.spec_chain_reset(cl)

    def test_partial_or_inexact_window_stales_predictions(self):
        cl = tpt._mini_cluster()
        _seed_chain(cl, predicted={"e1": {5}})
        assert stack_mod.spec_chain_view(cl, lease_token=1) is not None
        import jax.numpy as jnp

        z = jnp.zeros(1)
        stack_mod.spec_chain_advance(cl, 9202, ["e2"], set(), z, z)
        _commit_window(cl, "e1", {5}, 9101, exact=False)
        stale = stack_mod.spec_chain_certify(cl)
        assert stale is not None and 5 in stale
        stack_mod.release_view(cl, 1)
        stack_mod.spec_chain_reset(cl)

    def test_unresolved_expected_dispatch_is_unprovable(self):
        cl = tpt._mini_cluster()
        _seed_chain(cl, predicted=None)  # outputs never landed
        assert stack_mod.spec_chain_view(cl, lease_token=1) is not None
        import jax.numpy as jnp

        z = jnp.zeros(1)
        stack_mod.spec_chain_advance(cl, 9202, ["e2"], set(), z, z)
        assert stack_mod.spec_chain_certify(cl) is None
        stack_mod.release_view(cl, 1)
        stack_mod.spec_chain_reset(cl)

    def test_node_churn_is_unprovable(self):
        cl = tpt._mini_cluster()
        _seed_chain(cl, predicted={"e1": set()})
        assert stack_mod.spec_chain_view(cl, lease_token=1) is not None
        import jax.numpy as jnp

        z = jnp.zeros(1)
        stack_mod.spec_chain_advance(cl, 9202, ["e2"], set(), z, z)
        cl.node_version += 1
        assert stack_mod.spec_chain_certify(cl) is None
        stack_mod.release_view(cl, 1)
        stack_mod.spec_chain_reset(cl)

    def test_refresh_resets_chain(self):
        cl = tpt._mini_cluster()
        _seed_chain(cl, predicted={"e1": set()})
        assert stack_mod.spec_chain_view(cl, lease_token=1) is not None
        assert stack_mod.spec_chain_head_token(cl) == 9101
        # a real refresh rebuilds the cached arrays → base identity gone
        cl._log_hot(0)
        cl.version += 1
        TPUStack(cl).device_arrays()
        assert stack_mod.spec_chain_view(cl, lease_token=2) is None
        assert stack_mod.spec_chain_head_token(cl) is None
        stack_mod.release_view(cl, 1)

    def test_observer_survives_window_ring_wrap(self):
        cl = tpt._mini_cluster()
        _seed_chain(cl, predicted={"e1": {2}})
        assert stack_mod.spec_chain_view(cl, lease_token=1) is not None
        import jax.numpy as jnp

        z = jnp.zeros(1)
        stack_mod.spec_chain_advance(cl, 9202, ["e2"], set(), z, z)
        _commit_window(cl, "e1", {2}, 9101)
        # wrap the bounded window ring with foreign no-op commits: the
        # observer captured e1's verdict, so certification still covers
        # row 2 even though the ring forgot the window
        for i in range(cl.PLAN_WINDOW_LEN + 8):
            cl.mark_plan_window(f"x{i}", cl.version, cl.version,
                                clean=True, exact=False)
        assert stack_mod.spec_chain_certify(cl) == frozenset()
        stack_mod.release_view(cl, 1)
        stack_mod.spec_chain_reset(cl)
        assert cl.plan_window_observer is None


class _FakeHolder:
    def __init__(self):
        self.resolved = 0

    def resolve(self):
        self.resolved += 1
        return ()


class TestCertifyMapping:
    """The rollback granularity contract: a stale hit rolls back the
    affected program AND its lane suffix (later programs in a lane saw
    its placements through the in-lane carry); disjoint lanes are
    untouched; `spec.redispatch_programs` counts exactly."""

    def _spec(self, coord, cl, lanes, n):
        from nomad_tpu.server.select_batch import _SelectReq

        reqs = [_SelectReq(None, None, 1, i) for i in range(n)]
        return {"reqs": reqs, "idxs": None, "cluster": cl,
                "holder": _FakeHolder(), "token": 7, "lanes": lanes,
                "kernel_ms": 12.0, "seq": 1}

    def _run(self, monkeypatch, stale, lanes, footprints, n=4):
        cl = tpt._mini_cluster(n_nodes=4)
        reg = MetricsRegistry()
        coord = SelectCoordinator(registry=reg)
        coord.footprints = footprints
        spec = self._spec(coord, cl, lanes, n)
        monkeypatch.setattr(stack_mod, "spec_chain_certify",
                            lambda c: stale)
        redispatched = []
        monkeypatch.setattr(coord, "_dispatch",
                            lambda reqs: redispatched.extend(reqs))
        coord._certify_spec(spec)
        rolled = sorted(r.order for r in redispatched)
        certified = sorted(i for i, r in enumerate(spec["reqs"])
                           if r.event.is_set())
        return coord, spec, rolled, certified, reg

    @staticmethod
    def _mask(n, *rows):
        m = np.zeros(n, dtype=bool)
        for r in rows:
            m[r] = True
        return m

    def test_only_affected_lane_suffix_rolls_back(self, monkeypatch):
        fps = {0: self._mask(8, 0, 1), 1: self._mask(8, 2, 3),
               2: self._mask(8, 4, 5), 3: self._mask(8, 6, 7)}
        coord, spec, rolled, certified, reg = self._run(
            monkeypatch, frozenset({4}), [[0, 1], [2, 3]], fps)
        # program 2 (rows 4-5) hit → its lane suffix {2,3} rolls;
        # lane [0,1] untouched and certified with the holder
        assert rolled == [2, 3]
        assert certified == [0, 1]
        for i in certified:
            assert spec["reqs"][i].out == (spec["holder"], i, 7)
        c = reg.counters()
        assert c["spec.rolled_back"] == 1
        assert c["spec.redispatch_programs"] == 2
        assert spec["holder"].resolved == 1
        # wasted = kernel share of the rolled programs
        assert c["spec.wasted_kernel_ms"] == pytest.approx(6.0)

    def test_suffix_only_from_hit_position(self, monkeypatch):
        fps = {0: self._mask(8, 0), 1: self._mask(8, 2),
               2: self._mask(8, 4), 3: self._mask(8, 6)}
        _c, _s, rolled, certified, reg = self._run(
            monkeypatch, frozenset({2}), [[0, 1], [2, 3]], fps)
        # program 1 (row 2) at lane position 1 → only it rolls; its
        # lane predecessor 0 never saw its placement
        assert rolled == [1]
        assert certified == [0, 2, 3]
        assert reg.counters()["spec.redispatch_programs"] == 1

    def test_clean_certifies_everything(self, monkeypatch):
        fps = {i: None for i in range(4)}
        _c, spec, rolled, certified, reg = self._run(
            monkeypatch, frozenset(), [[0, 1, 2, 3]], fps)
        assert rolled == [] and certified == [0, 1, 2, 3]
        assert reg.counters()["spec.certified"] == 1
        assert spec["holder"].resolved == 0

    def test_unknown_footprint_conflicts_with_everything(self,
                                                         monkeypatch):
        fps = {0: self._mask(8, 0), 1: None}
        _c, _s, rolled, certified, reg = self._run(
            monkeypatch, frozenset({7}), [[0], [1]], fps, n=2)
        assert rolled == [1] and certified == [0]

    @pytest.mark.parametrize("stale_at,want", [
        ("dc1", [0, 1]), ("dc3", [3]), ("allocation-in-dc2", [1, 2]),
        ("past-every-mask", [0, 1, 2, 3]), ("nothing", [])])
    def test_cached_footprints_certify_as_fresh_ones(self, monkeypatch,
                                                     stale_at, want):
        """ISSUE 33: the masks `Worker.start_batch` hands the
        coordinator come from `Server._eval_footprint`, whose static
        part is cached. Cache warm or emptied before every estimate:
        the same programs keep their speculative result. Job 1 already
        runs an allocation in dc2, so its mask is a copy with that row
        set; the others are the cached arrays themselves."""
        import tests.test_footprint_cache as tfc

        s, nodes = tfc._server(n_nodes=60)
        cl = s.state.cluster
        jobs = [tfc._job(kind, k=i) for i, kind in enumerate(
            ("pinned-dc1", "pinned-dc1", "pinned-dc2", "pinned-dc3"))]
        evs = [tfc._eval_of(s, j) for j in jobs]
        bridge = next(n for n in nodes if n.datacenter == "dc2")
        s.state.upsert_alloc(tfc.synth_alloc(random.Random(4), bridge,
                                             jobs[1]))
        row_in = {dc: cl.row_of[next(n.id for n in nodes
                                     if n.datacenter == dc)]
                  for dc in ("dc1", "dc3")}
        stale = {"dc1": {row_in["dc1"]}, "dc3": {row_in["dc3"]},
                 "allocation-in-dc2": {cl.row_of[bridge.id]},
                 "past-every-mask": {cl.n_cap + 3},
                 "nothing": set()}[stale_at]

        def estimates(cold):
            out = {}
            for i, ev in enumerate(evs):
                if cold:
                    tfc._empty(s)
                out[i] = s._eval_footprint(ev)
            return out

        verdicts = []
        for cold in (True, False, False):
            fps = estimates(cold)
            _c, _s, rolled, certified, _reg = self._run(
                monkeypatch, frozenset(stale), [[0, 1], [2], [3]], fps)
            verdicts.append((rolled, certified))
        assert not fps[0].flags.writeable and fps[1].flags.writeable
        assert verdicts[0] == verdicts[1] == verdicts[2]
        assert verdicts[0] == (want, sorted(set(range(4)) - set(want)))

    def test_unprovable_rolls_back_all(self, monkeypatch):
        fps = {i: self._mask(8, i) for i in range(4)}
        _c, spec, rolled, certified, reg = self._run(
            monkeypatch, None, [[0, 1], [2, 3]], fps)
        assert rolled == [0, 1, 2, 3] and certified == []
        assert reg.counters()["spec.redispatch_programs"] == 4
        assert spec["holder"].resolved == 1


def _start_parked(cl, jobs, coord):
    """Launch one scheduler thread per job; they compile and PARK at
    the coordinator (run() not yet driven) — the successor-batch shape
    try_spec_launch expects. Returns (threads, results)."""
    results = {}

    def one(i, job):
        stack = TPUStack(cl)
        stack.coordinator = coord
        stack.coordinator_order = i
        try:
            r = stack.select(job, job.task_groups[0], 1, None)
            results[i] = (r.node_ids, [float(x) for x in r.scores],
                          r.ask, r.carry_token)
        finally:
            coord.thread_done()

    threads = []
    for i, j in enumerate(jobs):
        coord.add_thread()
        t = threading.Thread(target=one, args=(i, j), daemon=True)
        threads.append(t)
        t.start()
    deadline = time.time() + 20.0
    while time.time() < deadline:
        with coord._cv:
            if coord._parked and len(coord._parked) >= coord._live:
                return threads, results
        time.sleep(0.002)
    raise AssertionError("schedulers never parked")


def _dc_cluster(n_nodes=8, n_dcs=2):
    from nomad_tpu.tensor import ClusterTensors

    cl = ClusterTensors()
    for i in range(n_nodes):
        n = mock.node()
        n.id = f"node-{i}"
        n.datacenter = f"dc{1 + i % n_dcs}"
        n.node_resources.cpu = 4000
        n.node_resources.memory_mb = 8192
        cl.upsert_node(n)
    return cl


def _dc_job(dc, cpu=300):
    j = mock.job()
    j.datacenters = [dc]
    j.task_groups[0].tasks[0].resources.cpu = cpu
    j.task_groups[0].tasks[0].resources.memory_mb = 64
    j.task_groups[0].networks = []
    return j


def _dc_mask(cl, dc):
    m = np.zeros(cl.n_cap, dtype=bool)
    for nid, row in cl.row_of.items():
        if cl.nodes[nid].datacenter == dc:
            m[row] = True
    return m


def _foreign_alloc(node_id):
    return Allocation(
        id=uuid.uuid4().hex, namespace="default", job_id="foreign",
        task_group="web", node_id=node_id,
        allocated_resources=alloc_resources(cpu=123, memory_mb=64,
                                            disk_mb=10),
        desired_status="run", client_status="pending")


class TestSpecDispatchParity:
    """The acceptance parity gates, driven deterministically at the
    coordinator level: twin clusters run the same two rounds — one
    speculative, one sequential — and must place identically."""

    def _round2(self, cl, speculative, monkeypatch, foreign_node=None,
                rollback_max="1.0"):
        """Round 1 (dc-pinned pair) dispatch; then round 2 either
        SPECULATIVELY (launch against round 1's predicted carry, commit
        round 1, certify) or sequentially (commit round 1 first, then
        dispatch). `foreign_node` injects a conflicting foreign commit
        between launch and certification (and, on the sequential twin,
        before the dispatch — the same end state)."""
        monkeypatch.setenv("NOMAD_TPU_SPEC_ROLLBACK_MAX", rollback_max)
        r1_jobs = [_dc_job("dc1"), _dc_job("dc2")]
        r1_ids = ["r1-a", "r1-b"]
        coord1, res1 = tpt._run_round(cl, r1_jobs, eval_ids=r1_ids)
        r2_jobs = [_dc_job("dc1", cpu=250), _dc_job("dc2", cpu=250)]
        r2_ids = ["r2-a", "r2-b"]
        reg = MetricsRegistry()
        coord2 = SelectCoordinator(registry=reg)
        coord2.trace_ids = dict(enumerate(r2_ids))
        coord2.group_ids = {0: 0, 1: 1}
        coord2.footprints = {0: _dc_mask(cl, "dc1"),
                             1: _dc_mask(cl, "dc2")}
        if speculative:
            threads, res2 = _start_parked(cl, r2_jobs, coord2)
            assert coord2.try_spec_launch(cl), "speculation never armed"
            tpt._commit_round(cl, res1, r1_ids)
            if foreign_node is not None:
                cl.upsert_alloc(_foreign_alloc(foreign_node))
            coord2.run()
        else:
            tpt._commit_round(cl, res1, r1_ids)
            if foreign_node is not None:
                cl.upsert_alloc(_foreign_alloc(foreign_node))
            threads, res2 = _start_parked(cl, r2_jobs, coord2)
            coord2.run()
        for t in threads:
            t.join(30.0)
        stack_mod.spec_chain_reset(cl)
        return res2, reg.counters()

    def test_certified_spec_bit_identical_to_sequential(self,
                                                        monkeypatch):
        spec_res, c = self._round2(_dc_cluster(), True, monkeypatch)
        seq_res, _ = self._round2(_dc_cluster(), False, monkeypatch)
        assert c.get("spec.launches") == 1
        assert c.get("spec.certified") == 1
        assert not c.get("spec.rolled_back")
        for i in spec_res:
            assert spec_res[i][0] == seq_res[i][0], i   # node ids
            assert spec_res[i][1] == seq_res[i][1], i   # scores, exact

    def test_forced_conflict_rolls_back_only_affected_lane(
            self, monkeypatch):
        cl_spec = _dc_cluster()
        cl_seq = _dc_cluster()
        # a dc1 node both clusters share — the foreign commit lands
        # inside program 0's footprint, outside program 1's
        dc1_node = next(nid for nid in cl_spec.row_of
                        if cl_spec.nodes[nid].datacenter == "dc1")
        spec_res, c = self._round2(cl_spec, True, monkeypatch,
                                   foreign_node=dc1_node)
        seq_res, _ = self._round2(cl_seq, False, monkeypatch,
                                  foreign_node=dc1_node)
        assert c.get("spec.launches") == 1
        assert c.get("spec.rolled_back") == 1
        # EXACT counting: only the dc1 program re-dispatched
        assert c.get("spec.redispatch_programs") == 1
        assert c.get("spec.wasted_kernel_ms", 0) > 0
        for i in spec_res:
            assert spec_res[i][0] == seq_res[i][0], i
            assert spec_res[i][1] == seq_res[i][1], i

    def test_speculate_env_opt_out(self, monkeypatch):
        monkeypatch.setenv("NOMAD_TPU_SPECULATE", "0")
        cl = _dc_cluster()
        r1_jobs = [_dc_job("dc1"), _dc_job("dc2")]
        _coord1, res1 = tpt._run_round(cl, r1_jobs,
                                       eval_ids=["a", "b"])
        coord2 = SelectCoordinator(registry=MetricsRegistry())
        threads, res2 = _start_parked(cl, [_dc_job("dc1")], coord2)
        assert not coord2.try_spec_launch(cl)
        tpt._commit_round(cl, res1, ["a", "b"])
        coord2.run()
        for t in threads:
            t.join(30.0)
        assert res2[0][0][0] is not None

    def test_disarmed_gate_blocks_launch(self, monkeypatch):
        cl = _dc_cluster()
        from nomad_tpu.server import select_batch as sb

        g = sb._gate_for(cl)
        for _ in range(SpecGate.MIN_SAMPLES):
            g.record(True)
        r1_jobs = [_dc_job("dc1"), _dc_job("dc2")]
        _c1, res1 = tpt._run_round(cl, r1_jobs, eval_ids=["a", "b"])
        coord2 = SelectCoordinator(registry=MetricsRegistry())
        threads, res2 = _start_parked(cl, [_dc_job("dc1")], coord2)
        assert not coord2.try_spec_launch(cl)
        tpt._commit_round(cl, res1, ["a", "b"])
        coord2.run()
        for t in threads:
            t.join(30.0)
        assert res2[0][0][0] is not None


def _drive_chain(cl, monkeypatch, k=3, reg=None):
    """Round 0 REAL dispatch, then k speculative rounds: each round's
    batch parks, launches against the chain view, the predecessor's
    plans commit, and the coordinator certifies CLEAN — publishing the
    chain HEAD carry to the view cache every round (ISSUE 20). Returns
    (reg, last_res, last_ids) with the FINAL speculative round's plans
    still uncommitted — the caller decides how the chain ends."""
    monkeypatch.setenv("NOMAD_TPU_SPEC_ROLLBACK_MAX", "1.0")
    reg = reg if reg is not None else MetricsRegistry()
    prev_ids = ["c0-a", "c0-b"]
    _c0, prev_res = tpt._run_round(
        cl, [_dc_job("dc1"), _dc_job("dc2")], eval_ids=prev_ids)
    for n in range(1, k + 1):
        ids = [f"c{n}-a", f"c{n}-b"]
        jobs = [_dc_job("dc1", cpu=100 + 10 * n),
                _dc_job("dc2", cpu=100 + 10 * n)]
        coord = SelectCoordinator(registry=reg)
        coord.trace_ids = dict(enumerate(ids))
        coord.group_ids = {0: 0, 1: 1}
        coord.footprints = {0: _dc_mask(cl, "dc1"),
                            1: _dc_mask(cl, "dc2")}
        threads, res = _start_parked(cl, jobs, coord)
        assert coord.try_spec_launch(cl), f"round {n} never speculated"
        tpt._commit_round(cl, prev_res, prev_ids)
        coord.run()
        for t in threads:
            t.join(30.0)
        # _start_parked results carry scores; _commit_round wants
        # (node_ids, ask, carry_token)
        prev_res = {i: (r[0], r[2], r[3]) for i, r in res.items()}
        prev_ids = ids
    return reg, prev_res, prev_ids


class TestChainCarryAdoption:
    """Certified chain-carry adoption (ISSUE 20): a view refresh
    landing mid-chain or post-chain consumes the published chain HEAD
    carry and pays only the genuinely-foreign delta — never a full
    resync of spec-committed rows — while staying bit-identical to a
    cold full upload."""

    @staticmethod
    def _delta(led0, led1, site):
        return (led1.get(site, {}).get("bytes", 0)
                - led0.get(site, {}).get("bytes", 0))

    @staticmethod
    def _saved():
        from nomad_tpu.lib.metrics import default_registry
        return default_registry().counters(
            prefix="spec.").get("resync_bytes_saved", 0)

    def _parity(self, arrays, cl):
        view = tpt._np_view(arrays)
        cold = tpt._cold_view(cl)
        for f, a in view.items():
            assert np.array_equal(a, cold[f]), \
                f"adopted view diverges from cold upload in {f}"

    def test_zero_resync_refresh_after_certified_chain(self,
                                                       monkeypatch):
        """The acceptance gate: ≥3 consecutive certified-clean
        speculative dispatches, final plans committed, then a refresh
        under transfer_guard('disallow') with ZERO hot-upload bytes,
        view.chain_adopts ≥ 1, and bit-identical adoption."""
        from nomad_tpu.lib.transfer import default_ledger, guard_scope

        cl = _dc_cluster()
        reg, last_res, last_ids = _drive_chain(cl, monkeypatch, k=3)
        c = reg.counters()
        assert c.get("spec.launches", 0) >= 3
        assert c.get("spec.certified", 0) >= 3
        assert not c.get("spec.rolled_back", 0)
        tpt._commit_round(cl, last_res, last_ids)
        led0 = default_ledger().snapshot()
        adopts0 = tpt._counter("chain_adopts")
        rows0 = tpt._counter("chain_rows")
        saved0 = self._saved()
        with guard_scope("disallow"):
            arrays = TPUStack(cl).device_arrays()
        led1 = default_ledger().snapshot()
        for site in ("stack.hot_full", "stack.hot_delta",
                     "stack.static_full", "stack.ports_full"):
            assert self._delta(led0, led1, site) == 0, \
                f"chained steady state shipped bytes at {site}"
        assert tpt._counter("chain_adopts") == adopts0 + 1
        assert tpt._counter("chain_rows") > rows0
        assert self._saved() > saved0
        self._parity(arrays, cl)

    def test_mid_chain_refresh_overlays_inflight_head(self,
                                                      monkeypatch):
        """A refresh landing MID-chain (head dispatch's plans not yet
        committed) still adopts: the head's in-flight placements are
        phantoms until their windows commit, so they overlay from host
        instead of poisoning the proven prefix."""
        cl = _dc_cluster()
        _reg, _res, _ids = _drive_chain(cl, monkeypatch, k=3)
        # final round NOT committed — its predictions are uncovered
        adopts0 = tpt._counter("chain_adopts")
        arrays = TPUStack(cl).device_arrays()
        assert tpt._counter("chain_adopts") == adopts0 + 1
        self._parity(arrays, cl)

    def test_foreign_churn_after_chain_pays_only_delta(self,
                                                       monkeypatch):
        """Foreign mutations + a port-bitmap flip after the chain:
        adoption overlays exactly the foreign rows (hot_delta > 0,
        hot_full == 0) and stays bit-identical."""
        from nomad_tpu.lib.transfer import default_ledger

        cl = _dc_cluster()
        _reg, last_res, last_ids = _drive_chain(cl, monkeypatch, k=3)
        tpt._commit_round(cl, last_res, last_ids)
        dc1_node = next(nid for nid in cl.row_of
                        if cl.nodes[nid].datacenter == "dc1")
        cl.upsert_alloc(_foreign_alloc(dc1_node))
        # real port-bitmap flip on a row the chain never touched
        prow = cl.row_of[dc1_node]
        cl._log_ports(prow, word=3)
        cl.ports_used[prow, 3] ^= np.uint32(1)
        cl.ports_version += 1
        led0 = default_ledger().snapshot()
        adopts0 = tpt._counter("chain_adopts")
        arrays = TPUStack(cl).device_arrays()
        led1 = default_ledger().snapshot()
        assert tpt._counter("chain_adopts") == adopts0 + 1
        assert self._delta(led0, led1, "stack.hot_full") == 0
        assert self._delta(led0, led1, "stack.hot_delta") > 0
        self._parity(arrays, cl)

    def test_node_growth_mid_chain(self, monkeypatch):
        """Node growth mid-chain: inside the row bucket the new row
        overlays (adoption survives); growth that re-buckets n_cap
        rejects the carry (shape change) — both bit-identical."""
        cl = _dc_cluster()
        _reg, last_res, last_ids = _drive_chain(cl, monkeypatch, k=2)
        tpt._commit_round(cl, last_res, last_ids)
        n = mock.node()
        n.id = "grown-1"
        n.datacenter = "dc1"
        cl.upsert_node(n)
        adopts0 = tpt._counter("chain_adopts")
        arrays = TPUStack(cl).device_arrays()
        assert tpt._counter("chain_adopts") == adopts0 + 1
        self._parity(arrays, cl)

    def test_node_growth_rebucket_rejects_carry(self, monkeypatch):
        cl = _dc_cluster()
        _reg, last_res, last_ids = _drive_chain(cl, monkeypatch, k=2)
        tpt._commit_round(cl, last_res, last_ids)
        n_cap0 = cl.n_cap
        i = 0
        while cl.n_cap == n_cap0:
            n = mock.node()
            n.id = f"grown-{i}"
            n.datacenter = "dc2"
            cl.upsert_node(n)
            i += 1
        rejects0 = tpt._counter("chain_rejects")
        arrays = TPUStack(cl).device_arrays()
        assert tpt._counter("chain_rejects") == rejects0 + 1
        self._parity(arrays, cl)

    def test_partial_final_window_overlays_head(self, monkeypatch):
        """The final round commits INEXACT: no window vouches for the
        head's placements, so they overlay — adoption still fires for
        the proven prefix and parity holds."""
        cl = _dc_cluster()
        _reg, last_res, last_ids = _drive_chain(cl, monkeypatch, k=2)
        tpt._commit_round(cl, last_res, last_ids, exact=False)
        adopts0 = tpt._counter("chain_adopts")
        arrays = TPUStack(cl).device_arrays()
        assert tpt._counter("chain_adopts") == adopts0 + 1
        self._parity(arrays, cl)

    def test_adopt_env_opt_out(self, monkeypatch):
        monkeypatch.setenv("NOMAD_TPU_SPEC_CHAIN_ADOPT", "0")
        cl = _dc_cluster()
        _reg, last_res, last_ids = _drive_chain(cl, monkeypatch, k=2)
        tpt._commit_round(cl, last_res, last_ids)
        adopts0 = tpt._counter("chain_adopts")
        arrays = TPUStack(cl).device_arrays()
        # nothing was ever published: no adopt, no reject — the plain
        # delta/full path serviced the refresh
        assert tpt._counter("chain_adopts") == adopts0
        self._parity(arrays, cl)

    def test_randomized_churn_parity(self, monkeypatch):
        """Property sweep: random foreign mutations, partial windows,
        port flips, committed/uncommitted chain ends — the adopted (or
        rejected) view is ALWAYS bit-identical to a cold upload."""
        for seed in (3, 11, 23):
            rng = random.Random(seed)
            cl = _dc_cluster()
            _reg, last_res, last_ids = _drive_chain(
                cl, monkeypatch, k=rng.choice((1, 2, 3)))
            if rng.random() < 0.7:
                tpt._commit_round(cl, last_res, last_ids,
                                  exact=rng.random() < 0.8,
                                  clean=rng.random() < 0.8)
            for _ in range(rng.randrange(0, 4)):
                nid = rng.choice(list(cl.row_of))
                cl.upsert_alloc(_foreign_alloc(nid))
            if rng.random() < 0.5:
                row = rng.choice(list(cl.row_of.values()))
                word = rng.randrange(0, 8)
                cl._log_ports(row, word=word)
                cl.ports_used[row, word] ^= np.uint32(1)
                cl.ports_version += 1
            arrays = TPUStack(cl).device_arrays()
            self._parity(arrays, cl)


class TestDeltaLogWrap:
    """Satellite bugfix: a delta-log ring wrap mid-chain was a SILENT
    unprovable — now counted, flight-recorded with reason + sizing
    guidance, and the ring length is operator-tunable."""

    def test_env_knob_sizes_ring(self, monkeypatch):
        from nomad_tpu.tensor.cluster import (DELTA_LOG_LEN,
                                              ClusterTensors)

        monkeypatch.setenv("NOMAD_TPU_DELTA_LOG", "16")
        cl = ClusterTensors()
        assert cl.delta_log_len == 16
        for i in range(40):
            cl._log_hot(i % 4)
            cl.version += 1
        assert len(cl._hot_log) == 16
        monkeypatch.setenv("NOMAD_TPU_DELTA_LOG", "not-a-number")
        assert ClusterTensors().delta_log_len == DELTA_LOG_LEN
        monkeypatch.delenv("NOMAD_TPU_DELTA_LOG")
        assert ClusterTensors().delta_log_len == DELTA_LOG_LEN

    def test_wrap_mid_chain_counts_and_flight_records(self,
                                                      monkeypatch):
        from nomad_tpu.lib.flight import default_flight
        from nomad_tpu.lib.metrics import default_registry

        monkeypatch.setenv("NOMAD_TPU_DELTA_LOG", "8")
        cl = _dc_cluster()
        _seed_chain(cl, predicted={"e1": set()})
        assert stack_mod.spec_chain_view(cl, lease_token=1) is not None
        import jax.numpy as jnp

        z = jnp.zeros(1)
        stack_mod.spec_chain_advance(cl, 9202, ["e2"], set(), z, z)
        for i in range(12):   # wrap the 8-entry ring past the cursor
            cl._log_hot(i % 4)
            cl.version += 1
        wraps0 = default_registry().counters(
            prefix="spec.").get("chain_unprovable_wrap", 0)
        idx0 = default_flight().last_index()
        assert stack_mod.spec_chain_certify(cl) is None
        wraps1 = default_registry().counters(
            prefix="spec.").get("chain_unprovable_wrap", 0)
        assert wraps1 == wraps0 + 1
        _i, evs = default_flight().records_after(idx0)
        recs = [e for e in evs if e["type"] == "spec.rollback"
                and e.get("detail", {}).get("reason")
                == "delta_log_wrap"]
        assert recs, "wrap never flight-recorded"
        d = recs[0]["detail"]
        assert d["log"] == "hot" and d["log_len"] == 8
        assert "NOMAD_TPU_DELTA_LOG" in d["finding"]
        stack_mod.release_view(cl, 1)
        stack_mod.spec_chain_reset(cl)


class TestTimelineSpec:
    def test_rolled_back_kernel_is_wasted_not_overlap(self):
        from nomad_tpu.lib.transfer import DispatchTimeline

        reg = MetricsRegistry()
        tl = DispatchTimeline(reg)
        s1 = tl.commit(programs=1, batched=True, pack=(0.0, 0.001),
                       view=(0.001, 0.002), kernel_start=0.002,
                       transfer_bytes=0, transfer_count=0)
        tl.kernel_end(s1, 0.010)
        # speculative dispatch: host prep fully hidden under kernel 1
        s2 = tl.commit(programs=1, batched=True, pack=(0.003, 0.004),
                       view=(0.004, 0.005), kernel_start=0.005,
                       transfer_bytes=0, transfer_count=0,
                       speculative=True)
        tl.kernel_end(s2, 0.020)
        _i, recs = tl.records_after(0)
        r2 = [r for r in recs if r["seq"] == s2][0]
        assert r2["speculative"] and r2["overlap_ms"] > 0
        tl.spec_resolve(s2, "rolled_back")
        _i, recs = tl.records_after(0)
        r2 = [r for r in recs if r["seq"] == s2][0]
        assert r2["spec_outcome"] == "rolled_back"
        assert r2["overlap_ms"] == 0.0  # hiding bought nothing
        # successor overlaps under the WASTED kernel: also not a win
        s3 = tl.commit(programs=1, batched=True, pack=(0.006, 0.007),
                       view=(0.007, 0.008), kernel_start=0.021,
                       transfer_bytes=0, transfer_count=0)
        tl.kernel_end(s3, 0.025)
        _i, recs = tl.records_after(0)
        r3 = [r for r in recs if r["seq"] == s3][0]
        assert r3["overlap_ms"] == 0.0
        summ = tl.summary()
        assert summ["spec"] == {"launched": 1, "certified": 0,
                                "rolled_back": 1,
                                "wasted_kernel_ms":
                                pytest.approx(15.0)}

    def test_partial_rollback_wastes_only_its_share(self):
        """A partially certified speculative dispatch did real work:
        only the rolled share of its kernel is wasted, it stays in the
        overlap aggregates, and its own overlap is kept."""
        from nomad_tpu.lib.transfer import DispatchTimeline

        tl = DispatchTimeline(MetricsRegistry())
        s1 = tl.commit(programs=4, batched=True, pack=(0.0, 0.001),
                       view=(0.001, 0.002), kernel_start=0.002,
                       transfer_bytes=0, transfer_count=0)
        tl.kernel_end(s1, 0.010)
        s2 = tl.commit(programs=4, batched=True, pack=(0.003, 0.004),
                       view=(0.004, 0.005), kernel_start=0.005,
                       transfer_bytes=0, transfer_count=0,
                       speculative=True)
        tl.kernel_end(s2, 0.025)
        tl.spec_resolve(s2, "rolled_back", wasted_frac=0.25)
        _i, recs = tl.records_after(0)
        r2 = [r for r in recs if r["seq"] == s2][0]
        assert r2["spec_outcome"] == "rolled_back"
        assert r2["spec_wasted_frac"] == 0.25
        assert r2["overlap_ms"] > 0  # its certified slices were real
        summ = tl.summary()
        assert summ["spec"]["rolled_back"] == 1
        # 20ms kernel × 0.25 rolled share
        assert summ["spec"]["wasted_kernel_ms"] == pytest.approx(5.0)
        assert summ["overlap_ms_total"] > 0

    def test_certified_spec_counts_as_real_overlap(self):
        from nomad_tpu.lib.transfer import DispatchTimeline

        tl = DispatchTimeline(MetricsRegistry())
        s1 = tl.commit(programs=1, batched=True, pack=(0.0, 0.001),
                       view=(0.001, 0.002), kernel_start=0.002,
                       transfer_bytes=0, transfer_count=0)
        tl.kernel_end(s1, 0.010)
        s2 = tl.commit(programs=1, batched=True, pack=(0.003, 0.004),
                       view=(0.004, 0.005), kernel_start=0.005,
                       transfer_bytes=0, transfer_count=0,
                       speculative=True)
        tl.spec_resolve(s2, "certified")
        tl.kernel_end(s2, 0.012)
        summ = tl.summary()
        assert summ["spec"]["certified"] == 1
        assert summ["spec"]["wasted_kernel_ms"] == 0
        assert summ["overlap_ms_total"] > 0
        # zero device idle between kernel 1 landing and the already-
        # queued speculative kernel — the bubble_ms → 0 shape
        _i, recs = tl.records_after(0)
        r2 = [r for r in recs if r["seq"] == s2][0]
        assert r2["bubble_ms"] == 0.0


def _spec_feed(monkeypatch, speculate, n_jobs=24, eval_batch=8,
               seed=29, nodes=48):
    """One pipelined server run over a deterministic pre-enqueued
    dc-pinned feed; returns (placements, counters, planner stats)."""
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.synth import synth_node, synth_service_job

    monkeypatch.delenv("NOMAD_TPU_EVAL_BATCH", raising=False)
    monkeypatch.setenv("NOMAD_TPU_DRAIN_WINDOW_MS", "50")
    monkeypatch.setenv("NOMAD_TPU_SPEC_PARK_MS", "2000")
    monkeypatch.setenv("NOMAD_TPU_SPEC_ROLLBACK_MAX", "1.0")
    monkeypatch.setenv("NOMAD_TPU_SPECULATE",
                       "1" if speculate else "0")
    rng = random.Random(seed)
    s = Server(ServerConfig(num_schedulers=1, heartbeat_ttl=3600.0,
                            eval_batch=eval_batch))
    from nomad_tpu.lib.hbm import default_hbm

    # lease DELTA: the process-global ledger may carry leases from
    # earlier tests' clusters — only growth caused by THIS feed counts
    leases0 = default_hbm().outstanding_leases()
    for i in range(nodes):
        s.state.upsert_node(synth_node(rng, i))
    s.broker.set_enabled(False)
    jobs, evs = [], []
    for i in range(n_jobs):
        j = synth_service_job(rng, count=1,
                              datacenter=f"dc{1 + i % 3}")
        j.task_groups[0].tasks[0].resources.cpu = 50
        j.task_groups[0].tasks[0].resources.memory_mb = 64
        jobs.append(j)
        evs.append(s.job_register(j))
    s.start()
    s._restore_evals()
    try:
        for ev in evs:
            got = s.wait_for_eval(
                ev.id, statuses=("complete", "failed", "blocked",
                                 "cancelled"), timeout=120.0)
            assert got is not None and got.status == "complete", got
        node_names = {nid: nd.name for nid, nd in s.state._nodes.items()}
        placements = {}
        for ji, j in enumerate(jobs):
            for a in s.state.allocs_by_job("default", j.id):
                score = None
                for sm in a.metrics.score_meta:
                    if sm.node_id == a.node_id:
                        score = float(sm.norm_score)
                placements[(ji, a.name.rsplit("[", 1)[1])] = (
                    node_names.get(a.node_id, a.node_id), score)
        counters = dict(s.metrics.counters())
        stats = dict(s.planner.stats)
        leases = default_hbm().outstanding_leases() - leases0
    finally:
        s.shutdown()
    return placements, counters, stats, leases


class TestSpecServerE2E:
    def test_parity_speculation_on_vs_off(self, monkeypatch):
        """The ISSUE 15 server-level parity gate: the same pipelined
        feed with speculation on vs NOMAD_TPU_SPECULATE=0 — placements
        (node names + scores) identical, speculation demonstrably
        engaged, optimistic-concurrency counters flat, no leaked
        leases."""
        on, c_on, st_on, leases_on = _spec_feed(monkeypatch, True)
        off, c_off, st_off, _ = _spec_feed(monkeypatch, False)
        assert c_on.get("spec.launches", 0) >= 1, \
            "speculation never engaged"
        assert c_on.get("spec.certified", 0) >= 1
        assert not c_off.get("spec.launches", 0)
        assert on and set(on) == set(off)
        diffs = {k: (on[k], off[k]) for k in on if on[k] != off[k]}
        assert not diffs, \
            f"{len(diffs)} placements differ: {sorted(diffs.items())[:4]}"
        assert st_on.get("partial", 0) == st_off.get("partial", 0)
        assert leases_on == 0

    def test_forced_conflict_server_converges(self, monkeypatch):
        """Forced-conflict e2e: carry certification revoked for every
        dc1 plan (the offer-fail/preemption shape) in BOTH runs — the
        speculative run must roll back affected programs (counted),
        re-dispatch only them, and still place exactly like the
        sequential run."""
        from nomad_tpu.scheduler.generic import GenericScheduler

        orig = GenericScheduler._certify_carry_exact

        def revoke_dc1(self, alloc, ask):
            if list(getattr(self.job, "datacenters", ())) == ["dc1"]:
                self.plan.carry_exact = False
            else:
                orig(self, alloc, ask)

        monkeypatch.setattr(GenericScheduler, "_certify_carry_exact",
                            revoke_dc1)
        on, c_on, _st, leases_on = _spec_feed(monkeypatch, True)
        off, c_off, _st2, _ = _spec_feed(monkeypatch, False)
        assert c_on.get("spec.launches", 0) >= 1
        assert c_on.get("spec.rolled_back", 0) >= 1, \
            "forced conflict never rolled back"
        redisp = c_on.get("spec.redispatch_programs", 0)
        assert 1 <= redisp < 24, \
            f"rollback was not slice-granular: {redisp}"
        assert set(on) == set(off)
        diffs = {k: (on[k], off[k]) for k in on if on[k] != off[k]}
        assert not diffs, \
            f"{len(diffs)} placements differ: {sorted(diffs.items())[:4]}"
        assert leases_on == 0

    @pytest.mark.slow
    def test_loaded_window_soak_spec_steady_state(self, monkeypatch):
        """Soak: a 192-eval pre-enqueued window keeps the speculation
        chain healthy — launches keep happening, nothing rolls back on
        a conflict-free feed, every lease is returned."""
        on, c_on, st, leases = _spec_feed(monkeypatch, True,
                                          n_jobs=192, eval_batch=16)
        assert c_on.get("spec.launches", 0) >= 5
        assert c_on.get("spec.certified", 0) >= 5
        assert not c_on.get("spec.rolled_back", 0)
        assert st.get("partial", 0) == 0
        assert leases == 0
