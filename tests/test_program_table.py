"""Device-resident dispatch loop (ISSUE 10).

Four contracts under test, all on JAX_PLATFORMS=cpu:

- BIT-PARITY: the program-table dispatch (`place_table_chain` — static
  rows gathered on device + small dynamic rows) selects exactly what the
  legacy packed transport selects — `sel_idx`/`sel_score` bit-identical
  over randomized mixed-feature batches. The table is a transport
  optimization, never an approximation.
- TABLE MECHANICS: content-addressed dedup (steady state inserts
  nothing), caps growth flushes generations, residency ceilings fall
  back to the legacy path, LRU eviction recycles rows.
- GUARD: the steady-state table path performs ZERO unattributed
  host↔device transfers — it runs clean under
  `jax.transfer_guard("disallow")` with the ledger accounting every
  byte, and ships NO packed-program uploads (`select_batch.pack_buffers`
  stays untouched).
- D2D PLAN DELTAS: after a dispatch's plans commit clean+exact, the next
  refresh adopts the chain's device-resident (used, dyn_free) carry —
  zero `stack.hot_delta` upload for kernel-committed rows — and the
  adopted view stays BIT-IDENTICAL to a cold full upload of the host
  state. Unclean/inexact/foreign mutations must reject or overlay.
"""
import random
import threading
import uuid

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.kernels.placement import (pack_params, place_packed_chain)
from nomad_tpu.lib.metrics import default_registry
from nomad_tpu.lib.transfer import default_ledger
from nomad_tpu.mock import alloc_resources
from nomad_tpu.parallel.mesh import stack_params
from nomad_tpu.scheduler.stack import (_DEV_CACHE, TPUStack,
                                       drop_device_view)
from nomad_tpu.server.program_table import (DIM_CEILINGS,
                                            DeviceProgramTable, table_for)
from nomad_tpu.server.select_batch import SelectCoordinator
from nomad_tpu.structs import Allocation, Constraint
from nomad_tpu.tensor import ClusterTensors


def _counter(name):
    return default_registry().counters(prefix="view.").get(name, 0)


def _mini_cluster(n_nodes=12, cpu=4000.0, mem=8192.0):
    cl = ClusterTensors()
    for i in range(n_nodes):
        n = mock.node()
        n.id = f"node-{i}"
        n.node_resources.cpu = int(cpu)
        n.node_resources.memory_mb = int(mem)
        cl.upsert_node(n)
    return cl


def _job(rng, i):
    """Mixed-feature jobs: the synth flavor matrix, deterministic."""
    j = mock.job()
    j.task_groups[0].tasks[0].resources.cpu = rng.choice((100, 250, 400))
    j.task_groups[0].tasks[0].resources.memory_mb = rng.choice((64, 128))
    j.task_groups[0].networks = []
    if i % 2 == 0:
        j.constraints.append(
            Constraint("${node.datacenter}", "dc1", "="))
    if i % 3 == 0:
        from nomad_tpu.structs import Spread, SpreadTarget

        j.spreads.append(Spread(attribute="${node.datacenter}", weight=50,
                                spread_target=[
                                    SpreadTarget(value="dc1", percent=60),
                                ]))
    if i % 5 == 0:
        j.constraints.append(Constraint(operand="distinct_hosts"))
    return j


def _compile(cl, jobs, n_place=2):
    stack = TPUStack(cl)
    out = []
    for j in jobs:
        p, _m = stack.compile_tg(j, j.task_groups[0], n_place, None)
        out.append(p)
    return stack, out


class TestTableBitParity:
    def test_randomized_batches_bit_identical_to_packed_path(self):
        """The acceptance gate: table-gather dispatch == packed-upload
        dispatch, bit for bit, across randomized mixed batches."""
        rng = random.Random(17)
        cl = _mini_cluster()
        table = DeviceProgramTable()
        for round_i in range(6):
            jobs = [_job(rng, rng.randrange(12))
                    for _ in range(rng.choice((2, 3, 4)))]
            stack, params = _compile(cl, jobs)
            arrays = stack.device_arrays()

            batched, m = stack_params(params)
            ibuf, fbuf, ubuf, spec = pack_params(batched)
            legacy = place_packed_chain(arrays, ibuf, fbuf, ubuf, spec, m)
            lsel = np.asarray(legacy[0])[: len(params)]
            lscore = np.asarray(legacy[1])[: len(params)]

            prep = table.prepare(params)
            assert prep is not None
            import jax.numpy as jnp

            from nomad_tpu.kernels.placement import place_table_chain

            ti, tf, tu, _nb, _cnt = table.commit(prep, default_ledger())
            out, carry = place_table_chain(
                arrays, ti, tf, tu, jnp.asarray(prep.rows),
                jnp.asarray(prep.dyn_i), jnp.asarray(prep.dyn_f),
                jnp.asarray(prep.dyn_u), prep.sspec, prep.dspec, prep.m)
            tsel = np.asarray(out[0])[: len(params)]
            tscore = np.asarray(out[1])[: len(params)]
            # table pads to its caps (≥ the batch dims); padding is
            # semantically inert, so selection must not move a bit
            assert np.array_equal(lsel[:, :2], tsel[:, :2]), round_i
            assert np.array_equal(
                lscore[:, :2].view(np.uint32),
                tscore[:, :2].view(np.uint32)), round_i
            # churn between rounds so views/programs vary
            cl.upsert_alloc(Allocation(
                id=uuid.uuid4().hex, namespace="default",
                job_id=f"churn-{round_i}", task_group="web",
                node_id=f"node-{rng.randrange(12)}",
                allocated_resources=alloc_resources(
                    cpu=rng.randrange(10, 80), memory_mb=32, disk_mb=10),
                desired_status="run", client_status="pending"))

    def test_carry_matches_host_fold_of_selection(self):
        """The chain's (used, dyn_free) carry equals the base view plus
        the selections it reports — the invariant D2D adoption rests
        on."""
        rng = random.Random(5)
        cl = _mini_cluster()
        jobs = [_job(rng, i) for i in range(3)]
        stack, params = _compile(cl, jobs)
        arrays = stack.device_arrays()
        table = DeviceProgramTable()
        prep = table.prepare(params)
        import jax.numpy as jnp

        from nomad_tpu.kernels.placement import place_table_chain

        ti, tf, tu, _nb, _cnt = table.commit(prep, default_ledger())
        out, carry = place_table_chain(
            arrays, ti, tf, tu, jnp.asarray(prep.rows),
            jnp.asarray(prep.dyn_i), jnp.asarray(prep.dyn_f),
            jnp.asarray(prep.dyn_u), prep.sspec, prep.dspec, prep.m)
        sel = np.asarray(out[0])
        expect = np.asarray(arrays.used).copy()
        for i, p in enumerate(params):
            ask = np.asarray(p.ask, dtype=np.float32)
            for row in sel[i]:
                if row >= 0:
                    expect[int(row)] += ask
        assert np.array_equal(np.asarray(carry[0]), expect)


class TestTableMechanics:
    def test_content_dedup_steady_state_inserts_nothing(self):
        rng = random.Random(3)
        cl = _mini_cluster()
        jobs = [_job(rng, 0), _job(rng, 2)]
        _stack, params = _compile(cl, jobs)
        table = DeviceProgramTable()
        p1 = table.prepare(params)
        assert p1 is not None and table.inserts == 2
        table.commit(p1, default_ledger())
        # same job specs again (fresh compile, same content)
        _stack2, params2 = _compile(cl, jobs)
        p2 = table.prepare(params2)
        assert p2 is not None
        assert table.inserts == 2, "steady state re-inserted rows"
        assert np.array_equal(p1.rows, p2.rows)

    def test_caps_growth_flushes_generation(self):
        rng = random.Random(3)
        cl = _mini_cluster()
        _s, params = _compile(cl, [_job(rng, 0)])
        table = DeviceProgramTable()
        table.commit(table.prepare(params), default_ledger())
        gen0 = table.gen
        # a job with MANY constraints grows the c cap
        big = _job(rng, 1)
        for k in range(20):
            big.constraints.append(
                Constraint("${node.datacenter}", "dc1", "!="))
        _s2, params_big = _compile(cl, [big])
        prep = table.prepare(params_big)
        assert prep is not None
        assert table.gen > gen0, "caps growth must flush the table"
        assert table.commit(prep, default_ledger()) is not None

    def test_over_ceiling_program_falls_back(self):
        rng = random.Random(3)
        cl = _mini_cluster()
        j = _job(rng, 1)
        for k in range(DIM_CEILINGS["c"] + 1):
            j.constraints.append(
                Constraint("${node.datacenter}", f"dc-{k}", "!="))
        _s, params = _compile(cl, [j])
        assert DeviceProgramTable().prepare(params) is None

    def test_stale_generation_commit_rejected(self):
        rng = random.Random(3)
        cl = _mini_cluster()
        table = DeviceProgramTable()
        _s, params = _compile(cl, [_job(rng, 0)])
        prep = table.prepare(params)
        table._lock.acquire()
        try:
            table._flush_locked()  # caps flush races the commit
        finally:
            table._lock.release()
        assert table.commit(prep, default_ledger()) is None

    def test_lru_eviction_recycles_rows(self):
        rng = random.Random(11)
        cl = _mini_cluster()
        table = DeviceProgramTable(capacity=4)
        seen_rows = set()
        for i in range(8):
            j = _job(rng, 1)
            j.task_groups[0].tasks[0].resources.cpu = 100 + i  # unique
            _s, params = _compile(cl, [j])
            prep = table.prepare(params)
            assert prep is not None
            table.commit(prep, default_ledger())
            seen_rows.update(int(r) for r in prep.rows)
        assert seen_rows <= set(range(4)), "rows escaped the capacity"
        assert table.stats()["rows"] <= 4


    def test_a_working_set_past_the_capacity_resolves_right(self):
        """ISSUE 36's deployment: 64 partitions x 9 (cpu, memory) asks are
        576 distinct static rows, more than the 512 the table held until
        then. Through a table one row short of the set every program
        still resolves to a row that holds ITS content after eviction
        and re-insert (the rows a table with room gathers, bit for bit),
        and `hbm.table_resolved` / `hbm.table_inserts` /
        `hbm.table_evictions` count what it did; `TABLE_ROWS` itself
        holds the set whole."""
        from nomad_tpu.server.program_table import TABLE_ROWS

        assert TABLE_ROWS >= 2 * 64 * 9 - 128   # the set, with room
        cl = ClusterTensors()
        for i in range(64):
            n = mock.node()
            n.id = f"node-{i}"
            n.meta["cell"] = f"c{i % 16}"
            cl.upsert_node(n)
        asks = [(c, m) for c in (21, 37, 53) for m in (17, 29, 43)]
        jobs = []
        for k in range(16):
            for cpu, mem in asks:
                j = mock.job()
                j.task_groups[0].networks = []
                j.task_groups[0].tasks[0].resources.cpu = cpu
                j.task_groups[0].tasks[0].resources.memory_mb = mem
                j.constraints.append(Constraint("${meta.cell}", f"c{k}", "="))
                jobs.append(j)
        _stack, params = _compile(cl, jobs)
        n = len(params)                      # 144 distinct static rows
        small, roomy = DeviceProgramTable(capacity=n - 1), \
            DeviceProgramTable(capacity=2 * n)
        reg = default_registry()
        names = ("hbm.table_resolved", "hbm.table_inserts",
                 "hbm.table_evictions")

        def counted():
            c = reg.counters(prefix="hbm.")
            return [c.get(k[4:], 0) for k in names]

        def gathered(table, batch):
            prep = table.prepare(batch)
            assert prep is not None
            ti, tf, tu, _nb, _count = table.commit(prep, default_ledger())
            return [np.asarray(t)[prep.rows] for t in (ti, tf, tu)]

        for rnd in range(3):                 # the set in rotation, thrice
            for at in range(0, n, 16):
                batch = params[at:at + 16]
                want = gathered(roomy, batch)
                c0 = counted()
                got = gathered(small, batch)
                c1 = counted()
                for a, b in zip(got, want):
                    assert np.array_equal(a, b)
                d = [b - a for a, b in zip(c0, c1)]
                assert d[0] == 16
                # LRU under a rotation one past its capacity: every
                # program's row went before it came round again
                assert d[1] == 16
                assert d[2] == (16 if rnd else (1 if at + 16 >= n else 0))
        # the one flush is the first prepare's, which sized the rows
        assert small.stats()["rows"] == n - 1 and small.flushes == 1
        assert roomy.inserts == n            # with room: inserted once


def _run_round(cl, jobs, coord=None, eval_ids=None, plans=None):
    coord = coord or SelectCoordinator()
    if eval_ids:
        coord.trace_ids = dict(enumerate(eval_ids))
    results = {}

    def one(i, job):
        stack = TPUStack(cl)
        stack.coordinator = coord
        stack.coordinator_order = i   # the worker sets this in prod
        try:
            r = stack.select(job, job.task_groups[0], 1,
                             (plans or {}).get(i))
            results[i] = (r.node_ids, r.ask, r.carry_token)
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
    return coord, results


def _np_view(arrays):
    return {f: np.asarray(getattr(arrays, f)) for f in arrays._fields}


def _cold_view(cl):
    drop_device_view(cl)
    return _np_view(TPUStack(cl).device_arrays())


def _commit_round(cl, results, eval_ids, exact=True, clean=True,
                  skip_evals=(), wrong_token=False):
    """Host-commit each eval's placements the way the plan applier
    would: usage == the compiled ask, one mutation-lock-free window
    mark per eval (tests own the cluster, no concurrency), stamped
    with the dispatch token the selection reported (the plan
    carry_token binding); `wrong_token` simulates a retry plan from a
    different dispatch vouching for this carry."""
    for i, eid in enumerate(eval_ids):
        if eid in skip_evals:
            continue
        node_ids, ask, token = results[i]
        if wrong_token:
            token = (token or 0) + 10_000
        v_lo = cl.version
        for nid in node_ids:
            if nid is None:
                continue
            cl.upsert_alloc(Allocation(
                id=uuid.uuid4().hex, namespace="default",
                job_id=f"job-{eid}", task_group="web", node_id=nid,
                allocated_resources=alloc_resources(
                    cpu=int(ask[0]), memory_mb=int(ask[1]),
                    disk_mb=int(ask[2])),
                desired_status="run", client_status="pending"))
        cl.mark_plan_window(eid, v_lo, cl.version, clean=clean,
                            exact=exact, token=token)


class TestGuardAndZeroUpload:
    def test_steady_state_table_path_guard_clean_zero_pack(self,
                                                           monkeypatch):
        """Steady state: guard-disallow clean, zero packed-program
        uploads, zero kernel-attributable hot-row re-uploads — the
        ISSUE 10 acceptance triplet, counter-gated."""
        rng = random.Random(9)
        cl = _mini_cluster()
        jobs = [_job(rng, i) for i in range(4)]
        eval_ids = [f"ev-{i}" for i in range(4)]
        # round 1: cold (compiles, full uploads, table inserts)
        coord, res = _run_round(cl, jobs, eval_ids=eval_ids)
        _commit_round(cl, res, eval_ids)
        # round 2: warms carry adoption + any delta kernels
        coord, res = _run_round(cl, jobs, eval_ids=eval_ids)
        _commit_round(cl, res, eval_ids)
        led0 = default_ledger().snapshot()
        adopts0 = _counter("carry_adopts")
        monkeypatch.setenv("NOMAD_TPU_TRANSFER_GUARD", "disallow")
        coord, res = _run_round(cl, jobs, eval_ids=eval_ids)
        assert len(res) == 4 and all(r[0][0] is not None
                                     for r in res.values())
        led1 = default_ledger().snapshot()

        def delta(site):
            return (led1.get(site, {}).get("bytes", 0)
                    - led0.get(site, {}).get("bytes", 0))

        assert delta("select_batch.pack_buffers") == 0, \
            "steady state shipped a packed program"
        assert delta("select_batch.table_insert") == 0, \
            "steady state re-inserted table rows"
        assert delta("stack.hot_delta") == 0, \
            "kernel-committed rows re-uploaded from host"
        assert delta("stack.hot_full") == 0
        assert delta("select_batch.dyn_rows") > 0  # the only program tx
        assert _counter("carry_adopts") > adopts0


class TestCarryAdoption:
    def test_adopted_view_bit_identical_to_cold_upload(self):
        """Randomized rounds of dispatch → clean/exact commit → next
        dispatch adopts the carry; after every round the cached device
        view equals a cold full upload of the host state, bitwise."""
        rng = random.Random(21)
        cl = _mini_cluster()
        jobs = [_job(rng, i) for i in range(3)]
        eval_ids = [f"ev-{i}" for i in range(3)]
        stack = TPUStack(cl)
        for round_i in range(5):
            coord, res = _run_round(cl, jobs, eval_ids=eval_ids)
            _commit_round(cl, res, eval_ids)
            view = _np_view(stack.device_arrays())
            cold = _cold_view(cl)
            for f, a in view.items():
                assert a.dtype == cold[f].dtype and np.array_equal(
                    a, cold[f]), (round_i, f)
            stack.device_arrays()  # re-warm (cold dropped the entry)

    def test_adoption_happens_and_skips_upload(self):
        rng = random.Random(2)
        cl = _mini_cluster()
        jobs = [_job(rng, i) for i in range(3)]
        eval_ids = [f"ev-{i}" for i in range(3)]
        coord, res = _run_round(cl, jobs, eval_ids=eval_ids)
        _commit_round(cl, res, eval_ids)
        adopts0, rows0 = _counter("carry_adopts"), _counter("carry_rows")
        _run_round(cl, jobs, eval_ids=eval_ids)
        assert _counter("carry_adopts") == adopts0 + 1
        assert _counter("carry_rows") > rows0

    def test_inexact_commit_rejects_carry(self):
        """exact=False windows (scheduler could not certify usage==ask)
        must reject adoption — rows re-upload from host instead."""
        rng = random.Random(4)
        cl = _mini_cluster()
        jobs = [_job(rng, i) for i in range(2)]
        eval_ids = ["ev-a", "ev-b"]
        coord, res = _run_round(cl, jobs, eval_ids=eval_ids)
        _commit_round(cl, res, eval_ids, exact=False)
        rejects0 = _counter("carry_rejects")
        adopts0 = _counter("carry_adopts")
        _run_round(cl, jobs, eval_ids=eval_ids)
        assert _counter("carry_rejects") == rejects0 + 1
        assert _counter("carry_adopts") == adopts0
        # and the view still converges to host truth
        view = _np_view(TPUStack(cl).device_arrays())
        cold = _cold_view(cl)
        for f, a in view.items():
            assert np.array_equal(a, cold[f]), f

    def test_uncommitted_placement_rejects_carry(self):
        """An eval whose kernel placed but whose plan never committed
        (nack/stale token) would leave phantom usage in the carry — the
        missing window must reject adoption, and the view must match a
        cold upload (no phantom rows)."""
        rng = random.Random(6)
        cl = _mini_cluster()
        jobs = [_job(rng, i) for i in range(2)]
        eval_ids = ["ev-a", "ev-b"]
        coord, res = _run_round(cl, jobs, eval_ids=eval_ids)
        # ev-b's plan never commits
        _commit_round(cl, res, eval_ids, skip_evals={"ev-b"})
        adopts0 = _counter("carry_adopts")
        _run_round(cl, jobs, eval_ids=eval_ids)
        assert _counter("carry_adopts") == adopts0
        view = _np_view(TPUStack(cl).device_arrays())
        cold = _cold_view(cl)
        for f, a in view.items():
            assert np.array_equal(a, cold[f]), f

    def test_uncommitted_stop_delta_does_not_leak_into_view(self):
        """A program whose plan-relative STOP delta rode the chain (the
        carry's used0 subtracts it) but whose plan never commits must
        not leave a phantom release on the device view — stop rows
        always overlay from host, even when no hot entry names them."""
        from nomad_tpu.scheduler.stack import PlanContext

        rng = random.Random(31)
        cl = _mini_cluster()
        # a live alloc whose stop the doomed eval will propose
        victim = Allocation(
            id=uuid.uuid4().hex, namespace="default", job_id="victim",
            task_group="web", node_id="node-5",
            allocated_resources=alloc_resources(cpu=500, memory_mb=256,
                                                disk_mb=50),
            desired_status="run", client_status="pending")
        cl.upsert_alloc(victim)
        # ev-commit must not land on (and thereby overlay) the victim's
        # row; ev-doomed must predict NOTHING (infeasible ask) so its
        # stop delta is the only thing its program left in the carry —
        # the exact shape that bypasses the predicted-placements check
        commit_job = _job(rng, 1)
        commit_job.constraints.append(
            Constraint("${node.unique.id}", "node-5", "!="))
        doomed_job = _job(rng, 1)
        doomed_job.task_groups[0].tasks[0].resources.cpu = 10 ** 6
        jobs = [commit_job, doomed_job]
        eval_ids = ["ev-commit", "ev-doomed"]
        plans = {1: PlanContext(stopped_allocs=[victim])}
        coord, res = _run_round(cl, jobs, eval_ids=eval_ids, plans=plans)
        assert res[1][0][0] is None, "doomed eval unexpectedly placed"
        # only ev-commit's plan lands; ev-doomed (and its stop) never
        # commits — the victim keeps running host-side
        _commit_round(cl, res, eval_ids, skip_evals={"ev-doomed"})
        adopts0 = _counter("carry_adopts")
        _run_round(cl, jobs, eval_ids=eval_ids, plans=plans)
        assert _counter("carry_adopts") == adopts0 + 1, \
            "adoption did not happen — the phantom-release path is untested"
        view = _np_view(TPUStack(cl).device_arrays())
        cold = _cold_view(cl)
        # host truth still accounts the victim (≥500 cpu on its row) —
        # and the device view matches it bit-for-bit (no phantom release)
        row5 = cl.row_of["node-5"]
        assert cold["used"][row5, 0] >= 500.0
        for f, a in view.items():
            assert np.array_equal(a, cold[f]), f

    def test_window_from_other_dispatch_rejects_carry(self):
        """A clean+exact window stamped with a DIFFERENT dispatch token
        (a retry plan, or a stops-only later plan of the same eval)
        must not vouch for this carry — the whitewash scenario: the
        carry's predicted placements may never have committed."""
        rng = random.Random(13)
        cl = _mini_cluster()
        jobs = [_job(rng, i) for i in range(2)]
        eval_ids = ["ev-a", "ev-b"]
        coord, res = _run_round(cl, jobs, eval_ids=eval_ids)
        _commit_round(cl, res, eval_ids, wrong_token=True)
        adopts0 = _counter("carry_adopts")
        rejects0 = _counter("carry_rejects")
        _run_round(cl, jobs, eval_ids=eval_ids)
        assert _counter("carry_adopts") == adopts0
        assert _counter("carry_rejects") == rejects0 + 1
        view = _np_view(TPUStack(cl).device_arrays())
        cold = _cold_view(cl)
        for f, a in view.items():
            assert np.array_equal(a, cold[f]), f

    def test_foreign_mutation_overlays_on_top_of_carry(self):
        """Node churn interleaved with kernel commits: covered rows ride
        the carry, the foreign row re-uploads — and the merged view is
        still bit-identical to host truth."""
        rng = random.Random(8)
        cl = _mini_cluster()
        jobs = [_job(rng, i) for i in range(3)]
        eval_ids = [f"ev-{i}" for i in range(3)]
        coord, res = _run_round(cl, jobs, eval_ids=eval_ids)
        _commit_round(cl, res, eval_ids)
        # foreign, non-plan mutation AFTER the commits
        cl.upsert_alloc(Allocation(
            id=uuid.uuid4().hex, namespace="default", job_id="foreign",
            task_group="web", node_id="node-7",
            allocated_resources=alloc_resources(cpu=77, memory_mb=33,
                                                disk_mb=5),
            desired_status="run", client_status="pending"))
        adopts0 = _counter("carry_adopts")
        _run_round(cl, jobs, eval_ids=eval_ids)
        assert _counter("carry_adopts") == adopts0 + 1
        view = _np_view(TPUStack(cl).device_arrays())
        cold = _cold_view(cl)
        for f, a in view.items():
            assert np.array_equal(a, cold[f]), f

    def test_chain_carry_overlay_classification(self):
        """Row-math unit for the CHAIN carry decision (ISSUE 20):
        certified evidence (adopt_rows/stale) rides verbatim, tail
        mutations past proven_version are judged against the head
        token's windows, stops/phantoms/foreign always overlay."""
        cl = _mini_cluster()
        TPUStack(cl).device_arrays()
        ent = _DEV_CACHE[cl]
        prev = ent["arrays"]
        carry = {
            "chain": True, "token": 777, "base_arrays": prev,
            "evals": {"eh"}, "stop_rows": {4},
            "used": prev.used, "dyn_free": prev.dyn_free,
            "predicted": {"eh": {5, 6}},
            "proven_version": cl.version,
            "stale": {3}, "adopt_rows": {1, 2},
        }
        # tail: the head's own clean+exact commit on row 5, then a
        # foreign bump on row 7 no window covers
        v_lo = cl.version
        cl._log_hot(5)
        cl.version += 1
        cl.mark_plan_window("eh", v_lo, cl.version, clean=True,
                            exact=True, token=777)
        cl._log_hot(7)
        cl.version += 1
        res = TPUStack._chain_carry_overlay(cl, ent, carry, prev, None)
        assert res is not None
        skip, overlay = res
        # proven prefix {1,2} + covered tail prediction {5} skip;
        # stale {3}, stop {4}, foreign {7} overlay; predicted-but-
        # unplaced row 6 is in neither (nothing ever touched it)
        assert skip == {1, 2, 5}
        assert overlay == {3, 4, 7}
        # head never resolved its outputs → reject outright
        unresolved = dict(carry, predicted=None)
        assert TPUStack._chain_carry_overlay(
            cl, ent, unresolved, prev, None) is None
        # an UNCOMMITTED head prediction is a phantom: it overlays
        # instead of poisoning the proven prefix
        phantom = dict(carry, token=778, predicted={"eh": {5, 6}})
        res2 = TPUStack._chain_carry_overlay(cl, ent, phantom, prev,
                                             None)
        assert res2 is not None
        skip2, overlay2 = res2
        assert skip2 == {1, 2}
        assert {5, 6} <= overlay2


class TestPortWordDelta:
    def test_port_flip_ships_words_not_rows(self):
        cl = _mini_cluster()
        stack = TPUStack(cl)
        stack.device_arrays()
        led0 = default_ledger().snapshot()
        from nomad_tpu.structs.resources import NetworkResource, Port

        a = Allocation(
            id=uuid.uuid4().hex, namespace="default", job_id="p",
            task_group="web", node_id="node-3",
            allocated_resources=alloc_resources(
                cpu=10, memory_mb=16, disk_mb=5,
                networks=[NetworkResource(reserved_ports=[
                    Port(label="x", value=21007)])]),
            desired_status="run", client_status="pending")
        cl.upsert_alloc(a)
        view = _np_view(stack.device_arrays())
        led1 = default_ledger().snapshot()

        def delta(site):
            return (led1.get(site, {}).get("bytes", 0)
                    - led0.get(site, {}).get("bytes", 0))

        assert delta("stack.ports_word_delta") > 0
        assert delta("stack.ports_delta") == 0
        assert delta("stack.ports_full") == 0
        word = 21007 >> 5
        assert view["ports_used"][3, word] & np.uint32(1 << (21007 & 31))
        # still bit-identical to a cold upload
        cold = _cold_view(cl)
        for f, v in view.items():
            assert np.array_equal(v, cold[f]), f


class TestAttrsCompaction:
    def test_attrs_ride_int16_and_parity_holds(self):
        cl = _mini_cluster()
        stack = TPUStack(cl)
        view = _np_view(stack.device_arrays())
        assert view["attrs"].dtype == np.int16
        assert np.array_equal(view["attrs"],
                              cl.attrs[: cl.n_cap].astype(np.int16))
        # selection runs fine on the compacted view
        j = _job(random.Random(1), 0)
        r = stack.select(j, j.task_groups[0], 1, None)
        assert r.node_ids[0] is not None
