"""The system scheduler's one launch a task group (`kernels/preemption.py
system_place`, packed and read by `scheduler/system.py SystemTable`).

(a) node by node, the launch's victims are those of upstream's greedy on
    the host (`Preemptor.preempt_for_task_group`, the node fixed): the
    same ids in the same order, on seeded clusters with mixed priorities,
    candidates of equal distance, stops in the plan, and — in the coupled
    cases — victims whose group has a migrate `max_parallel`, so that each
    node's choice hangs on the nodes decided before it in the host's order;
(b) a `Server` with `system-600`'s cluster serves its two system kinds
    beside its service jobs, judged by the benchmark's plain reference
    (`perfbench/reference.py` `system_nodes` / `judge_preemption` through
    `check.replay`): `system_missing`, `system_extra`, `preempt_wrong` 0;
(c) the same with system preemption off in the scheduler configuration
    reads `system_missing` above 0 against the same reference: the
    judgement can fail;
(d) the launch runs clean under `transfer_guard("disallow")`, its upload
    and fetch on the transfer ledger.
"""
import json
import os
import random
import sys

import pytest

from nomad_tpu import mock
from nomad_tpu.kernels.preemption import (SYS_FITS, SYS_FREED,
                                          SYS_REFUSED)
from nomad_tpu.scheduler.harness import Harness
from nomad_tpu.scheduler.preemption import Preemptor
from nomad_tpu.scheduler.stack import PlanContext, TPUStack
from nomad_tpu.scheduler.system import SystemScheduler, SystemTable
from nomad_tpu.scheduler.util import SchedulerConfiguration, proposed_allocs
from nomad_tpu.structs.job import MigrateStrategy
from nomad_tpu.structs.resources import ComparableResources

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

#: allocation shapes (cpu MHz, memory MiB): repeated, so that candidates
#: of one node stand at the same distance from the ask
SHAPES = ((100, 128), (200, 256), (300, 128), (200, 256), (500, 512),
          (100, 128))
#: victim priorities against the system job's 100: 95 may not be evicted
#: (a delta under 10), 90 just may
PRIORITIES = (20, 30, 50, 50, 90, 95)


def _job(rng, priority, max_parallel):
    j = mock.job(priority=priority)
    tg = j.task_groups[0]
    tg.count = 1
    tg.networks = []
    tg.tasks[0].resources.networks = []
    if max_parallel:
        tg.migrate_strategy = MigrateStrategy(max_parallel=max_parallel)
    return j


def _alloc(job, node, cpu, mem):
    a = mock.alloc(job=job, node_id=node.id, client_status="running",
                   allocated_resources=mock.alloc_resources(
                       cpu=cpu, memory_mb=mem, disk_mb=10, networks=[]))
    a.task_group = job.task_groups[0].name
    a.name = f"{job.id}.{a.task_group}[0]"
    return a


def _scene(seed, coupled, state=None):
    """A seeded cluster of 64–256 filled nodes, a system job, and a
    system scheduler set up as `_compute_placements` finds it, with some
    of the cluster's allocations stopped in its plan (`state`: the store
    to fill, a fresh `InMemState` by default)."""
    rng = random.Random(seed)
    h = Harness(state)
    nodes = [mock.node() for _ in range(rng.randrange(64, 257))]
    for n in nodes:
        h.state.upsert_node(n)
    jobs = [_job(rng, rng.choice(PRIORITIES),
                 rng.choice((1, 2)) if coupled and k % 2 == 0 else 0)
            for k in range(12)]
    guarded = _job(rng, 95, 0)      # nodes full of it free nothing
    for j in jobs + [guarded]:
        h.state.upsert_job(j)
    system = mock.system_job(priority=100)
    tg = system.task_groups[0]
    tg.networks = []
    tg.tasks[0].resources.networks = []
    tg.tasks[0].resources.cpu = rng.choice((300, 500, 900))
    tg.tasks[0].resources.memory_mb = rng.choice((256, 512))
    h.state.upsert_job(system)
    allocs = []
    for n in nodes:
        budget = 3900 * rng.uniform(0.55, 1.0)
        used = 0
        pool = [guarded] if rng.random() < 0.1 else jobs
        while True:
            cpu, mem = rng.choice(SHAPES)
            if used + cpu > budget:
                break
            used += cpu
            a = _alloc(rng.choice(pool), n, cpu, mem)
            h.state.upsert_alloc(a)
            allocs.append(a)
        if rng.random() < 0.05:     # the job's own: never a candidate
            own = _alloc(system, n, 100, 128)
            h.state.upsert_alloc(own)

    ev = mock.eval_(job_id=system.id, type="system", priority=100)
    sched = SystemScheduler(h.state, h, h.state.cluster)
    sched.eval, sched.job = ev, system
    sched.plan = ev.make_plan(system)
    sched.stack = TPUStack(sched.cluster)
    sched.preemption_enabled = True
    for a in rng.sample(allocs, len(allocs) // 20):
        sched.plan.append_stopped_alloc(a, "stopped")
    rng.shuffle(nodes)
    return h, sched, tg, nodes


def _host_fits(h, plan, node, ask):
    """The node's room as `system_feasibility` reads it: every live
    allocation but those the plan stops."""
    stopped = {a.id for a in plan.node_update.get(node.id, [])}
    rem = node.comparable_resources()
    rem.subtract(node.comparable_reserved_resources())
    for a in h.state.allocs_by_node(node.id):
        if not a.terminal_status() and a.id not in stopped:
            rem.subtract(a.comparable_resources())
    return rem.superset(ask)[0]


@pytest.mark.parametrize("coupled", [False, True],
                         ids=["independent", "coupled"])
@pytest.mark.parametrize("seed", [2**31 + 441, 2**31 + 442, 2**31 + 443])
def test_victims_are_the_host_greedys_node_by_node(seed, coupled):
    h, sched, tg, nodes = _scene(seed, coupled)
    plan, job = sched.plan, sched.job
    ctx = PlanContext()
    for stops in plan.node_update.values():
        ctx.stopped_allocs.extend(stops)
    params, _m = sched.stack.compile_tg(job, tg, len(nodes), ctx)
    rows = [sched.cluster.row_of[n.id] for n in nodes]
    table = SystemTable(sched)
    table.pack(table.may_not_fit(params, rows))
    table.launch(params, rows)
    assert table.coupled == coupled

    res = job.combined_task_resources(tg)
    ask = ComparableResources(cpu=res.cpu, memory_mb=res.memory_mb,
                              disk_mb=res.disk_mb)
    freed = refused = moved_by_penalty = 0
    for node in nodes:
        row = sched.cluster.row_of[node.id]
        st = int(table.status[row])
        if _host_fits(h, plan, node, ask):
            assert st == SYS_FITS, node.id
            continue
        p = Preemptor(job.priority, job.namespace, job.id)
        p.set_node(node)
        p.set_candidates(proposed_allocs(h.state, plan, node.id))
        p.set_preemptions([a for lst in plan.node_preemptions.values()
                           for a in lst])
        want = p.preempt_for_task_group(ask)
        got = table.victims_of(node.id)
        assert [a.id for a in got] == [a.id for a in want], node.id
        assert {a.id for a in got} == {a.id for a in want}
        assert st == (SYS_FREED if want else SYS_REFUSED), node.id
        freed += bool(want)
        refused += not want
        if coupled:
            p.set_preemptions([])
            moved_by_penalty += [a.id for a in p.preempt_for_task_group(
                ask)] != [a.id for a in want]
        # the host loop's commit: the penalty of the nodes after this one
        for v in want:
            plan.append_preempted_alloc(v, "placed")
    assert freed > 0 and refused > 0
    assert (moved_by_penalty > 0) == coupled


def _host_greedy(state, job, tg, node_id, plan):
    """Upstream's greedy on the host, the node fixed, against the plan as
    it stands: what the system path took before its launch decided."""
    res = job.combined_task_resources(tg)
    p = Preemptor(job.priority, job.namespace, job.id)
    p.set_node(state.node_by_id(node_id))
    p.set_candidates(proposed_allocs(state, plan, node_id))
    p.set_preemptions([a for lst in plan.node_preemptions.values()
                       for a in lst])
    return p.preempt_for_task_group(ComparableResources(
        cpu=res.cpu, memory_mb=res.memory_mb, disk_mb=res.disk_mb))


@pytest.mark.parametrize("unpacked", [False, True],
                         ids=["refusals", "refusals-unpacked"])
def test_the_plan_is_the_host_greedys_where_the_host_departs(monkeypatch,
                                                             unpacked):
    """The plan `_compute_placements` builds, against the same loop with
    upstream's greedy on the host: the same nodes placed and the same
    victims, where a node the launch freed is then refused its placement
    (as a port-exhausted node is: its victims never reach the plan, so
    the max_parallel counts of the nodes after it are decided again),
    and — `unpacked` — where the host packed no row and every full row
    comes back from the launch unpacked."""
    import zlib

    from nomad_tpu.scheduler import preemption
    from nomad_tpu.scheduler.util import ready_nodes_in_dcs

    h, sched, _tg, _nodes = _scene(2**31 + 471, coupled=True)
    stops = [a for lst in sched.plan.node_update.values() for a in lst]
    sched.nodes, sched.nodes_by_dc = ready_nodes_in_dcs(
        h.state, sched.job.datacenters)
    place = SystemScheduler._place_on_node

    def refuse_some(self, tg, node_id, prev, ok, victims, plain):
        if victims and zlib.crc32(node_id.encode()) % 3 == 0:
            return place(self, tg, node_id, prev, False, [], plain)
        return place(self, tg, node_id, prev, ok, victims, plain)

    monkeypatch.setattr(SystemScheduler, "_place_on_node", refuse_some)
    if unpacked:
        monkeypatch.setattr(SystemTable, "may_not_fit",
                            lambda self, params, rows: [])

    def plan_of():
        sched.plan = sched.eval.make_plan(sched.job)
        for a in stops:
            sched.plan.append_stopped_alloc(a, "stopped")
        sched.failed_tg_allocs, sched.queued_allocs = {}, {}
        assert sched._compute_job_allocs() is None
        return ({n: [a.id for a in lst]
                 for n, lst in sched.plan.node_preemptions.items()},
                set(sched.plan.node_allocation))

    decided = plan_of()
    monkeypatch.setattr(preemption, "preempt_on_node", _host_greedy)
    assert decided == plan_of()
    assert sum(len(v) for v in decided[0].values()) > 0


# ---- (a'): the pack from the allocation index is the object walk ---------

PACK_CASES = ["plain", "coupled", "plan", "update", "ahead", "no-log"]


def _pack_scene(seed, case):
    """`_scene` on a `StateStore`, set up for one case of the pack: the
    scheduler, the task group and its nodes, and the rows the pack has to
    walk — those the case makes the index unable to stand for."""
    import copy

    from nomad_tpu.server.state import StateStore

    store = StateStore()
    h, sched, tg, nodes = _scene(seed, case == "coupled", state=store)
    rng = random.Random(seed + 1)
    row_of = sched.cluster.row_of
    own = sched.job.id
    on = {n.id: [a for a in store.allocs_by_node(n.id) if a.job_id != own
                 and not a.terminal_status()] for n in nodes}
    res = tg.tasks[0].resources
    ask = (res.cpu, res.memory_mb, res.disk_mb)
    cl = sched.cluster
    full = [n for n in nodes if on[n.id] and any(
        cl.used[row_of[n.id], :3] + ask > cl.capacity[row_of[n.id], :3])]
    picked = rng.sample(full, 12)
    walk = set()
    if case != "plan":
        sched.plan = sched.eval.make_plan(sched.job)   # no stops
    if case == "plain":
        for n in picked[:3]:    # a live allocation without its job
            a = copy.copy(rng.choice(on[n.id]))
            a.job = None
            store.upsert_alloc(a)
            walk.add(row_of[n.id])
    elif case == "plan":
        other = _job(rng, 30, 0)
        store.upsert_job(other)
        for n in picked[:6]:
            sched.plan.append_preempted_alloc(rng.choice(on[n.id]), "p")
        for n in picked[6:]:
            sched.plan.append_alloc(_alloc(other, n, 100, 128))
        walk = {row_of[nid] for lst in (sched.plan.node_update,
                                        sched.plan.node_preemptions,
                                        sched.plan.node_allocation)
                for nid, allocs in lst.items() if allocs}
    elif case == "update":
        for n in picked:        # usage moved in place, the state live
            b = copy.copy(on[n.id][0])
            b.allocated_resources = mock.alloc_resources(
                cpu=700, memory_mb=64, disk_mb=10, networks=[])
            store.upsert_alloc(b)
    elif case in ("ahead", "no-log"):
        sched.state = store.snapshot()
        other = _job(rng, 30, 0)
        store.upsert_job(other)
        if case == "ahead":
            for k, n in enumerate(picked):
                a = rng.choice(on[n.id])
                if k % 3 == 0:
                    b = copy.copy(a)
                    b.desired_status = "stop"
                elif k % 3 == 1:
                    b = copy.copy(a)
                    b.allocated_resources = mock.alloc_resources(
                        cpu=50, memory_mb=64, disk_mb=10, networks=[])
                else:
                    b = _alloc(other, n, 100, 128)
                store.upsert_alloc(b)
                walk.add(row_of[n.id])
        else:
            a = on[picked[0].id][0]
            for _ in range(sched.cluster.delta_log_len + 1):
                store.upsert_alloc(a)
            walk = {row_of[n.id] for n in nodes}
    return h, sched, tg, nodes, walk


@pytest.mark.parametrize("case", PACK_CASES)
@pytest.mark.parametrize("seed", [2**31 + 481, 2**31 + 482])
def test_the_pack_from_the_index_is_the_object_walk(monkeypatch, seed,
                                                    case):
    """`SystemTable.pack` from the cluster tensors' allocation index and
    the same pack walking every row's Allocation objects give the same
    `SystemCandidates` field for field and the same victims, and the rows
    the index did not stand for are exactly those the case made it unable
    to: the plan's nodes, rows the tensors moved since the snapshot, rows
    with a live allocation without a job, every row where the hot-row log
    cannot answer."""
    import numpy as np

    h, sched, tg, nodes, walk = _pack_scene(seed, case)
    ctx = PlanContext()
    for stops in sched.plan.node_update.values():
        ctx.stopped_allocs.extend(stops)
    params, _m = sched.stack.compile_tg(sched.job, tg, len(nodes), ctx)
    rows = [sched.cluster.row_of[n.id] for n in nodes]
    from_index = SystemTable._from_index
    seen = {}

    def spy(self, new):
        got = from_index(self, new)
        ok = got[0] if got is not None else np.zeros(len(new), bool)
        seen["walked"] = {r for r, o in zip(new, ok) if not o}
        return got

    tables = []
    for read in (spy, lambda self, new: None):
        monkeypatch.setattr(SystemTable, "_from_index", read)
        table = SystemTable(sched)
        packed = table.may_not_fit(params, rows)
        table.pack(packed)
        table.launch(params, rows)
        tables.append(table)
    arrays, walked = tables
    assert seen["walked"] == walk & set(packed)
    assert arrays.n_rows == walked.n_rows == len(set(packed))
    assert arrays.n_array == arrays.n_rows - len(seen["walked"])
    assert walked.n_array == 0
    assert bool(seen["walked"]) == (case not in ("coupled", "update"))

    assert arrays.coupled == walked.coupled == (case == "coupled")
    n = sched.cluster.n_cap
    got, want = arrays.candidates(n, rows), walked.candidates(n, rows)
    for field in want._fields:
        assert np.array_equal(getattr(got, field), getattr(want, field)), \
            field
    assert arrays.slots == walked.slots
    assert np.array_equal(arrays.status, walked.status)
    assert any(walked.victims_of(nd.id) for nd in nodes)
    for nd in nodes:
        assert [a.id for a in arrays.victims_of(nd.id)] \
            == [a.id for a in walked.victims_of(nd.id)], nd.id
        assert all(a is b for a, b in zip(arrays.victims_of(nd.id),
                                           walked.victims_of(nd.id)))


def test_the_pack_reads_the_index_whole_beside_a_writer(monkeypatch):
    """Packs from the index while another thread keeps rewriting half the
    nodes' allocations — each write held up between its arrays and its
    hot-row log entry, the interpreter switching threads every
    microsecond: every row a pack reads from the index is the snapshot's
    (remaining and candidates as the walk read them before the writer
    started) — a read a write ran into is read again, and a row written
    since the snapshot is walked."""
    import copy
    import sys
    import threading
    import time

    import numpy as np

    from nomad_tpu.server.state import StateStore
    from nomad_tpu.tensor.cluster import ClusterTensors

    monkeypatch.setenv("NOMAD_TPU_DELTA_LOG", str(1 << 20))
    store = StateStore()
    h, sched, _tg, nodes = _scene(2**31 + 495, coupled=False, state=store)
    sched.plan = sched.eval.make_plan(sched.job)
    sched.state = store.snapshot()
    rows = [sched.cluster.row_of[n.id] for n in nodes]

    def cands(table, row):
        lo = int(table.first[row])
        hi = lo + int(table.ncand[row])
        return (table.remaining[row].tolist(), table._table()[lo:hi].tolist(),
                [x if isinstance(x, str) else x.id
                 for x in table.refs[lo:hi]])

    from_index = SystemTable._from_index
    monkeypatch.setattr(SystemTable, "_from_index", lambda self, new: None)
    ref = SystemTable(sched)
    ref.pack(rows)
    want = {r: cands(ref, r) for r in rows}
    read = {}

    def spy(self, new):
        got = from_index(self, new)
        read["ok"] = [r for r, o in zip(new, got[0]) if o] if got else []
        return got

    monkeypatch.setattr(SystemTable, "_from_index", spy)
    churn = [a for n in nodes[::2] for a in store.allocs_by_node(n.id)
             if not a.terminal_status()]
    stop, wrote = threading.Event(), [0]
    log_hot = ClusterTensors._log_hot

    def slow_log(self, *rows):
        if threading.current_thread() is t:
            time.sleep(5e-4)
        log_hot(self, *rows)

    monkeypatch.setattr(ClusterTensors, "_log_hot", slow_log)

    def writer():
        rng = random.Random(7)
        while not stop.is_set():
            b = copy.copy(rng.choice(churn))
            b.allocated_resources = mock.alloc_resources(
                cpu=rng.choice((50, 700)), memory_mb=32, disk_mb=10,
                networks=[])
            store.upsert_alloc(b)
            wrote[0] += 1
            time.sleep(2e-3)

    interval = sys.getswitchinterval()
    t = threading.Thread(target=writer, daemon=True)
    compared = 0
    sys.setswitchinterval(1e-6)
    try:
        t.start()
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline:
            table = SystemTable(sched)
            table.pack(rows)
            for r in read["ok"]:
                assert cands(table, r) == want[r], r
            compared += len(read["ok"])
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        t.join(10.0)
    assert not t.is_alive()
    assert wrote[0] > 100 and compared > 0
    assert np.isin([sched.cluster.row_of[n.id] for n in nodes[::2]],
                   read["ok"]).sum() < len(nodes[::2])


def test_the_packs_counts_and_wall_reach_the_registry():
    """A system eval adds `system.pack_rows`, `system.pack_rows_array` (no
    more than the rows) and a `system.pack_ms` sample a task group."""
    from nomad_tpu.lib.metrics import default_registry

    h, sched, _tg, _nodes = _scene(2**31 + 491, coupled=False)
    reg = default_registry()
    before = {k: reg.counters().get(k, 0.0)
              for k in ("system.pack_rows", "system.pack_rows_array")}
    samples = reg.histogram("system.pack_ms").count
    h.process(mock.eval_(job_id=sched.job.id, type="system", priority=100))
    rows = reg.counters()["system.pack_rows"] - before["system.pack_rows"]
    array = reg.counters()["system.pack_rows_array"] \
        - before["system.pack_rows_array"]
    assert 0 < array <= rows
    assert reg.histogram("system.pack_ms").count == samples + 1


# ---- (b), (c): served, and judged by the benchmark's plain reference -----

TERMINAL = ("complete", "failed", "blocked", "cancelled")
N_JOBS, GROUP, COUNT = 48, 8, 8


def _serve(seed, system_preemption=True):
    """`system-600`'s mix through a `Server`, eight jobs registered at a
    time, each group waited for -> what `check.serial_order` takes."""
    import adapter
    import check
    import cluster as cl
    import launcher
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.structs.codec import to_wire

    with open(os.path.join(BENCH, "testdata", "system-600",
                           "config.json")) as f:
        cfg = json.load(f)
    s = Server(ServerConfig(num_schedulers=1, heartbeat_ttl=3600.0,
                            eval_batch=32))
    launcher.load_cluster(s, cfg, seed)
    s.state.set_scheduler_config(SchedulerConfiguration(
        preemption_system_enabled=system_preemption))
    kinds = cl.kinds_sequence(cfg, seed, N_JOBS)
    specs = [cl.make_job(cfg, seed, k, kinds[k], COUNT)
             for k in range(N_JOBS)]
    jobs = []
    try:
        s.start()
        for g in range(0, N_JOBS, GROUP):
            evs = [s.job_register(adapter.to_job(spec))
                   for spec in specs[g:g + GROUP]]
            for spec, ev in zip(specs[g:g + GROUP], evs):
                got = s.wait_for_eval(ev.id, statuses=TERMINAL,
                                      timeout=120.0)
                assert got is not None, ev.id
                allocs = [rec for rec in (
                    check.alloc_record(to_wire(a))
                    for a in s.state.allocs_by_job("default", spec["id"]))
                    if rec is not None]
                jobs.append({"spec": spec, "allocs": allocs,
                             "enqueued": ev.modify_index,
                             "finished": got.modify_index})
    finally:
        s.shutdown()
    return cfg, jobs


def _judge(cfg, jobs, seed):
    import check
    import cluster as cl

    with open(os.path.join(BENCH, "testdata", "system-600",
                           "limits.json")) as f:
        limits = json.load(f)
    sample = check.draw_sample([j["spec"]["id"] for j in jobs], None,
                               limits["sample_evals"], seed)
    numbers = check.replay(cl.Cluster(cfg, seed), check.serial_order(jobs),
                           sample)
    numbers["unanswered"] = 0
    return numbers, check.verdict(numbers, limits["limits"])


SERVED_SEEDS = [2**31 + 451, 2**31 + 452, 2**31 + 453]


@pytest.mark.parametrize("seed", SERVED_SEEDS)
def test_served_system_jobs_are_the_references(seed):
    cfg, jobs = _serve(seed)
    numbers, held = _judge(cfg, jobs, seed)
    for name in ("system_missing", "system_extra", "preempt_wrong"):
        assert numbers[name] == 0, (name, numbers["worst"])
    assert all(c["ok"] for c in held.values()), (held, numbers["worst"])
    assert numbers["evictions"] > 0 and numbers["system_refused"] > 0
    assert numbers["system_interleaved"] == 0


@pytest.mark.parametrize("seed", SERVED_SEEDS)
def test_served_without_system_preemption_is_not_the_references(seed):
    cfg, jobs = _serve(seed, system_preemption=False)
    numbers, held = _judge(cfg, jobs, seed)
    assert numbers["system_missing"] > 0
    assert not held["system_missing"]["ok"]
    assert numbers["evictions"] == 0


# ---- (d): the launch under the transfer guard ------------------------------

def test_system_launch_is_clean_under_the_transfer_guard(monkeypatch):
    from nomad_tpu.lib.metrics import default_registry
    from nomad_tpu.lib.transfer import default_ledger

    h, sched, _tg, _nodes = _scene(2**31 + 461, coupled=False)
    job = sched.job
    sched.stack.device_arrays()     # the view as the server keeps it
    before = default_ledger().snapshot()
    reg = default_registry()
    evals0 = reg.counters().get("system.evals", 0.0)
    evictions0 = reg.counters().get("system.evictions", 0.0)
    monkeypatch.setenv("NOMAD_TPU_TRANSFER_GUARD", "disallow")
    h.process(mock.eval_(job_id=job.id, type="system", priority=100))
    plan = h.plans[-1]
    evicted = sum(len(v) for v in plan.node_preemptions.values())
    assert evicted > 0 and plan.node_allocation
    after = default_ledger().snapshot()
    for site in ("system.upload", "system.fetch"):
        assert after[site]["count"] > before.get(site, {}).get("count", 0)
    assert reg.counters()["system.evals"] == evals0 + 1
    assert reg.counters()["system.evictions"] == evictions0 + evicted
    for name in ("system.schedule_ms", "system.place_ms", "system.build_ms"):
        assert reg.histogram(name).count > 0
