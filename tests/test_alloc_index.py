"""The cluster tensors' allocation index (`tensor/alloc_index.py`) is
derived state: after seeded sequences of allocation upserts (new, updated
in place, stopped, revived, moved, without a job), plan placements,
deletions, node removals and re-registrations, every row's slots — their
allocations, their order and their values — are what a rebuild from the
state's allocations on that node gives, and so they are after a restore.
Rows the index marks partial are exactly the nodes that held allocations
while they had no row: the pack walks those."""
import copy
import random

import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.harness import InMemState
from nomad_tpu.structs import PlanResult
from nomad_tpu.structs.job import MigrateStrategy
from nomad_tpu.tensor import alloc_index as ai
from nomad_tpu.tensor.cluster import ClusterTensors


class _Tables:
    """What the state keeps of allocations: by id, and by node in the
    order its per-node map keeps (`InMemState.upsert_alloc`)."""

    def __init__(self):
        self.allocs = {}
        self.by_node = {}

    def upsert(self, a):
        prev = self.allocs.get(a.id)
        if prev is not None and prev.node_id != a.node_id:
            self.by_node.get(prev.node_id, {}).pop(a.id, None)
        self.allocs[a.id] = a
        self.by_node.setdefault(a.node_id, {})[a.id] = a

    def delete(self, aid):
        a = self.allocs.pop(aid, None)
        if a is not None:
            self.by_node.get(a.node_id, {}).pop(aid, None)


class _OnTensors:
    """The index driven on `ClusterTensors` alone, the state's tables
    beside it."""

    def __init__(self):
        self.cluster = ClusterTensors()
        self.tables = _Tables()

    def upsert_node(self, node):
        self.cluster.upsert_node(node)

    def delete_node(self, node_id):
        self.cluster.remove_node(node_id)

    def upsert_alloc(self, a):
        self.tables.upsert(a)
        self.cluster.upsert_alloc(a)

    def place(self, allocs):
        cl = self.cluster
        rows = [cl.placement_row(a) for a in allocs]
        run = [(a, r) for a, r in zip(allocs, rows) if r is not None]
        for a in allocs:
            self.tables.upsert(a)
        if run:
            cl.upsert_placements([a for a, _r in run], [r for _a, r in run])
        for a, r in zip(allocs, rows):
            if r is None:
                cl.upsert_alloc(a)

    def delete_alloc(self, aid):
        a = self.tables.allocs.get(aid)
        self.tables.delete(aid)
        self.cluster.remove_alloc(aid, a.job_id if a else "")

    def by_node(self, node_id):
        return list(self.tables.by_node.get(node_id, {}).values())

    def restored(self, nodes):
        out = _OnTensors()
        for n in nodes:
            out.upsert_node(n)
        for a in self.tables.allocs.values():
            out.upsert_alloc(a)
        return out


class _OnState:
    """The index driven through `InMemState`'s mutators (`store`: a
    `StateStore`, restored through the FSM's snapshot)."""

    def __init__(self, store=False):
        if store:
            from nomad_tpu.server.state import StateStore

            self.state = StateStore()
        else:
            self.state = InMemState()
        self.cluster = self.state.cluster

    def upsert_node(self, node):
        self.state.upsert_node(node)

    def delete_node(self, node_id):
        self.state.delete_node(node_id)

    def upsert_alloc(self, a):
        self.state.upsert_alloc(a)

    def place(self, allocs):
        by_node = {}
        for a in allocs:
            by_node.setdefault(a.node_id, []).append(a)
        self.state.upsert_plan_results(None, PlanResult(
            node_allocation=by_node))

    def delete_alloc(self, aid):
        self.state.delete_alloc(aid)

    def by_node(self, node_id):
        return self.state.allocs_by_node(node_id)

    def restored(self, nodes):
        from nomad_tpu.server.state import StateStore

        if isinstance(self.state, StateStore):
            from nomad_tpu.server.fsm import restore_state, snapshot_state

            out = _OnState(store=True)
            restore_state(out.state, snapshot_state(self.state))
            return out
        out = _OnState()
        for n in nodes:      # restore_state's order: nodes, then allocs
            out.upsert_node(n)
        for a in self.state._allocs.values():
            out.upsert_alloc(a)
        return out


def _want(a):
    """A live allocation's slot as the state's object says it."""
    cr = a.comparable_resources()
    job, mp = a.job, 0
    if job is not None:
        for tg in job.task_groups:
            if tg.name == a.task_group and tg.migrate_strategy is not None:
                mp = tg.migrate_strategy.max_parallel
    return (a.id, float(cr.cpu), float(cr.memory_mb), float(cr.disk_mb),
            job.priority if job is not None else 0, (a.namespace, a.job_id),
            (a.namespace, a.job_id, a.task_group), mp, job is not None)


def _got(cl, row):
    """The row's slots: every allocation id in order, and the live ones'
    values decoded."""
    idx = cl.alloc_index
    jobs = {v: k for k, v in idx.jobs.items()}
    ids, live = [], []
    for s in idx.row_slots[row]:
        v = idx.vals[s]
        assert int(v[ai.ROW]) == row
        ids.append(idx.ids[s])
        if v[ai.LIVE]:
            live.append((idx.ids[s], float(v[ai.CPU]), float(v[ai.MEM]),
                         float(v[ai.DISK]), int(v[ai.PRIO]),
                         jobs[int(v[ai.JOB])],
                         idx.group_keys[int(v[ai.GROUP])],
                         int(v[ai.MAX_PAR]), bool(v[ai.HAS_JOB])))
    return ids, live


def _check(side, unrowed):
    """Every row not partial is its node's allocations in the state's
    order, the live ones with the values a rebuild gives; the partial
    rows are nodes that held allocations without a row."""
    cl = side.cluster
    idx = cl.alloc_index
    held = set()
    for node_id, row in cl.row_of.items():
        allocs = side.by_node(node_id)
        if row in idx.partial:
            assert node_id in unrowed, node_id
            continue
        ids, live = _got(cl, row)
        assert ids == [a.id for a in allocs], node_id
        assert live == [_want(a) for a in allocs
                        if not a.terminal_status()], node_id
        held.update(ids)
    assert set(idx.slot_of) >= held
    assert all(idx.ids[s] == aid for aid, s in idx.slot_of.items())
    assert len(idx.slot_of) + len(idx.free) == len(idx.ids)


def _jobs(rng):
    jobs = []
    for k in range(5):
        j = mock.job(priority=rng.choice((20, 50, 70, 90)))
        tg = j.task_groups[0]
        tg.networks, tg.tasks[0].resources.networks = [], []
        if k % 2:
            tg.migrate_strategy = MigrateStrategy(max_parallel=k)
        if k == 4:           # two task groups
            tg2 = copy.deepcopy(tg)
            tg2.name = "api"
            tg2.migrate_strategy = None
            j.task_groups.append(tg2)
        jobs.append(j)
    return jobs


def _new_alloc(rng, jobs, node_id):
    job = rng.choice(jobs)
    tg = rng.choice(job.task_groups)
    a = mock.alloc(job=job, node_id=node_id, client_status="running",
                   allocated_resources=mock.alloc_resources(
                       cpu=rng.choice((100, 250, 500)),
                       memory_mb=rng.choice((64, 256)), disk_mb=10,
                       networks=[]))
    a.task_group = tg.name
    return a


def _changed(rng, a, nodes, jobs):
    """A copy of `a` as an update of it writes it back."""
    b = copy.copy(a)
    what = rng.randrange(6)
    if what == 0:
        b.allocated_resources = mock.alloc_resources(
            cpu=rng.choice((50, 700)), memory_mb=32, disk_mb=20,
            networks=[])
    elif what == 1:
        b.desired_status = "stop"
    elif what == 2:
        b.client_status = "complete"
    elif what == 3:      # revived: terminal -> live again
        b.desired_status, b.client_status = "run", "running"
    elif what == 4 and a.job is not None:   # a new version of its job
        job = copy.deepcopy(a.job)
        job.version += 1
        job.priority = rng.choice((30, 60, 95))
        b.job = job
    elif what == 4:
        b.job = rng.choice(jobs)
        b.job_id = b.job.id
    else:
        if rng.random() < 0.5:
            b.job = None
        else:
            b.node_id = rng.choice(nodes).id
    return b


@pytest.mark.parametrize("backend", ["tensors", "inmem", "store"])
@pytest.mark.parametrize("seed", [2**31 + 501, 2**31 + 502])
def test_the_index_is_a_rebuild_of_the_states_allocations(seed, backend):
    rng = random.Random(seed)
    side = _OnTensors() if backend == "tensors" \
        else _OnState(store=backend == "store")
    nodes = [mock.node() for _ in range(10)]
    registered = []
    for n in nodes[:7]:
        side.upsert_node(n)
        registered.append(n)
    jobs = _jobs(rng)
    if backend != "tensors":
        for j in jobs:
            side.state.upsert_job(j)
    unrowed = set()      # nodes that held allocations without a row
    ids = []

    def note(node_id):
        if node_id not in side.cluster.row_of:
            unrowed.add(node_id)

    for step in range(400):
        op = rng.random()
        if op < 0.3 or not ids:
            a = _new_alloc(rng, jobs, rng.choice(
                nodes if rng.random() < 0.1 else registered).id)
            note(a.node_id)
            side.upsert_alloc(a)
            ids.append(a.id)
        elif op < 0.45:
            batch = [_new_alloc(rng, jobs, rng.choice(registered).id)
                     for _ in range(rng.randrange(1, 6))]
            side.place(batch)
            ids.extend(a.id for a in batch)
        elif op < 0.75:
            aid = rng.choice(ids)
            a = (side.tables.allocs if backend == "tensors"
                 else side.state._allocs).get(aid)
            if a is None:
                continue
            b = _changed(rng, a, nodes, jobs)
            note(b.node_id)
            side.upsert_alloc(b)
        elif op < 0.87:
            side.delete_alloc(ids.pop(rng.randrange(len(ids))))
        elif op < 0.9 and len(registered) > 3:
            n = registered.pop(rng.randrange(len(registered)))
            if side.by_node(n.id):
                unrowed.add(n.id)
            side.delete_node(n.id)
        elif op < 0.93:
            out = [n for n in nodes if n not in registered]
            if out:
                n = rng.choice(out)
                side.upsert_node(n)
                registered.append(n)
        if step % 50 == 49:
            _check(side, unrowed)
    _check(side, unrowed)
    assert side.cluster.alloc_index.seq % 2 == 0

    back = side.restored(registered)
    _check(back, set())
