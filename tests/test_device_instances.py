"""GPU instance ids under fused batches (ISSUE 28).

The kernel's chain counts devices and is right; the instance IDs are drawn
on the host, per eval, from the eval's own snapshot. Three layers keep one
instance from being assigned twice, each tested here on the CPU:

- (a) the CPU reproduction of PERF.md §7's fault: six device jobs
  outstanding through the fused dispatch (table chain, wave lanes) and one
  at a time through `process_one` — no instance held twice in state, every
  job whole, node and normalized score as `scheduler/oracle.py` gives them;
- (b) `plan_apply` rejects a node whose plan assigns an ID that a live
  alloc holds, or one ID twice (reason `devices`), on the tensor path and
  the object path alike, applies it once the holder is stopped in the same
  plan, and leaves a plan without devices on the fast path;
- (c) the cluster's ledger (`device_refs` / `alloc_devices`) equals a walk
  of the state store after upsert, stop, in-place update, node
  deregistration and snapshot restore.
"""
import copy
import random
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.oracle import OracleContext, select_option
from nomad_tpu.server import plan_apply
from nomad_tpu.server.fsm import restore_state, snapshot_state
from nomad_tpu.server.plan_apply import (REASON_DEVICES, PlanApplier,
                                         PlanQueue, evaluate_node_plan)
from nomad_tpu.server.state import StateStore
from nomad_tpu.structs import Plan
from nomad_tpu.structs.resources import AllocatedDeviceResource

TERMINAL = ("complete", "failed", "blocked", "cancelled")


def held_instances(state):
    """{(node id, group id, instance id): [alloc ids]} from a walk of the
    store's live allocations on the nodes it has (like `used` and the
    ports, the ledger keeps nothing for a node that is gone)."""
    out = {}
    for a in list(state._allocs.values()):
        if a.terminal_status() or a.allocated_resources is None \
                or state.node_by_id(a.node_id) is None:
            continue
        for tr in a.allocated_resources.tasks.values():
            for ad in tr.devices:
                for inst in ad.device_ids:
                    out.setdefault(
                        (a.node_id, f"{ad.vendor}/{ad.type}/{ad.name}", inst),
                        []).append(a.id)
    return out


def ledger_instances(cl):
    """The same from the cluster's ledger, both of its tables."""
    by_row = {}
    for row, refs in enumerate(cl.device_refs):
        for (group, inst), holders in refs.items():
            by_row[(cl.node_of_row[row], group, inst)] = sorted(holders)
    by_alloc = {}
    for aid, (row, keys) in cl.alloc_devices.items():
        for group, inst in keys:
            by_alloc.setdefault((cl.node_of_row[row], group, inst),
                                []).append(aid)
    return by_row, {k: sorted(v) for k, v in by_alloc.items()}


def assert_ledger_is_the_walk(state):
    walk = {k: sorted(v) for k, v in held_instances(state).items()}
    by_row, by_alloc = ledger_instances(state.cluster)
    assert by_row == walk
    assert by_alloc == walk


# ---- (a) fused dispatch of device jobs against the plain scheduler ---------


def _device_jobs(rng, dispatch):
    from nomad_tpu.synth import synth_service_job

    jobs = []
    for i in range(6):
        dc = f"dc{1 + i % 2}" if dispatch == "wave" else None
        j = synth_service_job(rng, count=4, with_devices=True, datacenter=dc)
        # distinct asks: no two nodes tie on the score
        j.task_groups[0].tasks[0].resources.cpu = 300 + 70 * i
        j.task_groups[0].tasks[0].resources.memory_mb = 200 + 90 * i
        jobs.append(j)
    return jobs


@pytest.mark.parametrize("dispatch", ["chain", "wave", "solo"])
def test_device_jobs_outstanding_together_hold_no_instance_twice(
        dispatch, monkeypatch):
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.synth import synth_node

    monkeypatch.delenv("NOMAD_TPU_EVAL_BATCH", raising=False)
    rng = random.Random(28)
    s = Server(ServerConfig(num_schedulers=1, heartbeat_ttl=3600.0,
                            eval_batch=1 if dispatch == "solo" else 8))
    nodes = [synth_node(rng, i) for i in range(48)]  # every 4th: 4 GPUs
    for n in nodes:
        s.state.upsert_node(n)
    jobs = _device_jobs(rng, dispatch)
    # registered before the workers start: one drain sees all six
    evs = [s.job_register(j) for j in jobs]
    s.start()
    try:
        for ev in evs:
            got = s.wait_for_eval(ev.id, statuses=TERMINAL, timeout=120.0)
            assert got is not None and got.status == "complete", got
        # the worker folds a batch's counts in once the batch has ended,
        # which is after its last eval was answered
        deadline = time.monotonic() + 30.0
        while dispatch != "solo" and time.monotonic() < deadline and \
                s.metrics.counters().get("worker.0.batch.batched", 0) < 6:
            time.sleep(0.05)
        counters = s.metrics.counters()
        stats = dict(s.planner.stats)
        offer_spans = s.metrics.histogram(
            "eval.phase.device_offer_ms").summary()["count"]
        doubled = {k: v for k, v in held_instances(s.state).items()
                   if len(v) > 1}
        assert not doubled, doubled
        assert_ledger_is_the_walk(s.state)
        served = {j.id: sorted(s.state.allocs_by_job("default", j.id),
                               key=lambda a: int(a.name.rsplit("[", 1)[1][:-1]))
                  for j in jobs}
    finally:
        s.shutdown()
    batched = counters.get("worker.0.batch.batched", 0)
    wave = counters.get("wave.dispatches", 0)
    if dispatch == "solo":
        assert batched == 0
    else:
        assert batched >= len(jobs)
        assert (wave >= 1) == (dispatch == "wave")
        # one `device_offer` span an eval, gathered on its own thread
        assert offer_spans == len(jobs)
    # nobody was handed an instance a batch-mate holds, so nothing was
    # rejected at the commit point and no plan was partial
    assert stats["rejected_devices"] == 0 and stats["partial"] == 0, stats
    assert counters["sched.device_offers"] == 24
    assert counters.get("sched.device_offer_retries", 0) == 0

    # the plain scheduler over the same cluster, eval by eval in the
    # order they were enqueued, allocation by allocation
    by_node = {}
    for j in jobs:
        allocs = served[j.id]
        assert len(allocs) == j.task_groups[0].count, (j.id, len(allocs))
        placed = {}
        for a in allocs:
            ctx = OracleContext(nodes=nodes, allocs_by_node=by_node,
                                plan_node_alloc=placed)
            opt = select_option(ctx, j, j.task_groups[0])
            assert opt is not None
            assert a.node_id == opt.node.id, (j.id, a.name)
            score = next(sm.norm_score for sm in a.metrics.score_meta
                         if sm.node_id == a.node_id)
            assert abs(score - opt.final_score) < 1e-4, (j.id, a.name)
            placed.setdefault(a.node_id, []).append(a)
        for nid, allocs_on in placed.items():
            by_node.setdefault(nid, []).extend(allocs_on)


# ---- (b) the commit point ---------------------------------------------------


def _gpu_alloc(node, ids, job=None):
    a = mock.alloc(node_id=node.id, client_status="running",
                   **({"job": job} if job is not None else {}))
    tr = next(iter(a.allocated_resources.tasks.values()))
    tr.networks = []
    tr.devices = [AllocatedDeviceResource(
        vendor="nvidia", type="gpu", name="1080ti", device_ids=list(ids))]
    return a


def _store_with_holder():
    state = StateStore()
    node = mock.nvidia_node()
    state.upsert_node(node)
    ids = [i.id for i in node.node_resources.devices[0].instances]
    holder = _gpu_alloc(node, ids[:1])
    state.upsert_alloc(holder)
    return state, node, ids, holder


def _plan(node, placed, stopped=()):
    plan = Plan(eval_id="e-devices", priority=50)
    for a in placed:
        plan.node_allocation.setdefault(node.id, []).append(a)
    for a in stopped:
        plan.node_update.setdefault(node.id, []).append(a)
    return plan


CASES = {
    # the plan's placements (as instance indexes), whether it stops the
    # holder, and whether the node fits
    "held-by-a-live-alloc": ([[0]], False, False),
    "assigned-twice-in-the-plan": ([[1], [1]], False, False),
    "two-ids-one-of-them-held": ([[1, 0]], False, False),
    "holder-stopped-in-the-same-plan": ([[0]], True, True),
    "free-instances": ([[1], [2, 3]], False, True),
}


@pytest.mark.parametrize("path", ["tensor", "object"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_a_node_is_rejected_for_an_instance_id_and_applied_once_it_is_free(
        case, path, monkeypatch):
    placements, stop_holder, fits = CASES[case]
    state, node, ids, holder = _store_with_holder()
    if path == "object":
        monkeypatch.setattr(plan_apply, "_tensor_node_verify",
                            lambda *a, **kw: None)
    placed = [_gpu_alloc(node, [ids[k] for k in idx]) for idx in placements]
    stopped = []
    if stop_holder:
        gone = copy.copy(holder)
        gone.desired_status = "stop"
        stopped.append(gone)
    plan = _plan(node, placed, stopped)
    fit, reason = evaluate_node_plan(state, plan, node.id)
    assert (fit, reason) == ((True, "") if fits else (False, REASON_DEVICES))

    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(state, queue)
    result = applier.apply(plan)
    stats = applier.stats
    if fits:
        assert not result.refresh_index
        assert [a.id for a in result.node_allocation[node.id]] == \
            [a.id for a in placed]
        assert stats["rejected_devices"] == 0 and stats["partial"] == 0
    else:
        # a partial plan, as after a port collision: nothing of the node
        # commits and the scheduler is told to refresh
        assert result.refresh_index and node.id not in result.node_allocation
        assert stats["rejected_devices"] == 1 == stats["rejected_nodes"]
        assert stats["partial"] == 1
    assert not {k: v for k, v in held_instances(state).items() if len(v) > 1}
    assert_ledger_is_the_walk(state)


@pytest.mark.parametrize("n_allocs", [1, 40])
def test_a_plan_without_devices_never_looks_at_the_ledger(n_allocs):
    state = StateStore()
    node = mock.node()
    node.node_resources.cpu = 100_000
    node.node_resources.memory_mb = 100_000
    state.upsert_node(node)
    placed = []
    for _ in range(n_allocs):
        a = mock.alloc(node_id=node.id)
        next(iter(a.allocated_resources.tasks.values())).networks = []
        placed.append(a)
    plan = _plan(node, placed)
    cl = state.cluster
    cl.device_refs = None  # any look at it raises
    row = cl.row_of[node.id]
    assert plan_apply._tensor_node_verify(cl, row, plan, node.id) == (True, "")
    assert evaluate_node_plan(state, plan, node.id) == (True, "")


# ---- (c) the ledger is derived state ----------------------------------------


def _ledger_steps():
    """The store after each step, cumulatively."""
    state = StateStore()
    nodes = [mock.nvidia_node() for _ in range(3)]
    for n in nodes:
        state.upsert_node(n)
    ids = [[i.id for i in n.node_resources.devices[0].instances]
           for n in nodes]
    job = mock.job()
    state.upsert_job(job)
    allocs = [_gpu_alloc(nodes[0], ids[0][:2], job),
              _gpu_alloc(nodes[0], ids[0][2:3], job),
              _gpu_alloc(nodes[1], ids[1][:4], job),
              _gpu_alloc(nodes[2], ids[2][1:2], job)]
    plain = mock.alloc(node_id=nodes[1].id, job=job)
    next(iter(plain.allocated_resources.tasks.values())).networks = []

    def upsert():
        for a in allocs + [plain]:
            state.upsert_alloc(a)
        return state

    def stop():
        gone = copy.copy(allocs[1])
        gone.desired_status = "stop"
        gone.client_status = "complete"
        state.upsert_alloc(gone)
        return state

    def inplace_update():
        # the same alloc id comes back with other instances
        moved = copy.copy(allocs[0])
        moved.allocated_resources = copy.deepcopy(allocs[0].allocated_resources)
        next(iter(moved.allocated_resources.tasks.values())
             ).devices[0].device_ids = ids[0][2:4]
        state.upsert_alloc(moved)
        return state

    def deregister():
        state.delete_node(nodes[1].id)
        return state

    def delete():
        state.delete_alloc(allocs[3].id)
        return state

    def restore():
        fresh = StateStore()
        restore_state(fresh, snapshot_state(state))
        return fresh

    return [("upsert", upsert), ("stop", stop),
            ("in-place-update", inplace_update), ("delete", delete),
            ("node-deregistration", deregister),
            ("snapshot-restore", restore)]


STEPS = [name for name, _ in _ledger_steps()]


@pytest.mark.parametrize("upto", STEPS)
def test_the_ledger_equals_a_walk_of_the_store(upto):
    state = None
    for name, step in _ledger_steps():
        state = step()
        if name == upto:
            break
    assert_ledger_is_the_walk(state)
    walk = held_instances(state)
    if upto == "upsert":
        assert len(walk) == 8
    if upto == "snapshot-restore":
        # what survives: allocs[0] as moved (two instances of node 0)
        assert len(walk) == 2 and len(state.cluster.alloc_devices) == 1
    # nothing is left behind for an alloc that holds nothing any more
    live = {a.id for a in state._allocs.values()
            if not a.terminal_status() and state.node_by_id(a.node_id)}
    assert set(state.cluster.alloc_devices) <= live
