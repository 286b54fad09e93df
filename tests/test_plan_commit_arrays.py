"""A plan's placements verified and committed in one pass, against one
allocation at a time.

`PlanApplier` reads each placement's usage row, ports and devices once
(`ClusterTensors.placement_rows`), judges every node from them
(`evaluate_node_plan` → `_tensor_node_verify`) and hands them to the store,
which commits new allocations in one pass (`_upsert_placements` →
`ClusterTensors.upsert_placements`). The reference here lives in this file
only: the same plan verified node by node through `evaluate_node_plan`
(`_tensor_node_verify` reading each row itself, then the object path) and
committed by a loop of
`StateStore.upsert_alloc`, one allocation at a time. Both sides start from
the same store, built twice from one seed, and must end byte for byte
alike: verdicts and reasons, the order of the rejected nodes, every table
and index of the store, `used`, the allocation ledgers, the hot rows of the
plan's window, the event stream and the canonical state fingerprint — on
the leader, on a raft follower that applies the entry, and after a WAL
replay — and a snapshot taken before the commit must not see it.
"""
import copy
import random

import numpy as np
import pytest

from nomad_tpu import synth
from nomad_tpu.lib.flight import default_flight
from nomad_tpu.server import plan_apply
from nomad_tpu.server.event_broker import ClusterEventBroker, events_fingerprint
from nomad_tpu.server.fsm import FSM, state_fingerprint
from nomad_tpu.server.plan_apply import PlanApplier, PlanQueue, evaluate_node_plan
from nomad_tpu.server.state import StateStore
from nomad_tpu.server.wal import DurableStateStore, Wal, _encode_args
from nomad_tpu.structs import Allocation, Plan, PlanResult
from nomad_tpu.structs.resources import (AllocatedDeviceResource,
                                         AllocatedResources,
                                         AllocatedSharedResources,
                                         AllocatedTaskResources,
                                         NetworkResource, Port)

NOW = 1.75e9


# ---- the world: one seed, built as often as a side needs it --------------


def _alloc(rng, node, job, i, cpu=21, mem=17, disk=10, mbits=0, ports=(),
           reserved=(), gpus=()):
    nets = []
    if mbits or ports or reserved:
        nets = [NetworkResource(
            device="eth0", ip="10.0.0.1", mbits=mbits,
            reserved_ports=[Port(label=f"r{p}", value=p) for p in reserved],
            dynamic_ports=[Port(label=f"d{p}", value=p) for p in ports])]
    devices = ([AllocatedDeviceResource(vendor="nvidia", type="gpu",
                                        name="1080ti", device_ids=list(gpus))]
               if gpus else [])
    return Allocation(
        id=f"{rng.getrandbits(128):032x}", eval_id="e-plan",
        namespace="default", name=f"{job.id}.web[{i}]", node_id=node.id,
        job_id=job.id, job=job, job_version=job.version, task_group="web",
        allocated_resources=AllocatedResources(
            tasks={"web": AllocatedTaskResources(
                cpu=cpu, memory_mb=mem, networks=nets, devices=devices)},
            shared=AllocatedSharedResources(disk_mb=disk)),
        desired_status="run", client_status="pending")


def _gpu_ids(node):
    return [i.id for i in node.node_resources.devices[0].instances]


class World:
    """A store of `n_nodes` synthetic nodes (every fourth with four GPUs,
    port 22 reserved on each), `n_standing` running allocations, and one
    plan: `n_plain` placements on distinct nodes plus what `case` adds.
    Everything, ids included, is drawn from `seed`."""

    def __init__(self, case, n_nodes=48, n_standing=96, n_plain=24,
                 seed=39, store=None):
        rng = random.Random(seed)
        self.store = store if store is not None else StateStore()
        self.store.event_broker = ClusterEventBroker()
        self.nodes = [synth.synth_node(rng, i) for i in range(n_nodes)]
        for n in self.nodes:
            self.store.upsert_node(n)
        filler = synth.synth_service_job(rng)
        self.store.upsert_job(filler)
        self.standing = []
        for i in range(n_standing):
            node = self.nodes[4 + i % (n_nodes - 4)]
            a = _alloc(rng, node, filler, i, cpu=21.3)
            a.client_status = "running"
            self.store.upsert_alloc(a)
            self.standing.append(a)
        self.job = synth.synth_service_job(rng, count=n_plain)
        self.store.upsert_job(self.job)
        self.plan = Plan(eval_id="e-plan", priority=50, job=self.job,
                         carry_exact=True, carry_token=7)
        self.k = 0
        # plain placements on distinct nodes that no case below touches
        for node in rng.sample(self.nodes[16:], min(n_plain,
                                                    n_nodes - 16)):
            self.place(rng, node)
        CASES[case](self, rng)

    def place(self, rng, node, **kw):
        a = _alloc(rng, node, self.job, self.k, **kw)
        self.k += 1
        self.plan.node_allocation.setdefault(node.id, []).append(a)
        return a

    def standing_on(self, node):
        return [a for a in self.standing if a.node_id == node.id]


def _stacked(w, rng):
    # three on one node whose float64 sum depends on the order it is
    # taken in: (42.6 + 0.1) + 0.7 + 21.2 != (42.6 + 21.2) + 0.7 + 0.1
    for cpu in (0.1, 0.7, 21.2):
        w.place(rng, w.nodes[12], cpu=cpu)


def _stops(w, rng):
    # a node with stops only, and one with stops beside placements
    for node in (w.nodes[5], w.nodes[6]):
        for a in w.standing_on(node)[:2]:
            gone = copy.copy(a)
            gone.desired_status = "stop"
            w.plan.node_update.setdefault(node.id, []).append(gone)
    w.place(rng, w.nodes[6], cpu=200)


def _preemption(w, rng):
    victim = copy.copy(w.standing_on(w.nodes[7])[0])
    victim.desired_status = "evict"
    victim.preempted_by_allocation = "preemptor"
    w.plan.node_preemptions[w.nodes[7].id] = [victim]
    w.place(rng, w.nodes[7], cpu=300)


def _in_place(w, rng):
    live = w.standing_on(w.nodes[9])[0]
    upd = copy.copy(live)
    upd.allocated_resources = copy.deepcopy(live.allocated_resources)
    upd.allocated_resources.tasks["web"].cpu = 77
    upd.client_status = "pending"
    w.plan.node_allocation.setdefault(w.nodes[9].id, []).append(upd)
    w.place(rng, w.nodes[9], cpu=31)


def _ports(w, rng):
    # held by a live allocation of the node
    holder = _alloc(rng, w.nodes[5], w.standing[0].job, 999, ports=(25001,))
    holder.client_status = "running"
    w.store.upsert_alloc(holder)
    w.place(rng, w.nodes[5], ports=(25001,))               # already in use
    w.place(rng, w.nodes[6], reserved=(22,))               # node-reserved
    w.place(rng, w.nodes[7], ports=(25000,))
    w.place(rng, w.nodes[7], ports=(25000,))               # collision
    w.place(rng, w.nodes[9], ports=(25002,), reserved=(8080,))  # fits
    w.place(rng, w.nodes[9], ports=(25003,), mbits=10)     # fits


def _devices(w, rng):
    g1, g2, g3 = w.nodes[0], w.nodes[4], w.nodes[8]      # four GPUs each
    holder = _alloc(rng, g2, w.standing[0].job, 998, gpus=_gpu_ids(g2)[:1])
    holder.client_status = "running"
    w.store.upsert_alloc(holder)
    w.place(rng, g1, gpus=_gpu_ids(g1)[:1])
    w.place(rng, g1, gpus=_gpu_ids(g1)[:1])               # drawn twice
    w.place(rng, g2, gpus=_gpu_ids(g2)[:1])               # held by a live one
    w.place(rng, g3, gpus=_gpu_ids(g3)[1:3])              # fits


def _over_capacity(w, rng):
    # one node over in each dimension the check names (synthetic node 3k:
    # 3,900 MHz, 7,936 MiB, 98,304 MiB of disk, 1,000 Mbit/s, 4 GPUs on
    # every fourth)
    w.place(rng, w.nodes[3], cpu=2000)
    w.place(rng, w.nodes[3], cpu=2000)                    # cpu, summed
    w.place(rng, w.nodes[6], mem=9000)                    # memory
    w.place(rng, w.nodes[9], disk=200_000)                # disk
    w.place(rng, w.nodes[10], mbits=1500)                 # network
    w.place(rng, w.nodes[0], gpus=[f"x{k}" for k in range(5)])  # devices
    w.place(rng, w.nodes[11], cpu=3800)                   # fits, just
    # over in two dimensions: the reason names the first
    w.place(rng, w.nodes[13], cpu=8000, mem=17000)        # cpu
    w.place(rng, w.nodes[14], mem=33000, disk=200_000)    # memory


def _all_at_once(w, rng):
    w.plan.all_at_once = True
    _stops(w, rng)
    w.place(rng, w.nodes[10], mbits=1500)


def _mostly_refused(w, rng):
    # three nodes refused, one placement committed
    w.plan.node_allocation.clear()
    for node in w.nodes[13:16]:
        w.place(rng, node, cpu=40_000)
    w.place(rng, w.nodes[12])


def _single(w, rng):
    # one placement, as a plan of `c1m-5k.singles` holds
    w.plan.node_allocation = dict(list(w.plan.node_allocation.items())[:1])


CASES = {"plain": lambda w, rng: None, "stacked": _stacked, "stops": _stops,
         "preemption": _preemption, "in_place": _in_place, "ports": _ports,
         "devices": _devices, "over_capacity": _over_capacity,
         "all_at_once": _all_at_once, "mostly_refused": _mostly_refused,
         "single": _single}


# ---- the reference: node by node, one allocation at a time ----------------


def _reference_commit(store, result):
    """`InMemState.upsert_plan_results` as it was before placements were
    committed in one pass: `StateStore.upsert_alloc` an allocation."""
    for allocs in result.node_update.values():
        for a in allocs:
            existing = store._allocs.get(a.id)
            if existing is not None:
                merged = copy.copy(existing)
                merged.desired_status = a.desired_status
                merged.desired_description = a.desired_description
                if a.client_status:
                    merged.client_status = a.client_status
                store.upsert_alloc(merged)
    for allocs in result.node_preemptions.values():
        for a in allocs:
            existing = store._allocs.get(a.id)
            if existing is not None:
                merged = copy.copy(existing)
                merged.desired_status = a.desired_status
                merged.desired_description = a.desired_description
                merged.preempted_by_allocation = a.preempted_by_allocation
                store.upsert_alloc(merged)
    for allocs in result.node_allocation.values():
        for a in allocs:
            existing = store._allocs.get(a.id)
            if existing is not None:
                a = copy.copy(a)
                a.client_status = existing.client_status
                a.client_description = existing.client_description
                a.task_states = existing.task_states
            if a.job is None:
                a.job = (store._job_versions.get(
                    (a.namespace, a.job_id, a.job_version))
                    or store._jobs.get((a.namespace, a.job_id)))
            store.upsert_alloc(a)
    if result.deployment is not None:
        store.upsert_deployment(result.deployment)


def _reference_apply(store, plan):
    """`PlanApplier._apply`'s verify and commit, node by node and one
    allocation at a time. Returns (result, verdicts, rejected)."""
    result = PlanResult(
        node_update={k: list(v) for k, v in plan.node_update.items()},
        node_allocation={}, node_preemptions={},
        deployment=plan.deployment,
        deployment_updates=list(plan.deployment_updates))
    verdicts, rejected = {}, []
    touched = set(plan.node_allocation) | set(plan.node_preemptions)
    with store.mutation_lock():
        for node_id in touched:
            verdicts[node_id] = fit, _ = evaluate_node_plan(
                store, plan, node_id)
            if fit:
                if node_id in plan.node_allocation:
                    result.node_allocation[node_id] = list(
                        plan.node_allocation[node_id])
                if node_id in plan.node_preemptions:
                    result.node_preemptions[node_id] = list(
                        plan.node_preemptions[node_id])
            else:
                rejected.append(node_id)
    if rejected and plan.all_at_once:
        result.node_update.clear()
        result.node_allocation.clear()
        result.node_preemptions.clear()
        result.deployment = None
        result.deployment_updates = []
    for allocs in result.node_allocation.values():
        for a in allocs:
            a.create_time = a.create_time or NOW
            a.modify_time = NOW
    cl = store.cluster
    rej_rows = [cl.row_of[n] for n in rejected if n in cl.row_of] or None
    with store._cv:
        depth = store._emit_enter()
        before = store.index.value
        v_lo = cl.version
        try:
            _reference_commit(store, result)
        finally:
            store._emit_exit(depth)
        store._emit_entry("upsert_plan_results", (plan, result), before)
        cl.mark_plan_window(plan.eval_id, v_lo, cl.version,
                            clean=not rejected, exact=plan.carry_exact,
                            token=plan.carry_token, rejected_rows=rej_rows)
    result.alloc_index = store.index.value
    if rejected:
        result.refresh_index = store.index.value
    return result, verdicts, rejected


def _program_apply(store, plan, monkeypatch):
    """The program's `PlanApplier.apply`; returns (result, rejected,
    n_rejected, applier) — the rejected nodes in order, from its
    `plan.partial` flight event."""
    monkeypatch.setattr(plan_apply.time, "time", lambda: NOW)
    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(store, queue)
    mark = default_flight().last_index()
    result = applier.apply(plan)
    monkeypatch.undo()
    _, evs = default_flight().records_after(mark, types=["plan.partial"])
    evs = [e for e in evs if e["key"] == plan.eval_id]
    rejected = evs[-1]["detail"]["rejected_nodes"] if evs else []
    n_rejected = evs[-1]["detail"]["n_rejected"] if evs else 0
    return result, rejected, n_rejected, applier


# ---- what the two sides are compared by -----------------------------------


def _tables(store):
    def ids(m):
        return {k: list(v) for k, v in m.items()}

    idx = getattr(store, "index_at", None) or getattr(
        store.index, "value", None) or repr(store.index)
    return (list(store._allocs), ids(store._allocs_by_job),
            ids(store._allocs_by_node),
            [(a.id, a.create_index, a.modify_index, a.create_time,
              a.modify_time, a.client_status, a.desired_status,
              a.job.version if a.job else None)
             for a in store._allocs.values()], idx)


def _ledgers(cl):
    return ({k: (r, u.tobytes()) for k, (r, u) in cl.alloc_usage.items()},
            list(cl.alloc_usage),
            {k: (r, list(p)) for k, (r, p) in cl.alloc_ports.items()},
            [dict(refs) for refs in cl.port_refs],
            {k: (r, list(d)) for k, (r, d) in cl.alloc_devices.items()},
            [dict(refs) for refs in cl.device_refs],
            {j: list(m.items()) for j, m in cl.job_allocs.items()},
            cl.ports_used.tobytes(), cl.dyn_free.tobytes())


def _window(cl):
    """The plan's commit window, its versions left out, and the union of
    the hot rows logged inside it."""
    v_lo, v_hi, eid, covered, token, rej = cl.plan_windows_since(0)[-1]
    assert v_hi == cl.version
    entries = cl.hot_entries_since(v_lo, cl.n_cap)
    assert entries is not None and all(ver <= v_hi for ver, _ in entries)
    return (eid, covered, token, rej,
            sorted(set().union(*[set(rs) for _, rs in entries])))


def _placements(plan):
    return [a for allocs in plan.node_allocation.values() for a in allocs
            if not a.terminal_status()]


def _program_verdicts(store, plan):
    """Each node judged from the rows read once, as `_apply` judges it."""
    touched = set(plan.node_allocation) | set(plan.node_preemptions)
    rows = store.cluster.placement_rows(_placements(plan))
    return {n: evaluate_node_plan(store, plan, n, rows) for n in touched}


def _new_placements(store, result):
    """The placements of `result` the store does not hold yet: what the
    commit takes in one pass."""
    return sum(1 for allocs in result.node_allocation.values()
               for a in allocs if a.id not in store._allocs
               and not a.terminal_status())


def _compare(case, monkeypatch, tmp_path, **size):
    ref = World(case, **size)
    got = World(case, **size)
    follower = World(case, **size)
    durable = World(case, store=DurableStateStore(Wal(str(tmp_path / "w"))),
                    **size)
    assert _tables(ref.store) == _tables(got.store)

    # verdicts and reasons, node for node, before anything commits
    verdicts = _program_verdicts(got.store, got.plan)
    n_new = _new_placements(ref.store, ref.plan)
    ref_result, ref_verdicts, ref_rejected = _reference_apply(ref.store,
                                                              ref.plan)
    assert verdicts == ref_verdicts
    before = got.store.snapshot()
    held = (_tables(before), {k: v for k, v in before._allocs_by_node.items()})

    result, rejected, n_rejected, applier = _program_apply(
        got.store, got.plan, monkeypatch)
    # the rejected nodes, in order (the flight event holds the first 8)
    assert rejected == ref_rejected[:8] and n_rejected == len(ref_rejected)
    assert list(result.node_allocation) == list(ref_result.node_allocation)
    assert (result.alloc_index, result.refresh_index) == (
        ref_result.alloc_index, ref_result.refresh_index)
    # the store: tables, indexes, `used`, ledgers, hot rows, events
    assert _tables(got.store) == _tables(ref.store)
    cl, rcl = got.store.cluster, ref.store.cluster
    assert cl.used.dtype == np.float64
    assert cl.used.tobytes() == rcl.used.tobytes()
    assert _ledgers(cl) == _ledgers(rcl)
    assert _window(cl) == _window(rcl)
    assert events_fingerprint(got.store.event_broker.buffered()) == \
        events_fingerprint(ref.store.event_broker.buffered())
    fp = state_fingerprint(ref.store)
    assert state_fingerprint(got.store) == fp
    # a snapshot taken before the commit does not see it
    assert _tables(before) == held[0]
    assert all(before._allocs_by_node[k] is v for k, v in held[1].items())
    assert not before.allocs_by_job(got.job.namespace, got.job.id)

    # a raft follower applies the entry as the wire carries it: no rows
    # ride along, the store reads the rows from the decoded allocations
    entry = {"op": "upsert_plan_results",
             "args": _encode_args("upsert_plan_results", (got.plan, result))}
    FSM(follower.store).apply(entry)
    assert state_fingerprint(follower.store) == fp
    assert follower.store.cluster.used.tobytes() == rcl.used.tobytes()
    assert _ledgers(follower.store.cluster) == _ledgers(rcl)
    assert events_fingerprint(follower.store.event_broker.buffered()) == \
        events_fingerprint(ref.store.event_broker.buffered())

    # journaled, then replayed from the WAL into a fresh store
    _program_apply(durable.store, durable.plan, monkeypatch)
    replayed = DurableStateStore(Wal(str(tmp_path / "w")))
    replayed.restore()
    assert state_fingerprint(replayed) == fp
    assert replayed.cluster.used.tobytes() == rcl.used.tobytes()
    assert _ledgers(replayed.cluster) == _ledgers(rcl)
    # every new placement the commit took went in one pass
    assert _counters(applier)[0] == n_new - _new_placements(ref.store,
                                                            ref.plan)
    return applier, verdicts, ref_rejected


@pytest.mark.parametrize("case", sorted(CASES))
def test_one_pass_equals_one_allocation_at_a_time(
        case, monkeypatch, tmp_path):
    applier, verdicts, rejected = _compare(case, monkeypatch, tmp_path)
    reasons = {verdicts[n][1] for n in rejected}
    expect = {
        "ports": {"port 25001 already in use", "port 22 already in use",
                  "port 25000 collision in plan"},
        "devices": {plan_apply.REASON_DEVICES},
        "over_capacity": {"cpu", "memory", "disk", "network",
                          plan_apply.REASON_DEVICES},
        "all_at_once": {"network"},
        "mostly_refused": {"cpu"},
    }.get(case, set())
    assert reasons == expect


def test_a_thousand_placements_over_five_thousand_nodes(monkeypatch,
                                                        tmp_path):
    applier, verdicts, rejected = _compare(
        "plain", monkeypatch, tmp_path, n_nodes=5000, n_standing=5000,
        n_plain=1000)
    assert len(verdicts) == 1000 and not rejected
    c = applier.metrics.snapshot()["counters"]
    assert c["plan_apply.array_allocs"] == c["plan_apply.placed_allocs"] \
        == 1000


def test_rows_read_before_a_device_column_was_added_are_read_again():
    """The commit takes the store's lock anew after the verify that read
    the rows; a node with a new device pool may land in between. The rows
    then lack its column, so the commit reads them again."""
    from nomad_tpu.structs.resources import (NodeDeviceInstance,
                                             NodeDeviceResource)

    def fpga_node(w):
        node = copy.deepcopy(w.nodes[1])
        node.id, node.name = "fpga-node", "fpga-node"
        node.node_resources.devices = [NodeDeviceResource(
            vendor="xilinx", type="fpga", name="u250",
            instances=[NodeDeviceInstance(id="f0")])]
        return node

    def accepted(plan):
        return PlanResult(node_update={}, node_preemptions={},
                          node_allocation={k: list(v) for k, v
                                           in plan.node_allocation.items()})

    ref, got = World("plain"), World("plain")
    ref.store.upsert_node(fpga_node(ref))
    _reference_commit(ref.store, accepted(ref.plan))
    cl = got.store.cluster
    rows = cl.placement_rows(_placements(got.plan))
    got.store.upsert_node(fpga_node(got))
    assert len(cl.device_cols) == rows.device_cols + 1
    reads = []
    real = cl.placement_rows
    cl.placement_rows = lambda allocs: reads.append(len(allocs)) or real(allocs)
    n = got.store.upsert_plan_results(got.plan, accepted(got.plan), rows)
    assert n == len(got.plan.node_allocation) and reads == [n]
    assert cl.used.tobytes() == ref.store.cluster.used.tobytes()
    assert _ledgers(cl) == _ledgers(ref.store.cluster)
    assert _tables(got.store) == _tables(ref.store)


@pytest.mark.parametrize("case", ["plain", "stacked", "stops", "in_place",
                                  "devices"])
def test_the_harness_store_commits_as_one_upsert_alloc_at_a_time(case):
    """`InMemState`, the scheduler tests' store, writes its maps in place
    (no snapshots to keep): its one-pass commit against its own
    `upsert_alloc`, one allocation at a time."""
    from nomad_tpu.scheduler.harness import InMemState

    ref, got = World(case, store=InMemState()), World(case, store=InMemState())
    for w in (ref, got):
        w.result = PlanResult(
            node_update={k: list(v) for k, v in w.plan.node_update.items()},
            node_preemptions={}, node_allocation={
                k: list(v) for k, v in w.plan.node_allocation.items()})
    _reference_commit(ref.store, ref.result)
    n = got.store.upsert_plan_results(
        got.plan, got.result,
        got.store.cluster.placement_rows(_placements(got.plan)))
    assert n == len(_placements(got.plan)) - (case == "in_place")
    assert _tables(got.store) == _tables(ref.store)
    assert got.store.cluster.used.tobytes() == ref.store.cluster.used.tobytes()
    assert _ledgers(got.store.cluster) == _ledgers(ref.store.cluster)


# ---- the counters --------------------------------------------------------


def _counters(applier):
    c = applier.metrics.snapshot()["counters"]
    return c["plan_apply.array_allocs"], c["plan_apply.placed_allocs"]


@pytest.mark.parametrize("n_plain", [1, 8, 24])
def test_every_placement_of_a_placements_only_plan_is_counted_in_one_pass(
        n_plain, monkeypatch):
    w = World("plain", n_plain=n_plain)
    _, _, _, applier = _program_apply(w.store, w.plan, monkeypatch)
    array, placed = _counters(applier)
    assert placed == n_plain
    assert array == placed


def test_a_refused_node_commits_nothing_in_one_pass(monkeypatch):
    w = World("mostly_refused")
    _, _, _, applier = _program_apply(w.store, w.plan, monkeypatch)
    assert _counters(applier) == (1, 4)


@pytest.mark.parametrize("case,off", [("stops", 0), ("preemption", 0),
                                      ("in_place", 1)])
def test_only_an_allocation_the_store_holds_is_committed_off_the_one_pass_path(
        case, off, monkeypatch):
    """Stops and preemptions beside a node's placements leave them in the
    one pass; the copy of a live allocation (an in-place update) goes
    through `upsert_alloc`."""
    w = World(case)
    placed = len(_placements(w.plan))
    _, _, _, applier = _program_apply(w.store, w.plan, monkeypatch)
    assert _counters(applier) == (placed - off, placed)
