"""The offer step builds no per-node index for a group that asks for no
port and no device (ISSUE 29).

`allocated_resources` used to build, for EVERY allocation, the node's
proposed allocs, a `NetworkIndex` and a `DeviceAllocator`, and to consult
them only where the group asks for a port or a device. The plain reference
here is that code, copied from the parent commit as it was
(`indexed_reference`): whatever the group and whatever lives on the node,
the program grants field for field what it grants, and builds the indexes
exactly where the group asks for something they decide.

CPU: counts and equality, never a rate.
"""
import random
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.lib.metrics import default_registry
from nomad_tpu.scheduler import generic
from nomad_tpu.scheduler.device import DeviceAllocator, assign_task_devices
from nomad_tpu.scheduler.harness import Harness
from nomad_tpu.scheduler.util import proposed_allocs
from nomad_tpu.structs import (AllocatedResources, AllocatedSharedResources,
                               AllocatedTaskResources, NetworkIndex,
                               NetworkResource, Plan, RequestedDevice,
                               Resources, Task)
from nomad_tpu.structs.resources import AllocatedDeviceResource, Port
from nomad_tpu.synth import synth_node, synth_service_job

TERMINAL = ("complete", "failed", "blocked", "cancelled")


def indexed_reference(state, plan, tg, node):
    """`scheduler/generic.py allocated_resources` of the parent commit,
    line for line: the indexes are built for every placement."""
    tasks = {}
    shared = AllocatedSharedResources(disk_mb=tg.ephemeral_disk.size_mb)
    net_idx = None
    dev_offers = {}
    if node is not None:
        proposed = proposed_allocs(state, plan, node.id)
        net_idx = NetworkIndex()
        net_idx.set_node(node)
        net_idx.add_allocs(proposed)
        offers, derr = assign_task_devices(
            DeviceAllocator(node, proposed), tg)
        if offers is None:
            return None, derr
        dev_offers = offers
    for t in tg.tasks:
        tr = AllocatedTaskResources(
            cpu=t.resources.cpu, memory_mb=t.resources.memory_mb,
            devices=list(dev_offers.get(t.name, ())),
        )
        for ask in t.resources.networks:
            if net_idx is not None:
                offer, err = net_idx.assign_network(ask)
                if offer is None:
                    return None, err or f"task {t.name}: no network offer"
                net_idx.add_reserved(offer)
                tr.networks.append(offer)
        tasks[t.name] = tr
    for ask in tg.networks:
        if net_idx is not None:
            offer, err = net_idx.assign_network(ask)
            if offer is None:
                return None, err or "group network: no offer"
            net_idx.add_reserved(offer)
            shared.networks.append(offer)
    return AllocatedResources(tasks=tasks, shared=shared), None


class Built:
    """How many `NetworkIndex` and `DeviceAllocator` the scheduler
    constructs while this is planted in `scheduler/generic.py`."""

    def __init__(self, monkeypatch):
        self.network_index = self.device_allocator = 0
        built = self

        class CountedIndex(NetworkIndex):
            def __init__(self):
                built.network_index += 1
                super().__init__()

        class CountedAllocator(DeviceAllocator):
            def __init__(self, node, proposed):
                built.device_allocator += 1
                super().__init__(node, proposed)

        monkeypatch.setattr(generic, "NetworkIndex", CountedIndex)
        monkeypatch.setattr(generic, "DeviceAllocator", CountedAllocator)


# ---- (a) the offer alone: every node state x every kind of group -----------


def _live(node, job, networks=None, devices=None, cpu=20, memory_mb=16):
    a = mock.alloc(job=job, node_id=node.id, client_status="running")
    a.allocated_resources = mock.alloc_resources(
        cpu=cpu, memory_mb=memory_mb, disk_mb=10, networks=networks)
    if devices:
        a.allocated_resources.tasks["web"].devices = [AllocatedDeviceResource(
            vendor="nvidia", type="gpu", name="1080ti",
            device_ids=list(devices))]
    return a


def _node_state(kind):
    """(state, node, plan) with `kind` of things living on the node; the
    plan already holds two placements there, as a plan half built does."""
    rng = random.Random(29)
    h = Harness()
    node = synth_node(rng, 4)  # every fourth node: four GPUs
    h.state.upsert_node(node)
    filler = mock.job()
    h.state.upsert_job(filler)
    ip = node.node_resources.networks[0].ip
    live = []
    if kind == "live200":
        live = [_live(node, filler) for _ in range(200)]
    elif kind == "colliding-ports":
        # two live allocs reserve one static port, another holds the
        # first dynamic ones: `add_allocs` reports a collision (which
        # nothing reads) and the first-fit picker has to pass them
        for _ in range(2):
            live.append(_live(node, filler, networks=[NetworkResource(
                device="eth0", ip=ip, mbits=10,
                reserved_ports=[Port("admin", 8080)])]))
        live.append(_live(node, filler, networks=[NetworkResource(
            device="eth0", ip=ip, mbits=10,
            dynamic_ports=[Port("a", 20000), Port("b", 20001)])]))
    elif kind == "gpus-partly-taken":
        live = [_live(node, filler, devices=["gpu-4-0", "gpu-4-2"])]
    elif kind != "empty":
        raise ValueError(kind)
    for a in live:
        h.state.upsert_alloc(a)
    plan = Plan(eval_id="ev-offer", job=filler)
    for _ in range(2):
        plan.append_alloc(_live(node, filler, cpu=30))
    return h.state, node, plan


def _group(kind):
    job = synth_service_job(random.Random(7), count=4)
    tg = job.task_groups[0]
    if kind == "two-tasks":
        tg.tasks.append(Task(name="sidecar", driver="exec",
                             resources=Resources(cpu=40, memory_mb=32)))
    elif kind == "group-network":
        tg.networks = [NetworkResource(
            mbits=50, dynamic_ports=[Port(label="http"), Port(label="adm")])]
    elif kind == "task-port":
        tg.tasks[0].resources.networks = [NetworkResource(
            mbits=20, reserved_ports=[Port("admin", 8080)],
            dynamic_ports=[Port(label="http")])]
    elif kind == "device-ask":
        tg.tasks[0].resources.devices = [
            RequestedDevice(name="nvidia/gpu", count=2)]
    elif kind != "one-task":
        raise ValueError(kind)
    return tg


PLAIN_GROUPS = ("one-task", "two-tasks")
ASKING_GROUPS = ("group-network", "task-port", "device-ask")
NODE_STATES = ("empty", "live200", "colliding-ports", "gpus-partly-taken",
               "no-node")


@pytest.mark.parametrize("node_kind", NODE_STATES)
@pytest.mark.parametrize("group_kind", PLAIN_GROUPS + ASKING_GROUPS)
def test_offer_is_the_indexed_code_s_field_for_field(group_kind, node_kind,
                                                     monkeypatch):
    state, node, plan = _node_state(
        "empty" if node_kind == "no-node" else node_kind)
    if node_kind == "no-node":
        node = None
    tg = _group(group_kind)
    want, want_err = indexed_reference(state, plan, tg, node)

    built = Built(monkeypatch)
    got, got_err = generic.allocated_resources(state, plan, tg, node)
    assert got == want and (got_err or None) == (want_err or None)

    asks = group_kind in ASKING_GROUPS
    assert generic.offer_needs_node(tg) == asks
    # the indexes are built where the group asks for a port or a device
    # and a node is there to index, and nowhere else
    n = int(asks and node is not None)
    assert (built.network_index, built.device_allocator) == (n, n)

    if got is None:
        # a placement that must fail, with the reference's reason
        assert got_err and (group_kind, node_kind) in {
            ("task-port", "colliding-ports")}
        return
    # a fresh object per allocation: in-place updates and the client
    # write into it
    again, _ = generic.allocated_resources(state, plan, tg, node)
    assert again == got and again is not got
    assert again.shared is not got.shared
    for name, tr in got.tasks.items():
        assert again.tasks[name] is not tr
        assert again.tasks[name].devices is not tr.devices
        assert again.tasks[name].networks is not tr.networks
    if not asks:
        assert got == AllocatedResources(
            tasks={t.name: AllocatedTaskResources(
                cpu=t.resources.cpu, memory_mb=t.resources.memory_mb,
                networks=[], devices=[]) for t in tg.tasks},
            shared=AllocatedSharedResources(disk_mb=150, networks=[]))
    elif node is None:
        pass  # nothing to assign from: cpu, memory, disk (the reference's)
    elif group_kind == "device-ask":
        ids = got.tasks["web"].devices[0].device_ids
        assert ids == (["gpu-4-1", "gpu-4-3"]
                       if node_kind == "gpus-partly-taken"
                       else ["gpu-4-0", "gpu-4-1"])
    else:
        nets = (got.shared.networks if group_kind == "group-network"
                else got.tasks["web"].networks)
        dyn = [p.value for p in nets[0].dynamic_ports]
        first = 20002 if node_kind == "colliding-ports" else 20000
        assert dyn == list(range(first, first + len(dyn)))


# ---- (b) whole plans through the Harness -----------------------------------


def _cluster(n_nodes=64, seed=29):
    rng = random.Random(seed)
    h = Harness()
    for i in range(n_nodes):
        h.state.upsert_node(synth_node(rng, i))
    return h


def _job(kind, count, seed=3):
    rng = random.Random(seed)
    job = synth_service_job(rng, count=count, with_devices=(kind == "devices"))
    t = job.task_groups[0].tasks[0]
    t.resources.cpu, t.resources.memory_mb = 37, 29  # c1m-5k's asks
    if kind == "ports":
        job.task_groups[0].networks = [NetworkResource(
            mbits=1, dynamic_ports=[Port(label="http")])]
    return job


def _process(h, job):
    h.state.upsert_job(job)
    h.process(mock.eval_(job_id=job.id, type=job.type,
                         priority=job.priority))
    assert h.evals[-1].status == "complete"
    return h.plans[-1]


def _offers():
    c = default_registry().counters()
    return c.get("sched.offers", 0), c.get("sched.offers_skipped", 0)


def _plan_rows(plan):
    """What a plan decided, allocation by allocation (ids are random)."""
    rows = {}
    for node_id, allocs in plan.node_allocation.items():
        for a in allocs:
            # the metrics hold the scores: the served node's and the
            # top nodes', with every component
            rows[a.name] = (node_id, a.metrics, a.allocated_resources)
    return rows


@pytest.mark.parametrize("kind,count,offers,skipped,indexes", [
    ("binpack", 1000, 1000, 1000, 0),
    ("devices", 8, 8, 0, 8),
    ("ports", 8, 8, 0, 8),
])
def test_count_gate_of_one_job(kind, count, offers, skipped, indexes,
                               monkeypatch):
    """One job through the scheduler: so many offers, so many of them
    without an index, so many `NetworkIndex` and `DeviceAllocator` built
    (a thousand-allocation bin-pack job: none)."""
    h = _cluster()
    built = Built(monkeypatch)
    o0, s0 = _offers()
    plan = _process(h, _job(kind, count))
    o1, s1 = _offers()
    assert sum(len(v) for v in plan.node_allocation.values()) == count
    assert (o1 - o0, s1 - s0) == (offers, skipped)
    assert (built.network_index, built.device_allocator) == (indexes,
                                                             indexes)


def test_system_job_counts_its_offers(monkeypatch):
    """The system scheduler shares the offer step: one offer a node, an
    index for each only when the group asks for a port."""
    for with_port, skipped in ((False, 16), (True, 0)):
        h = _cluster(16)
        job = mock.system_job()
        job.datacenters = ["dc1", "dc2", "dc3"]
        if not with_port:
            job.task_groups[0].networks = []
            for t in job.task_groups[0].tasks:
                t.resources.networks = []
        assert generic.offer_needs_node(job.task_groups[0]) == with_port
        built = Built(monkeypatch)
        o0, s0 = _offers()
        plan = _process(h, job)
        o1, s1 = _offers()
        assert sum(len(v) for v in plan.node_allocation.values()) == 16
        assert (o1 - o0, s1 - s0) == (16, skipped)
        assert built.network_index == 16 - skipped


@pytest.mark.parametrize("kind,count", [("binpack", 1000), ("devices", 8),
                                        ("ports", 8)])
def test_plan_is_the_one_built_with_every_index(kind, count, monkeypatch):
    """The same job on the same cluster with the skip disabled (every
    group taken as asking): node ids, scores, resources and the carry's
    certificate are the same, allocation by allocation."""
    plan = _process(_cluster(), _job(kind, count))
    with monkeypatch.context() as m:
        m.setattr(generic, "offer_needs_node", lambda tg: True)
        built = Built(m)
        ref = _process(_cluster(), _job(kind, count))
        assert built.network_index == built.device_allocator == count
    rows, ref_rows = _plan_rows(plan), _plan_rows(ref)
    assert len(rows) == count and rows == ref_rows
    assert (plan.carry_exact, plan.carry_token) == \
        (ref.carry_exact, ref.carry_token)


# ---- (c) the served path: fused dispatch, the carry's certificate ----------


def _serve(monkeypatch, jobs):
    """`jobs`, outstanding together, through a Server's batched worker
    (the coordinator's fused dispatch, so plans are certified for the
    device carry): the plans as submitted, the allocations for which
    `_certify_carry_exact` was called, and the server's counters."""
    from nomad_tpu.scheduler.generic import GenericScheduler
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.server.worker import EvalContext

    monkeypatch.delenv("NOMAD_TPU_EVAL_BATCH", raising=False)
    plans, certified = {}, []
    submit = EvalContext.submit_plan
    certify = GenericScheduler._certify_carry_exact

    def submit_plan(self, plan):
        assert plan.job.id not in plans, "a plan was refreshed"
        plans[plan.job.id] = (plan, plan.carry_exact,
                              plan.carry_token is not None)
        return submit(self, plan)

    def certify_carry_exact(self, alloc, ask):
        certified.append(alloc.name)
        return certify(self, alloc, ask)

    monkeypatch.setattr(EvalContext, "submit_plan", submit_plan)
    monkeypatch.setattr(GenericScheduler, "_certify_carry_exact",
                        certify_carry_exact)
    rng = random.Random(29)
    s = Server(ServerConfig(num_schedulers=1, heartbeat_ttl=3600.0,
                            eval_batch=8))
    for i in range(48):
        s.state.upsert_node(synth_node(rng, i))
    # registered before the workers start: one drain sees them all
    evs = [s.job_register(j) for j in jobs]
    s.start()
    try:
        for ev in evs:
            got = s.wait_for_eval(ev.id, statuses=TERMINAL, timeout=120.0)
            assert got is not None and got.status == "complete", got
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and s.metrics.counters().get(
                "worker.0.batch.batched", 0) < len(jobs):
            time.sleep(0.05)
        counters = s.metrics.counters()
        stats = dict(s.planner.stats)
    finally:
        s.shutdown()
    assert counters.get("worker.0.batch.batched", 0) == len(jobs)
    assert stats["partial"] == 0, stats
    return [plans[j.id] for j in jobs], certified, counters


@pytest.mark.parametrize("kind", ["binpack", "ports", "devices"])
def test_served_plans_certify_a_plain_group_once(kind, monkeypatch):
    # a job of `kind` and two plain batch-mates (which draw no port and
    # no instance id: who draws first among batch-mates is the threads')
    kinds, counts = (kind, "binpack", "binpack"), (32, 4, 4)

    def jobs():
        return [_job(k, c, seed=3 + i)
                for i, (k, c) in enumerate(zip(kinds, counts))]

    with monkeypatch.context() as m:
        plans, certified, counters = _serve(m, jobs())
    with monkeypatch.context() as m:
        m.setattr(generic, "offer_needs_node", lambda tg: True)
        ref_plans, ref_certified, ref_counters = _serve(m, jobs())
    for (plan, exact, token), (ref, ref_exact, ref_token), n in zip(
            plans, ref_plans, counts):
        rows = _plan_rows(plan)
        assert len(rows) == n and rows == _plan_rows(ref)
        assert exact is True and ref_exact is True
        assert token is True and ref_token is True
    # the certificate is taken once for a group whose allocations are all
    # granted the same, and for every allocation where an offer can differ
    plain = [n for k, n in zip(kinds, counts) if k == "binpack"]
    total = sum(counts)
    assert len(ref_certified) == total
    assert len(certified) == total - sum(plain) + len(plain)
    assert counters["sched.offers"] == ref_counters["sched.offers"] == total
    assert counters.get("sched.offers_skipped", 0) == sum(plain)
    assert ref_counters.get("sched.offers_skipped", 0) == 0
