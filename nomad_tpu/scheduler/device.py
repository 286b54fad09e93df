"""Device instance allocation.

Behavioral reference: `scheduler/device.go` — `deviceAllocator` :13 wraps a
`structs.DeviceAccounter` over the node's proposed allocs; `AssignDevice` :32
picks the best matching device group (suffix-specificity id match, healthy
free instances ≥ count, ask constraints against device attributes, affinity
scoring) and returns concrete instance IDs.

Placement-kernel split: node *selection* uses the count-based device columns
in `tensor/cluster.py` (fast path) plus a host-evaluated per-node device
feasibility mask when asks carry constraints (`DeviceChecker`,
feasible.go:1138); instance IDs are assigned host-side at offer time — the
same two-tier design as ports. Documented deviation: device *affinities*
influence which device group's instances are picked on the chosen node, not
the node choice itself (the reference folds the affinity score into the node
score, rank.go:301-320); the oracle mirrors the kernel so parity holds.

Fused batches: the kernel's chain counts devices and is right, but each
eval draws its instance IDs from its OWN snapshot plus its own plan, which
holds nothing of what its batch-mates drew or committed meanwhile. The plan
applier verifies instance IDs at the commit point like ports
(`server/plan_apply.py`, reason `devices`) and the rejected node is offered
again on a refreshed snapshot — the reference's way. `DeviceHolds` keeps
batch-mates from colliding in the first place: the offer also excludes what
the cluster's live ledger (`tensor/cluster.py device_refs`) shows committed
and what other evals still building their plans were handed.
"""
from __future__ import annotations

import threading
import weakref
from typing import Dict, List, Optional, Tuple

from ..structs.devices import DeviceAccounter
from ..structs.resources import (AllocatedDeviceResource, NodeDeviceResource,
                                 RequestedDevice)
from ..tensor.cluster import device_keys


def _device_value(dev: NodeDeviceResource, target: str) -> Tuple[Optional[str], bool]:
    """Resolve a constraint/affinity LTarget against a device group
    (reference nodeDeviceMatches / resolveDeviceTarget, device.go:125):
    ${device.model}, ${device.vendor}, ${device.type}, ${device.ids},
    ${device.attr.<key>}."""
    t = target
    if t.startswith("${") and t.endswith("}"):
        t = t[2:-1]
    if t == "device.model":
        return dev.name, True
    if t == "device.vendor":
        return dev.vendor, True
    if t == "device.type":
        return dev.type, True
    if t.startswith("device.attr."):
        v = dev.attributes.get(t[len("device.attr."):])
        return (None, False) if v is None else (str(v), True)
    # non-device targets resolve as literals (constants)
    return target, True


def device_meets_constraints(dev: NodeDeviceResource, constraints) -> bool:
    from .oracle import check_constraint

    for c in constraints:
        lval, lok = _device_value(dev, c.ltarget)
        rval, rok = _device_value(dev, c.rtarget)
        if not check_constraint(c.operand, lval, rval, lok, rok):
            return False
    return True


def _affinity_score(dev: NodeDeviceResource, affinities) -> float:
    from .oracle import check_constraint

    if not affinities:
        return 0.0
    sum_w = sum(abs(float(a.weight)) for a in affinities) or 1.0
    total = 0.0
    for a in affinities:
        lval, lok = _device_value(dev, a.ltarget)
        rval, rok = _device_value(dev, a.rtarget)
        if check_constraint(a.operand, lval, rval, lok, rok):
            total += float(a.weight)
    return total / sum_w


class DeviceAllocator:
    """Reference deviceAllocator (device.go:13): DeviceAccounter over the
    node's proposed allocs, consumed incrementally as asks are assigned."""

    def __init__(self, node, proposed_allocs) -> None:
        self.node = node
        self.accounter = DeviceAccounter(node)
        self.accounter.add_allocs(proposed_allocs)
        self._groups = {d.id(): d for d in node.node_resources.devices}

    def assign(self, ask: RequestedDevice
               ) -> Tuple[Optional[AllocatedDeviceResource], str]:
        """Reference AssignDevice (device.go:32): best-scoring matching
        group with enough healthy free instances; returns instance IDs."""
        best: Optional[NodeDeviceResource] = None
        best_free: List[str] = []
        best_score = 0.0
        for dev_id, dev in self._groups.items():
            if not dev.matches(ask.name):
                continue
            if ask.constraints and not device_meets_constraints(
                    dev, ask.constraints):
                continue
            healthy = {i.id for i in dev.instances if i.healthy}
            free = [i for i in self.accounter.free_instances(dev_id)
                    if i in healthy]
            if len(free) < ask.count:
                continue
            score = _affinity_score(dev, ask.affinities)
            if best is None or score > best_score:
                best, best_free, best_score = dev, free, score
        if best is None:
            return None, f"no devices match request {ask.name!r}"
        offer = AllocatedDeviceResource(
            vendor=best.vendor, type=best.type, name=best.name,
            device_ids=sorted(best_free)[: ask.count],
        )
        self.accounter.add_reserved(offer)
        return offer, ""


def node_devices_feasible(node, asks) -> bool:
    """Per-node feasibility for a list of device asks (reference
    DeviceChecker, feasible.go:1138): each ask needs a matching group with
    enough healthy instances — installed capacity; proposed-usage fit
    happens at rank time (pool columns in the kernel) and offer time
    (DeviceAllocator)."""
    for ask in asks:
        ok = False
        for dev in node.node_resources.devices:
            if not dev.matches(ask.name):
                continue
            if ask.constraints and not device_meets_constraints(
                    dev, ask.constraints):
                continue
            if sum(1 for i in dev.instances if i.healthy) >= ask.count:
                ok = True
                break
        if not ok:
            return False
    return True


def node_device_feasible(node, tg) -> bool:
    return node_devices_feasible(
        node, [a for t in tg.tasks for a in t.resources.devices])


def assign_task_devices(allocator: DeviceAllocator, tg):
    """Assign every task's device asks from one allocator (shared by the
    scheduler offer path, the oracle, and the bench parity loop). Returns
    ({task name: [AllocatedDeviceResource]}, err) — err non-empty means the
    node cannot satisfy the group."""
    out = {}
    for t in tg.tasks:
        for ask in t.resources.devices:
            offer, err = allocator.assign(ask)
            if offer is None:
                return None, f"task {t.name}: {err}"
            out.setdefault(t.name, []).append(offer)
    return out, ""


def asks_devices(tg) -> bool:
    return any(t.resources.devices for t in tg.tasks)


class DeviceHolds:
    """Instance IDs handed to evals whose plans are still being built or
    applied, per node: {node id: {(group id, instance id): eval id}}.
    Leader-side scratch, never state: an eval's holds go when its attempt
    ends (committed IDs are in the cluster's ledger by then, rejected ones
    are free again). The lock is taken only by groups that ask for a
    device."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._by_node: Dict[str, Dict[Tuple[str, str], str]] = {}
        self._by_owner: Dict[str, List[Tuple[str, Tuple[str, str]]]] = {}

    def assign(self, cluster, node, allocator: DeviceAllocator, tg, plan):
        """`assign_task_devices` with the IDs excluded that a live alloc
        holds in `cluster`'s ledger (unless `plan` releases it) or that
        another eval was handed; what is assigned is held for
        `plan.eval_id`."""
        released = {a.id for a in plan.node_update.get(node.id, ())}
        released.update(a.id for a in plan.node_preemptions.get(node.id, ()))
        released.update(a.id for a in plan.node_allocation.get(node.id, ()))
        row = cluster.row_of.get(node.id)
        owner = plan.eval_id
        with self._lock:
            held = self._by_node.get(node.id, {})
            taken = [k for k, o in held.items() if o != owner]
            if row is not None:
                taken += [k for k, holders in
                          tuple(cluster.device_refs[row].items())
                          if any(h not in released for h in holders)]
            for group, inst in taken:
                acct = allocator.accounter.devices.get(group)
                if acct is not None and acct.instances.get(inst) == 0:
                    acct.instances[inst] = 1
            offers, err = assign_task_devices(allocator, tg)
            if offers is not None:
                held = self._by_node.setdefault(node.id, held)
                mine = self._by_owner.setdefault(owner, [])
                for task_offers in offers.values():
                    for key in device_keys(task_offers):
                        held[key] = owner
                        mine.append((node.id, key))
        return offers, err

    def release(self, owner: str) -> None:
        with self._lock:
            for node_id, key in self._by_owner.pop(owner, ()):
                held = self._by_node.get(node_id)
                if held is not None and held.get(key) == owner:
                    del held[key]
                    if not held:
                        del self._by_node[node_id]


#: cluster -> DeviceHolds (weak: the holds die with their cluster)
_HOLDS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_HOLDS_LOCK = threading.Lock()


def holds_for(cluster) -> DeviceHolds:
    with _HOLDS_LOCK:
        h = _HOLDS.get(cluster)
        if h is None:
            h = _HOLDS[cluster] = DeviceHolds()
        return h
