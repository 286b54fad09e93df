"""Allocation model (reference `structs.Allocation`, nomad/structs/structs.go:8507)."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

from .job import Job, ReschedulePolicy
from .resources import AllocatedResources, ComparableResources, Resources

# Desired statuses (reference structs.go:8487-8493)
ALLOC_DESIRED_RUN = "run"
ALLOC_DESIRED_STOP = "stop"
ALLOC_DESIRED_EVICT = "evict"

# Client statuses (reference structs.go:8495-8502)
ALLOC_CLIENT_PENDING = "pending"
ALLOC_CLIENT_RUNNING = "running"
ALLOC_CLIENT_COMPLETE = "complete"
ALLOC_CLIENT_FAILED = "failed"
ALLOC_CLIENT_LOST = "lost"


@dataclass
class RescheduleEvent:
    """Reference `structs.RescheduleEvent` (structs.go:8943)."""

    reschedule_time: float = 0.0
    prev_alloc_id: str = ""
    prev_node_id: str = ""
    delay_s: float = 0.0


@dataclass
class RescheduleTracker:
    events: List[RescheduleEvent] = field(default_factory=list)


@dataclass
class DesiredTransition:
    """Reference `structs.DesiredTransition` (structs.go:8440): server-set
    hints — migrate (drain), reschedule (failed alloc may be replaced)."""

    migrate: Optional[bool] = None
    reschedule: Optional[bool] = None

    def should_migrate(self) -> bool:
        return bool(self.migrate)

    def should_reschedule(self) -> bool:
        return bool(self.reschedule)


@dataclass
class AllocDeploymentStatus:
    """Reference `structs.AllocDeploymentStatus` (structs.go:9094)."""

    healthy: Optional[bool] = None
    timestamp: float = 0.0
    canary: bool = False
    modify_index: int = 0

    def is_healthy(self) -> bool:
        return self.healthy is True

    def is_unhealthy(self) -> bool:
        return self.healthy is False


@dataclass
class NodeScoreMeta:
    """Per-node score breakdown kept in metrics (reference
    `structs.NodeScoreMeta`, structs.go:9268)."""

    node_id: str = ""
    scores: Dict[str, float] = field(default_factory=dict)
    norm_score: float = 0.0


TASK_STATE_PENDING = "pending"
TASK_STATE_RUNNING = "running"
TASK_STATE_DEAD = "dead"


@dataclass
class TaskEvent:
    """Reference `structs.TaskEvent` (structs.go:7049): typed lifecycle
    event with display message."""

    type: str = ""
    time: float = 0.0
    message: str = ""
    details: Dict[str, str] = field(default_factory=dict)


@dataclass
class TaskState:
    """Reference `structs.TaskState` (structs.go:6920)."""

    state: str = TASK_STATE_PENDING
    failed: bool = False
    restarts: int = 0
    last_restart: float = 0.0
    started_at: float = 0.0
    finished_at: float = 0.0
    events: List[TaskEvent] = field(default_factory=list)

    def successful(self) -> bool:
        return self.state == TASK_STATE_DEAD and not self.failed


@dataclass
class AllocMetric:
    """Placement metrics (reference `structs.AllocMetric`, structs.go:9172):
    nodes evaluated/filtered/exhausted counters, per-class/constraint
    breakdowns, top-K score metadata."""

    nodes_evaluated: int = 0
    nodes_filtered: int = 0
    nodes_available: Dict[str, int] = field(default_factory=dict)  # per-DC
    class_filtered: Dict[str, int] = field(default_factory=dict)
    constraint_filtered: Dict[str, int] = field(default_factory=dict)
    nodes_exhausted: int = 0
    class_exhausted: Dict[str, int] = field(default_factory=dict)
    dimension_exhausted: Dict[str, int] = field(default_factory=dict)
    quota_exhausted: List[str] = field(default_factory=list)
    score_meta: List[NodeScoreMeta] = field(default_factory=list)
    allocation_time_ns: int = 0
    coalesced_failures: int = 0

    def filter_node(self, node, reason: str) -> None:
        self.nodes_filtered += 1
        if node is not None and node.node_class:
            self.class_filtered[node.node_class] = self.class_filtered.get(node.node_class, 0) + 1
        if reason:
            self.constraint_filtered[reason] = self.constraint_filtered.get(reason, 0) + 1

    def exhausted_node(self, node, dimension: str) -> None:
        self.nodes_exhausted += 1
        if node is not None and node.node_class:
            self.class_exhausted[node.node_class] = self.class_exhausted.get(node.node_class, 0) + 1
        if dimension:
            self.dimension_exhausted[dimension] = self.dimension_exhausted.get(dimension, 0) + 1

    def score_node(self, node_id: str, name: str, score: float) -> None:
        for sm in self.score_meta:
            if sm.node_id == node_id:
                sm.scores[name] = score
                return
        sm = NodeScoreMeta(node_id=node_id, scores={name: score})
        self.score_meta.append(sm)

    def score_selected(self, node_id: str, score: float, k: int = 5) -> None:
        """The selected node's own final score over whatever the list
        holds for it: `score_node(node_id, "normalized-score", score)`,
        then `populate_score_meta(k)`. A list that came populated and
        in descending order (the kernel's top-K, scheduler/stack.py
        explain_columns) with the node first, and still first under the
        new score, is in that order already: nothing to search or
        sort."""
        top = self.score_meta
        if (top and len(top) <= k and top[0].node_id == node_id
                and (len(top) == 1 or score >= top[1].norm_score)):
            top[0].scores["normalized-score"] = score
            top[0].norm_score = score
            return
        self.score_node(node_id, "normalized-score", score)
        self.populate_score_meta(k)

    def populate_score_meta(self, k: int = 5) -> None:
        """Derive each node's norm_score from its "normalized-score" entry,
        then retain only the top-K nodes, descending (reference
        `AllocMetric.PopulateScoreMetaData` via `lib/kheap`)."""
        for sm in self.score_meta:
            if "normalized-score" in sm.scores:
                sm.norm_score = sm.scores["normalized-score"]
        if len(self.score_meta) <= k:
            self.score_meta.sort(key=lambda sm: -sm.norm_score)
            return
        from ..lib import KHeap

        h = KHeap(k)
        for sm in self.score_meta:
            h.push(sm.norm_score, sm)
        self.score_meta = h.items_desc()


@dataclass
class Allocation:
    """Reference `structs.Allocation` (structs.go:8507)."""

    id: str = ""
    namespace: str = "default"
    eval_id: str = ""
    name: str = ""          # "<job>.<group>[<index>]"
    node_id: str = ""
    node_name: str = ""
    job_id: str = ""
    job: Optional[Job] = None
    task_group: str = ""
    allocated_resources: Optional[AllocatedResources] = None
    metrics: AllocMetric = field(default_factory=AllocMetric)
    desired_status: str = ALLOC_DESIRED_RUN
    desired_description: str = ""
    desired_transition: DesiredTransition = field(default_factory=DesiredTransition)
    client_status: str = ALLOC_CLIENT_PENDING
    client_description: str = ""
    task_states: Dict[str, object] = field(default_factory=dict)
    deployment_id: str = ""
    deployment_status: Optional[AllocDeploymentStatus] = None
    reschedule_tracker: Optional[RescheduleTracker] = None
    follow_up_eval_id: str = ""
    previous_allocation: str = ""
    next_allocation: str = ""
    preempted_allocations: List[str] = field(default_factory=list)
    preempted_by_allocation: str = ""
    job_version: int = 0
    create_index: int = 0
    modify_index: int = 0
    alloc_modify_index: int = 0
    create_time: float = 0.0
    modify_time: float = 0.0
    # distributed-trace binding (ISSUE 17): LEADER-stamped in
    # plan_apply.apply next to the `now=` mint and riding the raft
    # entry, so replicas store identical ids (NLR01) and the client's
    # alloc_runner parents its alloc.start span under the leader's
    # plan.apply span (trace_span_id) with no extra RPC.
    trace_id: str = ""
    trace_span_id: str = ""

    def server_terminal_status(self) -> bool:
        """Reference `Allocation.ServerTerminalStatus` (structs.go:8831)."""
        return self.desired_status in (ALLOC_DESIRED_STOP, ALLOC_DESIRED_EVICT)

    def client_terminal_status(self) -> bool:
        """Reference `Allocation.ClientTerminalStatus` (structs.go:8842)."""
        return self.client_status in (
            ALLOC_CLIENT_COMPLETE,
            ALLOC_CLIENT_FAILED,
            ALLOC_CLIENT_LOST,
        )

    def terminal_status(self) -> bool:
        """Reference `Allocation.TerminalStatus` (structs.go:8820): desired
        stop/evict first, then terminal client statuses."""
        return self.server_terminal_status() or self.client_terminal_status()

    def allocated_networks(self, task_name: str = "") -> list:
        """Assigned networks — group shared first, then the task's
        (reference AllocatedResources walk used by taskenv, service
        registration, and drivers alike; ONE place so address/port
        resolution can't drift between consumers)."""
        ar = self.allocated_resources
        if ar is None:
            return []
        nets = list(ar.shared.networks) if ar.shared is not None else []
        if task_name:
            tr = (ar.tasks or {}).get(task_name)
            if tr is not None:
                nets += list(tr.networks)
        else:
            for tr in (ar.tasks or {}).values():
                nets += list(tr.networks)
        return nets

    def port_map(self, task_name: str = "") -> tuple:
        """(ip, {label: host_port}) across the alloc's assigned networks
        (rank.go AllocatedPortsToPortMap analog)."""
        ip = ""
        ports = {}
        for net in self.allocated_networks(task_name):
            ip = ip or net.ip
            for p in list(net.dynamic_ports) + list(net.reserved_ports):
                if p.label:
                    ports[p.label] = p.value
        return ip, ports

    def port_objects(self, task_name: str = "") -> tuple:
        """(ip, {label: Port}) — for consumers that need the `to`
        (inside-the-netns) side as well as the assigned host value."""
        ip = ""
        ports = {}
        for net in self.allocated_networks(task_name):
            ip = ip or net.ip
            for p in list(net.dynamic_ports) + list(net.reserved_ports):
                if p.label:
                    ports[p.label] = p
        return ip, ports

    def comparable_resources(self) -> ComparableResources:
        """Reference `Allocation.ComparableResources` (structs.go:8958)."""
        if self.allocated_resources is not None:
            return self.allocated_resources.comparable()
        return ComparableResources()

    def migrate_disk(self) -> bool:
        if self.job is None:
            return False
        tg = self.job.lookup_task_group(self.task_group)
        return tg is not None and tg.ephemeral_disk.sticky

    def index(self) -> int:
        """Parse the alloc index out of the name (reference
        `structs.AllocIndexFromName` / `Allocation.Index`, structs.go:8905)."""
        try:
            return int(self.name.rsplit("[", 1)[1].rstrip("]"))
        except (IndexError, ValueError):
            return -1

    def reschedule_eligible(self, policy: Optional[ReschedulePolicy], now: float) -> bool:
        """Whether a failed alloc can be rescheduled now (reference
        `Allocation.ShouldReschedule` + `RescheduleEligible`, structs.go:8711)."""
        if policy is None:
            return False
        if policy.unlimited:
            return True
        if policy.attempts == 0:
            return False
        attempted = 0
        if self.reschedule_tracker is not None:
            for ev in self.reschedule_tracker.events:
                if ev.reschedule_time > now - policy.interval_s:
                    attempted += 1
        return attempted < policy.attempts

    def next_reschedule_time(self, policy: Optional[ReschedulePolicy], fail_time: float):
        """Compute (time, eligible) for the next reschedule attempt (reference
        `Allocation.NextRescheduleTime`, structs.go:8741) with exponential /
        fibonacci / constant backoff (structs.go:8770 `NextDelay`)."""
        if policy is None:
            return 0.0, False
        delay = self._next_delay(policy)
        eligible = policy.unlimited or self.reschedule_eligible(policy, fail_time)
        return fail_time + delay, eligible

    def _next_delay(self, policy: ReschedulePolicy) -> float:
        base = policy.delay_s
        events = self.reschedule_tracker.events if self.reschedule_tracker else []
        n = len(events)
        if policy.delay_function == "constant":
            return base
        if policy.delay_function == "exponential":
            d = base * (2 ** n)
        elif policy.delay_function == "fibonacci":
            a, b = 0.0, base
            for _ in range(n):
                a, b = b, a + b
            d = b
        else:
            d = base
        if policy.max_delay_s > 0:
            d = min(d, policy.max_delay_s)
        return d
