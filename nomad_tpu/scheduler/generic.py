"""GenericScheduler — service & batch scheduling.

Behavioral reference: `scheduler/generic_sched.go` (GenericScheduler :58,
Process :125, process :216, computeJobAllocs :332, computePlacements :468,
findPreferredNode :637, selectOptions/penalty nodes :622).

TPU-first restructuring: placements are grouped per task group and dispatched
as ONE kernel call per group (the lax.scan places every missing alloc of the
group); the reference's per-alloc stack.Select loop disappears. Plan-relative
state (stops, earlier groups' placements) rides into the kernel as sparse
deltas (PlanContext).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..utils import fast_uuid
from ..structs import (
    ALLOC_CLIENT_PENDING,
    ALLOC_DESIRED_RUN,
    AllocatedResources,
    AllocatedSharedResources,
    AllocatedTaskResources,
    AllocDeploymentStatus,
    AllocMetric,
    Allocation,
    EVAL_STATUS_BLOCKED,
    EVAL_STATUS_COMPLETE,
    EVAL_STATUS_FAILED,
    Evaluation,
    Job,
    NetworkIndex,
    Plan,
    PlanResult,
    TaskGroup,
)
from ..structs.evaluation import (
    TRIGGER_MAX_PLANS,
)
from ..tensor.cluster import ClusterTensors
from .reconcile import (
    AllocDestructiveResult,
    AllocPlaceResult,
    AllocReconciler,
    ReconcileResults,
    ALLOC_UPDATING,
)
from ..lib.trace import host_span
from .device import (DeviceAllocator, asks_devices, assign_task_devices,
                     holds_for)
from .stack import PlanContext, TPUStack
from .util import (
    Planner,
    SetStatusError,
    State,
    adjust_queued_allocations,
    fail_network_exhausted,
    generic_alloc_update_fn,
    progress_made,
    proposed_allocs,
    ready_counts_in_dcs,
    resolve_volume_asks,
    retry_max,
    tainted_nodes,
    update_non_terminal_allocs_to_lost,
    update_reschedule_tracker,
)

MAX_SERVICE_ATTEMPTS = 5   # reference generic_sched.go:18
MAX_BATCH_ATTEMPTS = 2     # reference generic_sched.go:22

BLOCKED_EVAL_MAX_PLAN_DESC = (
    "created due to placement conflicts"  # reference generic_sched.go:44
)
BLOCKED_EVAL_FAILED_PLACEMENTS = (
    "created to place remaining allocations"  # reference generic_sched.go:48
)


class GenericScheduler:
    """Reference GenericScheduler (generic_sched.go:58)."""

    def __init__(self, state: State, planner: Planner, cluster: ClusterTensors,
                 is_batch: bool = False) -> None:
        self.state = state
        self.planner = planner
        self.cluster = cluster
        self.batch = is_batch
        self.eval: Optional[Evaluation] = None
        self.job: Optional[Job] = None
        self.plan: Optional[Plan] = None
        self.plan_result: Optional[PlanResult] = None
        self.deployment = None
        self.blocked: Optional[Evaluation] = None
        self.failed_tg_allocs: Dict[str, AllocMetric] = {}
        self.queued_allocs: Dict[str, int] = {}
        self.follow_up_evals: List[Evaluation] = []
        #: set by the worker's batch path (server/select_batch.py) to
        #: fuse this eval's placement dispatches with its batch-mates'
        self.select_coordinator = None
        #: attempts begun (a plan refreshed after a rejection is another)
        self._attempts = 0
        #: device offers of this eval: the holds they drew past (None until
        #: the first: an eval that asks for no device never touches them),
        #: the first one's start and the seconds they took together
        self._holds = None
        self._offer_t0 = self._offer_s = 0.0
        #: offers of this eval and those that built no per-node index,
        #: counted on this thread and added to the registry when it ends
        self._offers = self._offers_skipped = 0

    # ---- entry point ----

    def process(self, eval: Evaluation) -> None:
        """Reference Process (generic_sched.go:125)."""
        self.eval = eval
        limit = MAX_BATCH_ATTEMPTS if self.batch else MAX_SERVICE_ATTEMPTS
        try:
            err = retry_max(
                limit, self._process, lambda: progress_made(self.plan_result)
            )
        finally:
            self._offers_end()
        if err is not None:
            if isinstance(err, SetStatusError):
                self._create_blocked_eval(plan_failure=True)
                self._set_status(EVAL_STATUS_FAILED, str(err))
                return
            raise err

        if eval.status == EVAL_STATUS_BLOCKED and self.failed_tg_allocs:
            new_eval = Evaluation(**{**eval.__dict__})
            self.planner.reblock_eval(new_eval)
            return
        self._set_status(EVAL_STATUS_COMPLETE, "")

    def _set_status(self, status: str, desc: str) -> None:
        """Reference setStatus (util.go:730)."""
        ev = self.eval
        updated = Evaluation(**{**ev.__dict__})
        updated.status = status
        updated.status_description = desc
        updated.failed_tg_allocs = dict(self.failed_tg_allocs)
        if self.blocked is not None:
            updated.blocked_eval = self.blocked.id
        updated.queued_allocations = dict(self.queued_allocs)
        if self.deployment is not None:
            updated.deployment_id = self.deployment.id
        self.planner.update_eval(updated)

    def _create_blocked_eval(self, plan_failure: bool = False) -> None:
        """Reference createBlockedEval (generic_sched.go:192).

        The timestamp is minted HERE — scheduler workers run leader-side
        only — and rides into the replicated eval, so FSM apply stays a
        pure function of the entry (the NLR01 invariant)."""
        self.blocked = self.eval.create_blocked_eval({}, True, "",
                                                     now=time.time())
        if plan_failure:
            self.blocked.triggered_by = TRIGGER_MAX_PLANS
            self.blocked.status_description = BLOCKED_EVAL_MAX_PLAN_DESC
        else:
            self.blocked.status_description = BLOCKED_EVAL_FAILED_PLACEMENTS
            # carry the failure attribution (dimension_exhausted,
            # constraint_filtered) onto the blocked eval itself: the
            # blocked tracker's diagnostics and "why is this stuck"
            # reads key off it (server/blocked.py dimension_stats)
            self.blocked.failed_tg_allocs = dict(self.failed_tg_allocs)
        self.planner.create_eval(self.blocked)

    # ---- one attempt ----

    def _process(self) -> Tuple[bool, Optional[Exception]]:
        """Reference process (generic_sched.go:216)."""
        ev = self.eval
        self.job = self.state.job_by_id(ev.namespace, ev.job_id)
        self.queued_allocs = {}
        self.follow_up_evals = []
        if self._holds is not None:
            # what the last attempt was handed is committed (and in the
            # cluster's ledger) or was rejected (and is free again)
            self._holds.release(ev.id)
        self._attempts += 1
        self.plan = ev.make_plan(self.job)
        # optimistic carry-exact certification (device-resident plan
        # deltas): only fused-coordinator dispatches produce a device
        # carry, and any post-kernel divergence below revokes it
        self.plan.carry_exact = self.select_coordinator is not None
        self.failed_tg_allocs = {}
        if not self.batch:
            self.deployment = self.state.latest_deployment_by_job(
                ev.namespace, ev.job_id
            )

        config = self.state.scheduler_config()
        self.stack = TPUStack(self.cluster, algorithm=config.scheduler_algorithm)
        self.stack.coordinator = self.select_coordinator
        self.stack.coordinator_order = getattr(self, "select_order", 0)
        self.preemption_enabled = (
            config.preemption_batch_enabled if self.batch
            else config.preemption_service_enabled
        )

        err = self._compute_job_allocs()
        if err is not None:
            return False, err

        delay_instead = bool(self.follow_up_evals) and not ev.wait_until

        if (
            ev.status != EVAL_STATUS_BLOCKED
            and self.failed_tg_allocs
            and self.blocked is None
            and not delay_instead
        ):
            self._create_blocked_eval(plan_failure=False)

        if self.plan.is_no_op() and not ev.annotate_plan:
            return True, None

        if delay_instead:
            for fe in self.follow_up_evals:
                fe.previous_eval = ev.id
                self.planner.create_eval(fe)

        result, new_state = self.planner.submit_plan(self.plan)
        self.plan_result = result

        adjust_queued_allocations(result, self.queued_allocs)

        if new_state is not None:
            self.state = new_state
            return False, None

        full, expected, actual = result.full_commit(self.plan)
        if not full:
            return False, Exception(
                f"plan not fully committed and no refresh ({actual}/{expected})"
            )
        return True, None

    # ---- reconcile + place ----

    def _compute_job_allocs(self) -> Optional[Exception]:
        """Reference computeJobAllocs (generic_sched.go:332)."""
        ev = self.eval
        allocs = self.state.allocs_by_job(ev.namespace, ev.job_id)
        tainted = tainted_nodes(self.state, allocs)
        update_non_terminal_allocs_to_lost(self.plan, tainted, allocs)

        reconciler = AllocReconciler(
            job=self.job,
            job_id=ev.job_id,
            is_batch=self.batch,
            existing_allocs=allocs,
            tainted_nodes=tainted,
            eval_id=ev.id,
            deployment=self.deployment,
            alloc_update_fn=generic_alloc_update_fn,
        )
        results = reconciler.compute()

        if ev.annotate_plan:
            from ..structs import PlanAnnotations

            self.plan.annotations = PlanAnnotations(
                desired_tg_updates=results.desired_tg_updates
            )

        self.plan.deployment = results.deployment
        self.plan.deployment_updates = results.deployment_updates

        for evs in results.desired_followup_evals.values():
            self.follow_up_evals.extend(evs)
        if results.deployment is not None:
            self.deployment = results.deployment

        for stop in results.stop:
            self.plan.append_stopped_alloc(
                stop.alloc, stop.status_description, stop.client_status
            )

        dep_id = self.deployment.id if self.deployment is not None else ""
        if results.inplace_update or results.attribute_updates:
            # in-place/attribute updates replace a live alloc's usage at
            # commit — host mutations on rows the kernel carry cannot
            # model (it only chains placements + plan-relative stops)
            self.plan.carry_exact = False
        for update in results.inplace_update:
            if update.deployment_id != dep_id:
                update.deployment_id = dep_id
                update.deployment_status = None
            self.plan.append_alloc(update)

        for update in results.attribute_updates.values():
            self.plan.append_alloc(update)

        if not results.place and not results.destructive_update:
            if self.job is not None:
                for tg in self.job.task_groups:
                    self.queued_allocs[tg.name] = 0
            return None

        for p in results.place:
            self.queued_allocs[p.task_group.name] = (
                self.queued_allocs.get(p.task_group.name, 0) + 1
            )
        for d in results.destructive_update:
            self.queued_allocs[d.place_task_group.name] = (
                self.queued_allocs.get(d.place_task_group.name, 0) + 1
            )

        return self._compute_placements(
            results.destructive_update, results.place
        )

    def _record_explain_metrics(self, ex: dict) -> None:
        """Fold one select's attribution into the `scheduler.filter.*` /
        `scheduler.exhausted.*` counter families (go-metrics
        `nomad.nomad.blocked_evals`-style rollups; Prometheus exposition
        rides the registry). Dimension keys keep their display names —
        the exposition layer mangles to [a-z0-9_]."""
        reg = planner_registry(self.planner)
        if ex["filtered_constraint"]:
            reg.inc("scheduler.filter.constraint", ex["filtered_constraint"])
        if ex["filtered_device_plugin"]:
            reg.inc("scheduler.filter.device_plugin",
                    ex["filtered_device_plugin"])
        dh = sum(ex["filtered_distinct_hosts"])
        dp = sum(ex["filtered_distinct_property"])
        if dh:
            reg.inc("scheduler.filter.distinct_hosts", dh)
        if dp:
            reg.inc("scheduler.filter.distinct_property", dp)
        dims: Dict[str, int] = {}
        for exhausted in ex["dimension_exhausted"]:
            for dim, n in exhausted.items():
                dims[dim] = dims.get(dim, 0) + n
        for dim, n in dims.items():
            reg.inc(f"scheduler.exhausted.{dim}", n)

    @staticmethod
    def _group_metrics(ex: Optional[dict], n: int, n_ready: int,
                       by_dc: Dict[str, int]) -> List[AllocMetric]:
        """One AllocMetric a placement of a task group, from the
        kernel attribution's columns (scheduler/stack.py
        explain_columns; reference: the iterator chain fills these as
        it walks, feasible.go filter_node / rank.go exhausted_node /
        kheap score meta — here the fused kernel already counted, so
        this is a host-side copy, not a recount). What every placement
        of the group shares is worked out once. Without attribution
        (NOMAD_TPU_EXPLAIN=0, an opted-out program) a metric carries the
        host's ready counts alone."""
        if ex is None:
            return [AllocMetric(nodes_evaluated=n_ready,
                                nodes_available=dict(by_dc))
                    for _ in range(n)]
        # the kernel count supersedes the host's per-DC ready count: it
        # respects sampled-candidate restriction, and the
        # evaluated−filtered−exhausted arithmetic only closes against
        # the same taxonomy (DC membership is a counted LUT row here)
        evaluated = ex["nodes_evaluated"]
        filtered = ex["filtered_constraint"] + ex["filtered_device_plugin"]
        by_constraint = dict(ex["constraint_filtered"])
        if ex["filtered_device_plugin"]:
            by_constraint["device-plugin/host checks"] = \
                ex["filtered_device_plugin"]
        out = []
        for dh, dp, exhausted, dims, top in zip(
                ex["filtered_distinct_hosts"],
                ex["filtered_distinct_property"], ex["nodes_exhausted"],
                ex["dimension_exhausted"], ex["score_meta"]):
            cf = dict(by_constraint)
            if dh:
                cf["distinct_hosts"] = dh
            if dp:
                cf["distinct_property"] = dp
            out.append(AllocMetric(
                nodes_evaluated=evaluated, nodes_filtered=filtered + dh + dp,
                nodes_available=dict(by_dc), constraint_filtered=cf,
                nodes_exhausted=exhausted, dimension_exhausted=dims,
                score_meta=top))
        return out

    def _compute_placements(
        self,
        destructive: List[AllocDestructiveResult],
        place: List[AllocPlaceResult],
    ) -> Optional[Exception]:
        """Reference computePlacements (generic_sched.go:468), restructured:
        one kernel dispatch per task group covering all its missing allocs."""
        by_dc = ready_counts_in_dcs(self.state, self.job.datacenters)
        n_ready = sum(by_dc.values())  # AllocMetric nodes_evaluated
        dep_id = ""
        if self.deployment is not None and self.deployment.active():
            dep_id = self.deployment.id
        now = time.time()

        # Destructive updates stop their previous alloc first (frees resources)
        missing: List[Tuple[TaskGroup, AllocPlaceResult, Optional[Allocation], bool]] = []
        for d in destructive:
            self.plan.append_stopped_alloc(d.stop_alloc, ALLOC_UPDATING)
            missing.append(
                (
                    d.place_task_group,
                    AllocPlaceResult(
                        name=d.place_name,
                        task_group=d.place_task_group,
                        previous_alloc=d.stop_alloc,
                    ),
                    d.stop_alloc,
                    True,
                )
            )
        for p in place:
            missing.append((p.task_group, p, p.previous_alloc, False))

        # Group by task group, preserving order (destructive first)
        groups: Dict[str, List[Tuple[AllocPlaceResult, Optional[Allocation], bool]]] = {}
        tg_by_name: Dict[str, TaskGroup] = {}
        for tg, p, prev, _dest in missing:
            groups.setdefault(tg.name, []).append((p, prev, _dest))
            tg_by_name[tg.name] = tg

        for tg_name, entries in groups.items():
            tg = tg_by_name[tg_name]
            plan_ctx = self._plan_context_for(tg, entries)
            volumes = resolve_volume_asks(self.state, self.job.namespace, tg)
            result = self.stack.select(self.job, tg, len(entries), plan_ctx,
                                       volumes=volumes)
            # bind the plan to the dispatch whose carry contains these
            # placements (multi-group plans: the LAST dispatch's carry
            # is the one a later refresh can adopt — earlier groups ride
            # it as plan-relative deltas, which always overlay)
            self.plan.carry_token = result.carry_token
            if result.explain is not None:
                self._record_explain_metrics(result.explain)
            # what is the same for every allocation of the group, once:
            # whether an offer consults the node at all, and (where it
            # does not, so that every allocation is granted the same)
            # whether what commits is what the kernel added
            plain = not offer_needs_node(tg)
            certified = False
            # kernel-native attribution (same fused dispatch): filtered
            # stages, exhausted dimensions, top-K score breakdown — for
            # successes AND failures
            group_metrics = self._group_metrics(
                result.explain, len(entries), n_ready, by_dc)

            for i, (p, prev, _dest) in enumerate(entries):
                node_id = result.node_ids[i]
                score = result.scores[i]
                victims: List[Allocation] = []
                metrics = group_metrics[i]
                if node_id is None and self.preemption_enabled:
                    # Second pass with eviction enabled (reference
                    # selectNextOption, generic_sched.go:720-738)
                    from .preemption import find_preemption_placement

                    params, _m = self.stack.compile_tg(
                        self.job, tg, 1, self._plan_context_for(tg, [(p, prev, _dest)])
                    )
                    found = find_preemption_placement(
                        self.state, self.cluster, self.job, tg, params,
                        self.plan,
                    )
                    if found is not None:
                        node_id, victims, score = found
                        # preemption places where the fused dispatch did
                        # NOT — the carry knows nothing of this row
                        self.plan.carry_exact = False
                if node_id is None:
                    # Failed placement (generic_sched.go:620 failedTGAllocs)
                    existing = self.failed_tg_allocs.get(tg.name)
                    if existing is not None:
                        existing.coalesced_failures += 1
                    else:
                        if result.explain is None:
                            # coarse legacy counts when the dispatch ran
                            # without attribution (NOMAD_TPU_EXPLAIN=0)
                            metrics.nodes_filtered = (
                                n_ready - result.nodes_feasible
                            )
                            metrics.nodes_exhausted = (
                                result.nodes_feasible - result.nodes_fit[i]
                                if i < len(result.nodes_fit) else 0
                            )
                        metrics.populate_score_meta()
                        self.failed_tg_allocs[tg.name] = metrics
                    continue

                node = self.state.node_by_id(node_id)
                alloc_id = fast_uuid()
                if victims:
                    # Victims must enter the plan BEFORE allocated_resources
                    # builds the NetworkIndex, so the new alloc can claim the
                    # ports/bandwidth they release (handlePreemptions,
                    # generic_sched.go:742).
                    for v in victims:
                        self.plan.append_preempted_alloc(v, alloc_id)
                alloc_res, net_err = self._allocated_resources(
                    tg, node, plain=plain)
                if net_err is not None:
                    # Offer-time assignment (ports/devices) failed on the
                    # selected node: the reference would have ranked it out
                    # (rank.go:256-267) and moved to the next candidate —
                    # retry selection with the node excluded, then fail.
                    # Either way the kernel's predicted placement row
                    # never commits — the dispatch carry is no longer a
                    # faithful post-commit view of this plan.
                    self.plan.carry_exact = False
                    if victims:
                        pres = self.plan.node_preemptions.get(node_id, [])
                        vset = {v.id for v in victims}
                        self.plan.node_preemptions[node_id] = [
                            a for a in pres if a.id not in vset]
                        victims = []
                    node_id, node, score, alloc_res, net_err = \
                        self._reselect_excluding(
                            tg, (p, prev, _dest), {node_id}, net_err)
                    if net_err is not None:
                        fail_network_exhausted(
                            self.plan, node_id, node, victims, metrics,
                            self.failed_tg_allocs, tg.name, net_err)
                        continue
                alloc = Allocation(
                    id=alloc_id,
                    namespace=self.job.namespace,
                    eval_id=self.eval.id,
                    name=p.name,
                    job_id=self.job.id,
                    job=self.job,
                    task_group=tg.name,
                    metrics=metrics,
                    node_id=node_id,
                    node_name=node.name if node else "",
                    deployment_id=dep_id,
                    allocated_resources=alloc_res,
                    desired_status=ALLOC_DESIRED_RUN,
                    client_status=ALLOC_CLIENT_PENDING,
                    job_version=self.job.version,
                )
                metrics.score_selected(node_id, score)
                if victims:
                    alloc.preempted_allocations = [v.id for v in victims]
                if prev is not None:
                    alloc.previous_allocation = prev.id
                    if p.reschedule:
                        update_reschedule_tracker(alloc, prev, now)
                if p.canary and self.deployment is not None:
                    alloc.deployment_status = AllocDeploymentStatus(canary=True)
                    ds = self.deployment.task_groups.get(tg.name)
                    if ds is not None:
                        ds.placed_canaries.append(alloc.id)
                if self.plan.carry_exact and not (plain and certified):
                    self._certify_carry_exact(alloc, result.ask)
                    certified = True
                self.plan.append_alloc(alloc)
        return None

    def _certify_carry_exact(self, alloc, ask) -> None:
        """Device-resident plan deltas: a placement may ride the
        dispatch's on-device carry only if what commits is EXACTLY what
        the kernel added — usage row bit-equal (as f32) to the compiled
        ask vector, and integral below the f32-exact bound so the
        chain's f32 accumulation cannot round differently from the host
        store's f64. Any mismatch revokes the whole plan's
        certification; the view then re-uploads its rows from host
        (slower, never wrong)."""
        if ask is None:
            self.plan.carry_exact = False
            return
        try:
            usage = self.cluster.usage_row(alloc)
        except Exception:  # noqa: BLE001 — odd shape: host path decides
            self.plan.carry_exact = False
            return
        if (usage.shape != ask.shape
                or not np.array_equal(usage.astype(np.float32), ask)
                or not np.all(usage == np.floor(usage))
                or np.any(np.abs(usage) >= 2 ** 24)):
            self.plan.carry_exact = False

    def _plan_context_for(
        self, tg: TaskGroup,
        entries: List[Tuple[AllocPlaceResult, Optional[Allocation], bool]],
    ) -> PlanContext:
        """Assemble plan-relative deltas for the kernel: in-plan stops release
        resources; per-step penalty/preferred nodes mirror getSelectOptions +
        findPreferredNode (generic_sched.go:622,637)."""
        ctx = PlanContext()
        for node_id, stops in self.plan.node_update.items():
            ctx.stopped_allocs.extend(stops)
        for node_id, pres in self.plan.node_preemptions.items():
            ctx.preempted_allocs.extend(pres)
        # in-plan placements from earlier groups of this eval
        for node_id, placements in self.plan.node_allocation.items():
            for a in placements:
                if a.create_index:
                    continue  # in-place updates already counted in state
                usage = self.cluster.usage_row(a)
                ctx.placed.append((node_id, a.task_group, usage))
                ctx.placed_allocs.append(a)

        sticky = tg.ephemeral_disk.sticky
        for p, prev, _dest in entries:
            penalties = set()
            preferred = None
            if prev is not None and p.reschedule:
                penalties.add(prev.node_id)
                if prev.reschedule_tracker is not None:
                    for ev in prev.reschedule_tracker.events:
                        if ev.prev_node_id:
                            penalties.add(ev.prev_node_id)
            if prev is not None and sticky and not p.reschedule:
                preferred = prev.node_id
            ctx.penalty_node_ids.append(frozenset(penalties))
            ctx.preferred_node_ids.append(preferred)
        return ctx

    def _allocated_resources(self, tg: TaskGroup, node, again: bool = False,
                             plain: bool = False):
        """`allocated_resources`, counted; for a group that asks for a
        device the instance ids are drawn past what batch-mates hold
        (`DeviceHolds`), and the offer is counted and timed. `again`: the
        offer repeats one that failed or was rejected (a reselected node,
        a refreshed plan). `plain`: the caller has found, once for the
        group, that `offer_needs_node(tg)` is false."""
        self._offers += 1
        if plain or node is None or not offer_needs_node(tg):
            self._offers_skipped += 1
            return group_resources(tg), None
        if not asks_devices(tg):
            return allocated_resources(self.state, self.plan, tg, node)
        reg = planner_registry(self.planner)
        reg.inc("sched.device_offers")
        if again or self._attempts > 1:
            reg.inc("sched.device_offer_retries")

        if self._holds is None:
            self._holds = holds_for(self.cluster)

        def offer_devices(proposed):
            t0 = time.monotonic()
            with host_span("device_offer"):
                out = self._holds.assign(
                    self.cluster, node, DeviceAllocator(node, proposed), tg,
                    self.plan)
            if not self._offer_s:
                self._offer_t0 = t0
            self._offer_s += time.monotonic() - t0
            return out

        return allocated_resources(self.state, self.plan, tg, node,
                                   offer_devices=offer_devices)

    def _offers_end(self) -> None:
        """The eval ends: its offers are counted, its holds go, and the
        time its device offers took is one `device_offer` phase of its
        trace (gathered on this thread, recorded when `schedule` ends:
        lib/trace.py)."""
        count_offers(self.planner, self._offers, self._offers_skipped)
        self._offers = self._offers_skipped = 0
        if self._holds is None:
            return
        self._holds.release(self.eval.id)
        tracer = getattr(getattr(self.planner, "server", None), "tracer",
                         None)
        if tracer is not None:
            tracer.host_add("device_offer", self._offer_t0,
                            self._offer_t0 + self._offer_s)

    def _reselect_excluding(self, tg: TaskGroup, entry, excluded: set,
                            first_err: str):
        """Offer-time failure recovery: re-run selection with the failed
        nodes masked out (via the candidate-restriction mode) and re-offer,
        up to 3 nodes deep. The reference's BinPackIterator simply continues
        to the next candidate (rank.go:256-267); the batched kernel can't
        see precise offer-time state, so disagreements re-enter selection
        here instead of failing the placement outright."""
        err = first_err
        volumes = resolve_volume_asks(self.state, self.job.namespace, tg)
        for _ in range(3):
            rows = [row for nid, row in self.cluster.row_of.items()
                    if nid not in excluded]
            if not rows:
                break
            plan_ctx = self._plan_context_for(tg, [entry])
            # no attribution on the retry dispatch: only node/score are
            # consumed here, and the group's main select already
            # recorded this placement's metrics
            sel = self.stack.select(self.job, tg, 1, plan_ctx,
                                    volumes=volumes, sampled_rows=rows,
                                    explain=False)
            node_id = sel.node_ids[0]
            if node_id is None:
                break
            node = self.state.node_by_id(node_id)
            alloc_res, err = self._allocated_resources(tg, node, again=True)
            if err is None:
                return node_id, node, sel.scores[0], alloc_res, None
            excluded.add(node_id)
        return None, None, 0.0, None, err


def planner_registry(planner):
    """Metrics registry for scheduler.* counters: the owning server's
    when scheduling for a real server (EvalContext planner), else the
    process-global one (harness / tests / bare stacks)."""
    srv = getattr(planner, "server", None)
    reg = getattr(srv, "metrics", None)
    if reg is None:
        from ..lib.metrics import default_registry

        reg = default_registry()
    return reg


def offer_needs_node(tg: TaskGroup) -> bool:
    """Whether an offer for `tg` consults the node it was placed on: a
    group network, a task port or a device ask. A group that asks for
    none of them is granted the same on every node, whatever lives there."""
    return bool(tg.networks) or any(
        t.resources.networks or t.resources.devices for t in tg.tasks)


def group_resources(tg: TaskGroup) -> AllocatedResources:
    """What `tg` is granted before any port or device instance: cpu and
    memory per task, the group's disk. A fresh object per allocation
    (in-place updates and the client write into it)."""
    return AllocatedResources(
        tasks={t.name: AllocatedTaskResources(
            cpu=t.resources.cpu, memory_mb=t.resources.memory_mb)
            for t in tg.tasks},
        shared=AllocatedSharedResources(disk_mb=tg.ephemeral_disk.size_mb))


def count_offers(planner, offers: int, skipped: int) -> None:
    """One eval's offers into `sched.offers`, and those that built no
    per-node index into `sched.offers_skipped`: once, when the eval ends
    (an `inc` per allocation from every scheduler thread is a lock
    hand-off per allocation, PERF.md §6, PR 26)."""
    if offers:
        registry = planner_registry(planner)
        registry.inc("sched.offers", offers)
        if skipped:
            registry.inc("sched.offers_skipped", skipped)


def allocated_resources(state: State, plan: Plan, tg: TaskGroup, node,
                        offer_devices=None):
    """Grant resources + assign ports for a placement (reference:
    BinPackIterator's per-task network/port assignment, rank.go:231-320).
    Port assignment happens host-side against the node's NetworkIndex built
    from plan-relative proposed allocs — otherwise two allocs of one eval on
    one node double-book dynamic ports and the plan applier rejects it.

    The proposed allocs, the NetworkIndex and the DeviceAllocator are built
    only for a group that asks for a port or a device
    (`offer_needs_node`): they decide nothing else (their collision result
    was never read here, as in the reference), and building them for every
    allocation of a thousand on a handful of nodes is quadratic in the job.

    Returns (resources, error): a non-None error means the node cannot
    satisfy the group's port asks and the placement MUST fail (the reference
    ranks such nodes out, rank.go:256-267 — an alloc is never placed with
    its ports silently dropped).

    Device instance ids come from the same proposed allocs;
    `offer_devices(proposed)` stands in for that step where the caller
    knows more (GenericScheduler: what batch-mates hold)."""
    if node is None or not offer_needs_node(tg):
        return group_resources(tg), None

    proposed = proposed_allocs(state, plan, node.id)
    net_idx = NetworkIndex()
    net_idx.set_node(node)
    net_idx.add_allocs(proposed)
    if offer_devices is not None:
        dev_offers, derr = offer_devices(proposed)
    else:
        dev_offers, derr = assign_task_devices(
            DeviceAllocator(node, proposed), tg)
    if dev_offers is None:
        return None, derr

    res = group_resources(tg)
    for t in tg.tasks:
        tr = res.tasks[t.name]
        tr.devices = list(dev_offers.get(t.name, ()))
        for ask in t.resources.networks:
            offer, err = net_idx.assign_network(ask)
            if offer is None:
                return None, err or f"task {t.name}: no network offer"
            net_idx.add_reserved(offer)
            tr.networks.append(offer)

    for ask in tg.networks:
        offer, err = net_idx.assign_network(ask)
        if offer is None:
            return None, err or "group network: no offer"
        net_idx.add_reserved(offer)
        res.shared.networks.append(offer)
    return res, None
