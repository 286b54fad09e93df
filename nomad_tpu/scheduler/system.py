"""SystemScheduler — one alloc per eligible node.

Behavioral reference: `scheduler/system_sched.go` (:45 NewSystemScheduler,
:54 Process, :183 computeJobAllocs, :268 computePlacements) and
`scheduler/util.go` diffSystemAllocsForNode (:70) / diffSystemAllocs (:201).

TPU-first restructuring: the reference runs the feasibility stack once per
node (SystemStack with a single-node source) and, where a node is full,
the Preemptor on it. Here ONE launch of `kernels/preemption.py
system_place` per task group decides every node — fits, needs an
eviction, and the victims upstream's greedy takes there — from a
candidate table the host packs once; the per-node diff and the plan are
host set arithmetic over its arrays.
"""
from __future__ import annotations

import time
from itertools import chain, compress
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..utils import fast_uuid
from ..structs import (
    ALLOC_CLIENT_LOST,
    ALLOC_CLIENT_PENDING,
    ALLOC_DESIRED_RUN,
    AllocMetric,
    Allocation,
    EVAL_STATUS_COMPLETE,
    EVAL_STATUS_FAILED,
    Evaluation,
    Job,
    Plan,
    PlanResult,
    TaskGroup,
    filter_terminal_allocs,
)
from ..lib.trace import host_span
from ..tensor.cluster import ClusterTensors
from . import preemption
from .generic import (allocated_resources, count_offers,
                      offer_needs_node, planner_registry)
from .reconcile import ALLOC_LOST, ALLOC_NOT_NEEDED, ALLOC_UPDATING
from .stack import PlanContext, TPUStack
from .util import (
    Planner,
    SetStatusError,
    State,
    fail_network_exhausted,
    progress_made,
    ready_nodes_in_dcs,
    retry_max,
    tainted_nodes,
    update_non_terminal_allocs_to_lost,
)

MAX_SYSTEM_ATTEMPTS = 5  # reference system_sched.go:17
ALLOC_NODE_TAINTED = "alloc not needed as node is tainted"

#: `system.<name>` counters an eval adds when it ends: allocations
#: placed, allocations evicted, nodes that needed an eviction, those of
#: them that no eviction could free, and the victim candidates packed
SYSTEM_COUNTS = ("placements", "evictions", "preempt_nodes", "refused",
                 "candidates")

#: the floor of `system_place`'s candidate bucket C: one shape for every
#: table a loaded 10,000-node cluster packs, so that no launch compiles
#: anew as its nodes fill up
CANDIDATE_BUCKET = 128


def materialize_system_groups(job: Job) -> Dict[str, TaskGroup]:
    """System jobs want one alloc per (node, tg); names use index 0
    (reference materializeTaskGroups, util.go:37, with system semantics)."""
    return {f"{job.id}.{tg.name}[0]": tg for tg in job.task_groups}


class SystemScheduler:
    """Reference SystemScheduler (system_sched.go:23)."""

    def __init__(self, state: State, planner: Planner, cluster: ClusterTensors
                 ) -> None:
        self.state = state
        self.planner = planner
        self.cluster = cluster
        self.eval: Optional[Evaluation] = None
        self.job: Optional[Job] = None
        self.plan: Optional[Plan] = None
        self.plan_result: Optional[PlanResult] = None
        self.failed_tg_allocs: Dict[str, AllocMetric] = {}
        self.queued_allocs: Dict[str, int] = {}
        self.nodes = []
        self.nodes_by_dc: Dict[str, int] = {}
        #: offers of this eval and those that built no per-node index
        #: (`scheduler/generic.py count_offers`)
        self._offers = self._offers_skipped = 0
        #: this eval's `system.*` counts, added once when it ends
        self._counts = dict.fromkeys(SYSTEM_COUNTS, 0)

    def process(self, eval: Evaluation) -> None:
        self.eval = eval
        try:
            err = retry_max(
                MAX_SYSTEM_ATTEMPTS, self._process,
                lambda: progress_made(self.plan_result),
            )
        finally:
            count_offers(self.planner, self._offers, self._offers_skipped)
            self._offers = self._offers_skipped = 0
            reg = planner_registry(self.planner)
            reg.inc("system.evals")
            for name, n in self._counts.items():
                if n:
                    reg.inc("system." + name, n)
            self._counts = dict.fromkeys(SYSTEM_COUNTS, 0)
        if err is not None:
            if isinstance(err, SetStatusError):
                self._set_status(EVAL_STATUS_FAILED, str(err))
                return
            raise err
        self._set_status(EVAL_STATUS_COMPLETE, "")

    def _set_status(self, status: str, desc: str) -> None:
        updated = Evaluation(**{**self.eval.__dict__})
        updated.status = status
        updated.status_description = desc
        updated.failed_tg_allocs = dict(self.failed_tg_allocs)
        updated.queued_allocations = dict(self.queued_allocs)
        self.planner.update_eval(updated)

    def _process(self) -> Tuple[bool, Optional[Exception]]:
        ev = self.eval
        t0 = time.monotonic()
        self.job = self.state.job_by_id(ev.namespace, ev.job_id)
        self.queued_allocs = {}
        self.failed_tg_allocs = {}
        if self.job is not None and not self.job.stopped():
            self.nodes, self.nodes_by_dc = ready_nodes_in_dcs(
                self.state, self.job.datacenters
            )
        else:
            self.nodes = []
        self.plan = ev.make_plan(self.job)
        config = self.state.scheduler_config()
        self.stack = TPUStack(self.cluster, algorithm=config.scheduler_algorithm)
        self.preemption_enabled = config.preemption_system_enabled

        err = self._compute_job_allocs()
        planner_registry(self.planner).add_sample(
            "system.schedule_ms", 1e3 * (time.monotonic() - t0))
        if err is not None:
            return False, err

        if self.plan.is_no_op() and not ev.annotate_plan:
            return True, None

        result, new_state = self.planner.submit_plan(self.plan)
        self.plan_result = result
        if new_state is not None:
            self.state = new_state
            return False, None
        full, expected, actual = result.full_commit(self.plan)
        if not full:
            return False, Exception(
                f"plan not fully committed and no refresh ({actual}/{expected})"
            )
        return True, None

    def _compute_job_allocs(self) -> Optional[Exception]:
        """Reference computeJobAllocs (system_sched.go:183)."""
        ev = self.eval
        allocs = self.state.allocs_by_job(ev.namespace, ev.job_id)
        tainted = tainted_nodes(self.state, allocs)
        update_non_terminal_allocs_to_lost(self.plan, tainted, allocs)
        live, terminal = filter_terminal_allocs(allocs)

        stopped = self.job is None or self.job.stopped()
        required = {} if stopped else materialize_system_groups(self.job)
        eligible = {n.id: n for n in self.nodes}

        place: List[Tuple[str, TaskGroup, Optional[Allocation]]] = []
        update: List[Allocation] = []

        allocs_by_node: Dict[str, List[Allocation]] = {}
        for a in live:
            allocs_by_node.setdefault(a.node_id, []).append(a)

        # Per-node diff (reference diffSystemAllocsForNode, util.go:70)
        node_ids = set(eligible) | set(allocs_by_node)
        for node_id in node_ids:
            existing_names = set()
            for a in allocs_by_node.get(node_id, []):
                existing_names.add(a.name)
                tg = required.get(a.name)
                if tg is None:
                    self.plan.append_stopped_alloc(a, ALLOC_NOT_NEEDED)
                    continue
                if not a.terminal_status() and a.desired_transition.should_migrate():
                    self.plan.append_stopped_alloc(a, ALLOC_NODE_TAINTED)
                    continue
                if a.node_id in tainted:
                    node = tainted[a.node_id]
                    if not a.terminal_status() and (
                        node is None or node.terminal_status()
                    ):
                        self.plan.append_stopped_alloc(
                            a, ALLOC_LOST, ALLOC_CLIENT_LOST
                        )
                    continue
                if node_id not in eligible:
                    continue
                if (
                    a.job is not None
                    and self.job.job_modify_index != a.job.job_modify_index
                ):
                    update.append(a)
                    continue
            if node_id not in eligible or node_id in tainted:
                continue
            for name, tg in required.items():
                if name not in existing_names:
                    prev = terminal.get(name)
                    if prev is not None and prev.node_id != node_id:
                        prev = None
                    place.append((node_id, tg, prev))

        # In-place vs destructive for updates: system jobs treat job changes as
        # destructive (evict + replace) up to the rolling-update limit
        # (system_sched.go:240-247 evictAndPlace)
        limit = len(update)
        if self.job is not None and self.job.update is not None and self.job.update.rolling():
            limit = self.job.update.max_parallel
        for a in update[:limit]:
            self.plan.append_stopped_alloc(a, ALLOC_UPDATING)
            tg = self.job.lookup_task_group(a.task_group)
            if tg is not None:
                place.append((a.node_id, tg, a))

        if not place:
            if self.job is not None and not self.job.stopped():
                for tg in self.job.task_groups:
                    self.queued_allocs[tg.name] = 0
            return None

        for _nid, tg, _prev in place:
            self.queued_allocs[tg.name] = self.queued_allocs.get(tg.name, 0) + 1

        return self._compute_placements(place)

    def _compute_placements(
        self, place: List[Tuple[str, TaskGroup, Optional[Allocation]]]
    ) -> Optional[Exception]:
        """One `system_place` launch per task group, then the plan built
        node by node from its arrays (replaces the reference's per-node
        SystemStack.Select loop, system_sched.go:268, and its Preemptor
        on each full node)."""
        from ..kernels.preemption import SYS_FITS, SYS_FREED, SYS_UNPACKED
        from ..tensor.vocab import MISSING

        by_tg: Dict[str, List[Tuple[str, Optional[Allocation]]]] = {}
        tg_map: Dict[str, TaskGroup] = {}
        for node_id, tg, prev in place:
            by_tg.setdefault(tg.name, []).append((node_id, prev))
            tg_map[tg.name] = tg
        reg = planner_registry(self.planner)
        counts = self._counts

        for tg_name, entries in by_tg.items():
            tg = tg_map[tg_name]
            plan_ctx = PlanContext()
            for stops in self.plan.node_update.values():
                plan_ctx.stopped_allocs.extend(stops)
            params, _m = self.stack.compile_tg(self.job, tg, len(entries), plan_ctx)
            rows = [self.cluster.row_of.get(nid) for nid, _p in entries]
            t0 = time.monotonic()
            table = SystemTable(self)
            if self.preemption_enabled:
                table.pack(table.may_not_fit(params, rows))
            table.launch(params, rows)
            place_s = time.monotonic() - t0

            # distinct_property tracking (SystemStack includes the
            # DistinctPropertyIterator too, stack.go:248): counts update
            # as this loop places, host-side since placement here is
            # per-node scalar
            dp_active = np.asarray(params.dp_active)
            dp_keys = np.asarray(params.dp_key_idx)
            dp_allowed = np.asarray(params.dp_allowed)
            dcounts = np.array(params.dp_counts0)
            has_dp = bool(dp_active.any())
            budget = int(params.n_place)  # < len(entries) iff constant-
            #                               LTarget dp caps total placements
            plain = not offer_needs_node(tg)  # no index is built per node

            self.plan.system_victims = table.victims_of
            try:
                for i, (node_id, prev) in enumerate(entries):
                    row = rows[i]
                    st = int(table.status[row]) if row is not None else 0
                    ok = st == SYS_FITS
                    # distinct_property gates BOTH normal and preemption
                    # placements; check before deciding to preempt, so a
                    # dp-infeasible node never evicts victims
                    dp_ok = True
                    dp_toks: List[Tuple[int, int]] = []
                    if row is not None and has_dp:
                        for k in range(len(dp_keys)):
                            if not dp_active[k]:
                                continue
                            tok = int(self.cluster.attrs[row, dp_keys[k]])
                            if tok == MISSING or tok >= dcounts.shape[1] \
                                    or dcounts[k, tok] >= dp_allowed[k]:
                                dp_ok = False
                                break
                            dp_toks.append((k, tok))
                    dp_ok = dp_ok and budget > 0
                    ok = ok and dp_ok
                    victims: List[Allocation] = []
                    if not ok and dp_ok and st > SYS_FITS \
                            and self.preemption_enabled:
                        # Feasible but exhausted → evict lower-priority
                        # allocs (system jobs preempt by default,
                        # stack.go:256-263): the launch's victims
                        if st == SYS_UNPACKED:
                            t1 = time.monotonic()
                            table.pack([r for r in rows[i:] if r is not None
                                        and table.status[r] == SYS_UNPACKED])
                            table.launch(params, rows[i:])
                            place_s += time.monotonic() - t1
                        counts["preempt_nodes"] += 1
                        victims = preemption.preempt_on_node(
                            self.state, self.job, tg, node_id, self.plan)
                        ok = bool(victims)
                        counts["refused"] += not ok
                    committed = self._place_on_node(
                        tg, node_id, prev, ok, victims, plain)
                    if committed:
                        budget -= 1
                        for k, tok in dp_toks:
                            dcounts[k, tok] += 1
                        counts["evictions"] += len(victims)
                    if row is not None and table.coupled \
                            and table.status[row] == SYS_FREED \
                            and not (committed and victims) \
                            and table.penalized(row):
                        # the launch counted this node's victims toward
                        # the max_parallel penalty of the nodes after it,
                        # and the plan holds none of them: decide those
                        # nodes again from the plan as it stands
                        t1 = time.monotonic()
                        table.launch(params, rows[i + 1:])
                        place_s += time.monotonic() - t1
            finally:
                del self.plan.system_victims
            reg.add_sample("system.place_ms", 1e3 * place_s)
            if self.preemption_enabled:
                reg.add_sample("system.pack_ms", 1e3 * table.pack_s)
                reg.inc("system.pack_rows", table.n_rows)
                reg.inc("system.pack_rows_array", table.n_array)
            reg.add_sample("system.build_ms", 1e3 * (
                time.monotonic() - t0 - place_s))
        return None

    def _place_on_node(self, tg: TaskGroup, node_id: str,
                       prev: Optional[Allocation], ok: bool,
                       victims: List[Allocation], plain: bool) -> bool:
        """One node's allocation and its preemptions into the plan, or its
        failure into `failed_tg_allocs`. -> whether it was placed."""
        metrics = AllocMetric()
        metrics.nodes_evaluated = 1
        metrics.nodes_available = dict(self.nodes_by_dc)
        if not ok:
            existing = self.failed_tg_allocs.get(tg.name)
            if existing is not None:
                existing.coalesced_failures += 1
            else:
                metrics.nodes_filtered = 1
                self.failed_tg_allocs[tg.name] = metrics
            return False
        node = self.state.node_by_id(node_id)
        alloc_id = fast_uuid()
        # Same ordering contract as the generic scheduler: plan
        # preemptions precede the NetworkIndex build.
        for v in victims:
            self.plan.append_preempted_alloc(v, alloc_id)
        self._offers += 1
        if plain or node is None:
            self._offers_skipped += 1
        alloc_res, net_err = allocated_resources(
            self.state, self.plan, tg, node
        )
        if net_err is not None:
            # Port-exhausted node: fail the per-node placement rather
            # than placing without ports (rank.go:256-267)
            fail_network_exhausted(
                self.plan, node_id, node, victims, metrics,
                self.failed_tg_allocs, tg.name, net_err)
            return False
        alloc = Allocation(
            id=alloc_id,
            namespace=self.job.namespace,
            eval_id=self.eval.id,
            name=f"{self.job.id}.{tg.name}[0]",
            job_id=self.job.id,
            job=self.job,
            task_group=tg.name,
            metrics=metrics,
            node_id=node_id,
            node_name=node.name if node else "",
            allocated_resources=alloc_res,
            desired_status=ALLOC_DESIRED_RUN,
            client_status=ALLOC_CLIENT_PENDING,
            job_version=self.job.version,
        )
        if victims:
            alloc.preempted_allocations = [v.id for v in victims]
        if prev is not None:
            alloc.previous_allocation = prev.id
        self.plan.append_alloc(alloc)
        self._counts["placements"] += 1
        return True


class SystemTable:
    """One system task group's `SystemCandidates`, packed on the host,
    and the `system_place` launch that decides it.

    A row's candidates are its proposed allocations (`proposed_allocs`:
    live less the plan's stops and preemptions, plus its placements),
    never the job's own; those the job may evict — a job, and a priority
    at least `PRIORITY_DELTA` below its own — go into the table in that
    order, and every one of them comes off the node's usable resources
    (the Preemptor's `set_candidates` + `preempt_for_task_group`).

    The rows are packed from the cluster tensors' allocation index
    (`tensor/alloc_index.py`) at once, as arrays; a row the index cannot
    stand for as the eval's snapshot and plan see it walks its
    Allocation objects (`_walk`): the plan touches its node, the tensors
    changed it since the snapshot (or their hot-row log cannot say), the
    index holds it partial, or a live allocation on it has no job."""

    #: reads of the index a writer may spoil before the pack walks
    GATHER_TRIES = 4

    def __init__(self, sched: SystemScheduler) -> None:
        self.sched = sched
        self.cluster = sched.cluster
        n = self.cluster.n_cap
        self.packed = np.zeros(n, bool)
        self.remaining = np.zeros((n, 3), np.float64)
        #: a packed row's candidates: `ncand` of them from `first` on in
        #: the table's order, each with its (cpu, memory, disk, priority,
        #: max_parallel, slot) in `_parts` and its Allocation — or its
        #: id, packed from the index — in `refs`
        self.first = np.zeros(n, np.int64)
        self.ncand = np.zeros(n, np.int64)
        self._parts: List[np.ndarray] = []
        self.refs: list = []
        #: (namespace, job, task group) -> slot of the penalty counts
        self.slots: Dict[Tuple[str, str, str], int] = {}
        self._max_par: Dict[Tuple[str, int, str], int] = {}
        #: a candidate's group has a migrate max_parallel: the nodes are
        #: decided in the host's order, the counts carried
        self.coupled = False
        self.status = np.zeros(n, np.int8)
        self.victims = np.zeros((n, 1), np.int16)
        #: rows packed, those of them packed from the index, and the
        #: packs' seconds (`system.pack_*`)
        self.n_rows = self.n_array = 0
        self.pack_s = 0.0

    def may_not_fit(self, params, rows: List[Optional[int]]) -> List[int]:
        """The rows where the ask may not fit as the launch's view will
        stand — `system_feasibility`'s fit on the host's copy of the
        same tensors, and every row where the group asks for a port:
        only those get candidates. A row the launch finds full with no
        candidates packed comes back `SYS_UNPACKED` and is packed then."""
        cl = self.cluster
        got = [r for r in rows if r is not None]
        if not got:
            return []
        if (np.asarray(params.res_ports) >= 0).any() \
                or float(params.n_dyn) > 0:
            return got
        idx = np.asarray(got)
        used = cl.used[idx].copy()
        didx = np.asarray(params.delta_idx)
        live = didx >= 0
        if live.any():
            pos = {r: k for k, r in enumerate(got)}
            for r, res in zip(didx[live], np.asarray(params.delta_res)[live]):
                k = pos.get(int(r))
                if k is not None:
                    used[k] -= res
        over = np.any(used + np.asarray(params.ask, np.float64)
                      > cl.capacity[idx], axis=1)
        return [r for r, o in zip(got, over) if o]

    def pack(self, rows: List[int]) -> None:
        """Each of `rows` not packed yet into the table, in that order:
        those the index stands for from its arrays at once, the others
        by `_walk`."""
        t0 = time.monotonic()
        new = [r for r in dict.fromkeys(rows) if not self.packed[r]]
        if new:
            self._pack(new, self._from_index(new))
            self.n_rows += len(new)
        self.pack_s += time.monotonic() - t0

    def _from_index(self, new: List[int]):
        """The index's read of `new`: `(ok, nodes, cand)` — per row whether
        the index stands for it, its node id, and for the rows it stands
        for their `remaining` and their candidates in table order
        (`_candidates`) — or None where no row can be read from it."""
        s, cl = self.sched, self.cluster
        state = s.state
        if getattr(state, "cluster", None) is not cl \
                or not hasattr(state, "tensor_version"):
            return None
        v0 = state.tensor_version()
        idx = cl.alloc_index
        lists, node_of_row = idx.row_slots, cl.node_of_row
        for _ in range(self.GATHER_TRIES):
            seq = idx.seq
            if seq & 1:
                time.sleep(0)   # a writer is inside: let it finish
                continue
            per_row = list(map(lists.__getitem__, new))
            lens = np.fromiter(map(len, per_row), np.int64, len(new))
            flat = list(chain.from_iterable(per_row))
            ids = list(map(idx.ids.__getitem__, flat))
            vals = idx.vals[np.fromiter(flat, np.int64, len(flat))]
            cap = cl.capacity[new, :3].astype(np.float64)
            nodes = list(map(node_of_row.__getitem__, new))
            own = idx.jobs.get((s.job.namespace, s.job.id), -1)
            if idx.seq == seq:
                break
        else:
            return None
        cand = self._candidates(len(new), lens, vals, ids, cap, own)
        # every write the read saw is in the log by now
        moved = cl.hot_rows_since(v0, cl.n_cap)
        if moved is None:
            return None
        plan = s.plan
        touched = {nid for t in (plan.node_update, plan.node_preemptions,
                                 plan.node_allocation)
                   for nid, lst in t.items() if lst}
        ok = cand.pop("ok")
        ok &= ~np.isin(new, list(moved | idx.partial))
        ok &= ~np.fromiter(map(touched.__contains__, nodes), bool, len(new))
        return ok, nodes, cand

    def _candidates(self, n, lens, vals, ids, cap, own) -> dict:
        """From the index's slots of `n` rows (`lens` a row, their `vals`
        and allocation `ids`, the rows' `cap`acity, `own` the job's
        interned id): `ok` (no live allocation without a job),
        `remaining` (capacity less every live allocation but the job's
        own, in order), and the candidates in table order with their
        row's position (`pos`), (cpu, memory, disk, priority,
        max_parallel) `vals`, group ids and allocation ids."""
        from ..tensor import alloc_index as ai

        pos = np.repeat(np.arange(n), lens)
        live = vals[:, ai.LIVE] > 0
        ok = np.ones(n, bool)
        ok[pos[live & (vals[:, ai.HAS_JOB] == 0)]] = False
        keep = live & (vals[:, ai.JOB] != own)
        # each row's capacity less its allocations one after another, in
        # order, as the walk subtracts them: `bincount` adds a bin's
        # weights in the order they come, the capacity first
        at = np.concatenate([np.arange(n), pos[keep]])
        rem = np.stack([np.bincount(at, np.concatenate(
            [cap[:, d], -vals[keep, ai.CPU + d]]), n) for d in range(3)],
            axis=1)
        cm = keep & (vals[:, ai.HAS_JOB] > 0) & (
            self.sched.job.priority - vals[:, ai.PRIO]
            >= preemption.PRIORITY_DELTA)
        cm &= ok[pos]
        got = vals[cm]
        return {"ok": ok, "remaining": rem, "pos": pos[cm],
                "vals": got[:, [ai.CPU, ai.MEM, ai.DISK, ai.PRIO,
                                ai.MAX_PAR]],
                "group": got[:, ai.GROUP].astype(np.int64),
                "ids": list(compress(ids, cm.tolist()))}

    def _pack(self, new: List[int], read) -> None:
        """`new` into the table in order: each run of rows the index
        stands for as one slice of its read, each other row walked; then
        the candidates' penalty slots, in order of first appearance."""
        idx = self.cluster.alloc_index
        if read is None:
            ok, nodes, cand = np.zeros(len(new), bool), None, None
        else:
            ok, nodes, cand = read
            starts = np.searchsorted(cand["pos"], np.arange(len(new) + 1))
        counts = np.zeros(len(new), np.int64)
        vals: List[np.ndarray] = []
        groups: List[np.ndarray] = []
        local: Dict[Tuple[str, str, str], int] = {}
        run = 0
        for i in [int(k) for k in np.flatnonzero(~ok)] + [len(new)]:
            if i > run:     # rows new[run:i] from the index
                lo, hi = starts[run], starts[i]
                vals.append(cand["vals"][lo:hi])
                groups.append(cand["group"][lo:hi])
                self.refs.extend(cand["ids"][lo:hi])
                counts[run:i] = np.diff(starts[run:i + 1])
                sel = np.asarray(new[run:i])
                self.remaining[sel] = cand["remaining"][run:i]
            if i == len(new):
                break
            row = new[i]
            rem, out, keys, allocs = self._walk(
                row, nodes[i] if nodes else self.cluster.node_of_row[row])
            self.remaining[row] = rem
            counts[i] = len(allocs)
            vals.append(np.asarray(out, np.float64).reshape(-1, 5))
            groups.append(np.asarray(
                [g if g is not None else -1 - local.setdefault(k, len(local))
                 for k, g in zip(keys, map(idx.groups.get, keys))],
                np.int64))
            self.refs.extend(allocs)
            run = i + 1
        self.n_array += int(ok.sum())
        base = len(self.refs) - int(counts.sum())
        sel = np.asarray(new)
        self.packed[sel] = True
        self.ncand[sel] = counts
        self.first[sel] = base + np.cumsum(counts) - counts
        t = np.concatenate(vals) if vals else np.zeros((0, 5))
        g = np.concatenate(groups) if groups else np.zeros(0, np.int64)
        slot = np.zeros(len(g))
        if len(g):
            keys = {v: k for k, v in local.items()}
            u, at, inv = np.unique(g, return_index=True, return_inverse=True)
            lut = np.zeros(len(u))
            for j in np.argsort(at, kind="stable"):
                gid = int(u[j])
                key = idx.group_keys[gid] if gid >= 0 else keys[-1 - gid]
                lut[j] = self.slots.setdefault(key, len(self.slots))
            slot = lut[inv]
        self.coupled |= bool((t[:, 4] > 0).any())
        self._parts.append(np.column_stack([t, slot]))
        self.sched._counts["candidates"] += len(t)

    def _walk(self, row: int, node_id: str):
        """One row's (remaining, candidate rows, group keys, candidates)
        from its proposed Allocation objects."""
        from .util import proposed_allocs

        s, cl = self.sched, self.cluster
        job = s.job
        usage = cl.alloc_usage
        planned = {a.id for a in s.plan.node_allocation.get(node_id, ())}
        rem = cl.capacity[row, :3].astype(np.float64)
        allocs: List[Allocation] = []
        out: List[Tuple[float, ...]] = []
        keys: List[Tuple[str, str, str]] = []
        for a in proposed_allocs(s.state, s.plan, node_id):
            if a.job_id == job.id and a.namespace == job.namespace:
                continue  # never preempt the job being placed
            ent = usage.get(a.id)
            if ent is not None and ent[0] == row and a.id not in planned:
                u = ent[1][:3]
            else:
                cr = a.comparable_resources()
                u = (cr.cpu, cr.memory_mb, cr.disk_mb)
            rem -= u
            if a.job is None or job.priority - a.job.priority \
                    < preemption.PRIORITY_DELTA:
                continue
            allocs.append(a)
            out.append((u[0], u[1], u[2], a.job.priority,
                        self._max_parallel(a)))
            keys.append((a.namespace, a.job_id, a.task_group))
        return rem, out, keys, allocs

    def _max_parallel(self, a: Allocation) -> int:
        key = (a.job_id, a.job.version, a.task_group)
        mp = self._max_par.get(key)
        if mp is None:
            tg = a.job.lookup_task_group(a.task_group)
            ms = tg.migrate_strategy if tg is not None else None
            mp = self._max_par[key] = ms.max_parallel if ms else 0
        return mp

    def _table(self) -> np.ndarray:
        """The candidates' (cpu, memory, disk, priority, max_parallel,
        slot) rows in table order."""
        if len(self._parts) != 1:
            self._parts = [np.concatenate(self._parts) if self._parts
                           else np.zeros((0, 6))]
        return self._parts[0]

    def candidates(self, n: int, rows: List[Optional[int]]):
        """The table at `n` rows as `SystemCandidates` (NumPy), the
        coupled variant's order being the packed rows among `rows`, and
        its counts the plan's preemptions as they stand."""
        from ..kernels.preemption import INF_PRIO, SystemCandidates
        from ..utils import bucket

        m = min(n, len(self.packed))
        c = bucket(int(self.ncand.max()), CANDIDATE_BUCKET)
        t = self._table()
        full = np.flatnonzero(self.ncand)
        full = full[np.argsort(self.first[full])]
        crow = np.repeat(full, self.ncand[full])  # each candidate's row
        ck = np.arange(len(t)) - self.first[crow]
        packed = np.zeros(n, bool)
        packed[:m] = self.packed[:m]
        remaining = np.zeros((n, 3), np.float32)
        remaining[:m] = self.remaining[:m]
        res = np.zeros((n, c, 3), np.float32)
        prio = np.full((n, c), INF_PRIO, np.float32)
        res[crow, ck] = t[:, :3]
        prio[crow, ck] = t[:, 3]
        if not self.coupled:
            return SystemCandidates(
                packed, remaining, res, prio,
                max_par=np.zeros((1, 1), np.float32),
                slot=np.full((1, 1), -1, np.int32),
                counts=np.zeros(1, np.float32),
                order=np.full(1, -1, np.int32))
        max_par = np.zeros((n, c), np.float32)
        slot = np.full((n, c), -1, np.int32)
        max_par[crow, ck] = t[:, 4]
        slot[crow, ck] = t[:, 5]
        counts = np.zeros(bucket(max(len(self.slots), 1)), np.float32)
        for lst in self.sched.plan.node_preemptions.values():
            for a in lst:
                k = self.slots.get((a.namespace, a.job_id, a.task_group))
                if k is not None:
                    counts[k] += 1
        seq = np.asarray([-1 if r is None else r for r in rows], np.int64)
        seq = seq[seq >= 0]
        seq = seq[self.packed[seq]]
        _u, at = np.unique(seq, return_index=True)
        order = seq[np.sort(at)]
        walk = np.full(bucket(max(len(order), 1)), -1, np.int32)
        walk[:len(order)] = order
        return SystemCandidates(packed, remaining, res, prio, max_par, slot,
                                counts, walk)

    def launch(self, params, rows: List[Optional[int]]) -> None:
        """`system_place` over the table: uploads and fetch on the
        transfer ledger's `system.*` sites, under the transfer guard."""
        import jax

        from ..kernels.preemption import system_place
        from ..lib.transfer import default_ledger, guard_scope

        led = default_ledger()
        with guard_scope():
            arrays = self.sched.stack.device_arrays()
            host = (params, self.candidates(arrays.capacity.shape[0], rows))
            nbytes = sum(int(getattr(x, "nbytes", 0))
                         for x in jax.tree_util.tree_leaves(host))
            with led.timed("system.upload", nbytes):
                dev_params, dev_cand = jax.device_put(host)
            with host_span("system_place"):
                out = system_place(arrays, dev_params, dev_cand,
                                   coupled=self.coupled)
                with led.timed("system.fetch", sum(x.nbytes for x in out)):
                    self.status, self.victims = (np.asarray(x) for x in out)

    def victims_of(self, node_id: str) -> List[Allocation]:
        """The node's victims as the launch decided them, in upstream's
        order (`preemption.preempt_on_node` reads them off the plan); a
        row packed from the index names them by id, and the eval's
        snapshot holds them."""
        row = self.cluster.row_of.get(node_id)
        if row is None or not self.packed[row]:
            return []
        base, refs = int(self.first[row]), self.refs
        state = self.sched.state
        return [state.alloc_by_id(x) if isinstance(x, str) else x
                for x in (refs[base + int(k)] for k in self.victims[row]
                          if k >= 0)]

    def penalized(self, row: int) -> bool:
        """Whether a victim the launch chose on `row` counts toward a
        max_parallel penalty."""
        t, base = self._table(), int(self.first[row])
        return any(t[base + int(k), 4] > 0 for k in self.victims[row]
                   if k >= 0)
