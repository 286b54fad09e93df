"""Worker — dequeue evaluations, run the scheduler, submit plans.

Behavioral reference: `nomad/worker.go` (Worker :54, run :105,
dequeueEvaluation :142, snapshotMinIndex :228, invokeScheduler :244,
SubmitPlan :277, UpdateEval :346, CreateEval :378, ReblockEval :410).

The TPU twist: where the reference runs NumCPU workers racing on MVCC
snapshots (`nomad/server.go:1419`), one worker here drains a BATCH of
evals, runs each eval's scheduler in a short-lived thread, and a
SelectCoordinator (select_batch.py) fuses their placement dispatches
into one chained kernel call — conflict-aware batching over the eval
axis instead of goroutine concurrency (SURVEY §7 hard-part (e)).
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..lib.metrics import MetricsRegistry
from ..lib.trace import host_span
from ..scheduler.generic import GenericScheduler
from ..scheduler.system import SystemScheduler
from ..structs import Evaluation, Plan, PlanResult
from ..structs.evaluation import EVAL_STATUS_BLOCKED

SCHEDULER_TYPES = ("service", "batch", "system", "_core")
#: eval types safe to fan out in one batch (the broker already serializes
#: per job, so a drained batch never holds two evals of one job)
BATCHABLE_TYPES = ("service", "batch")

#: drain-cadence knobs (ISSUE 12). The hold window is ADAPTIVE by
#: default: the worker sizes it from the dispatch timeline's measured
#: per-dispatch host overhead (`pipeline.host_ms` — pack + upload +
#: view, i.e. dispatch_ms − kernel_ms), because waiting for more evals
#: is break-even exactly when the wait costs what the merged dispatch
#: saves. The env override pins it (ms) for BENCH cadence sweeps;
#: 0 disables holding entirely.
DRAIN_WINDOW_ENV = "NOMAD_TPU_DRAIN_WINDOW_MS"
#: adaptive-window ceiling: never hold longer than this, however slow
#: the measured dispatch path is (a stalled device must not turn the
#: drain loop into a 1 Hz scheduler)
DRAIN_WINDOW_CAP_MS = 50.0
#: re-read the measured overhead this often (the histogram summary
#: sorts its sample window — not a per-drain cost)
_DRAIN_WINDOW_REFRESH_S = 0.5


class EvalContext:
    """Planner-protocol implementation for ONE evaluation (worker.go:277-438).

    Split out of the worker so a batch of evals can be in flight
    concurrently — each scheduler gets its own token/snapshot context
    instead of racing on worker-instance fields."""

    def __init__(self, server, eval: Evaluation, token: str,
                 snapshot) -> None:
        self.server = server
        self.eval = eval
        self.token = token
        self.snapshot = snapshot

    def submit_plan(self, plan: Plan) -> Tuple[PlanResult, Optional[object]]:
        plan.eval_token = self.token
        plan.snapshot_index = (self.snapshot.index_at
                               if self.snapshot is not None else 0)
        # belt: plans built via Evaluation.make_plan already carry the
        # eval's trace context; backfill hand-built plans so plan_apply
        # can parent its span and stamp allocs (lib/tracectx.py)
        if not plan.trace_id and self.eval.trace_id:
            plan.trace_id = self.eval.trace_id
            plan.trace_span_id = self.eval.trace_span_id
        tracer = getattr(self.server, "tracer", None)
        if tracer is not None:
            tracer.host_end()  # plan_build (or prepare)
        t0 = time.monotonic()
        refreshed = None
        try:
            result, refreshed = self._submit_plan(plan)
            return result, refreshed
        finally:
            if tracer is not None:
                tracer.record(self.eval.id, "plan_apply", start=t0)
                if refreshed is not None:
                    # partial commit: the scheduler reconciles again
                    tracer.host_begin("prepare")

    def _submit_plan(self, plan: Plan
                     ) -> Tuple[PlanResult, Optional[object]]:
        # inline fast path (same commit-point mutex, no thread hops);
        # queue round trip only when the applier is busy
        result = self.server.planner.try_apply_inline(plan)
        if result is None:
            fut = self.server.plan_queue.enqueue(plan)
            # backstop only — the applier's 1s poll loop recovers any
            # missed wakeup, so this fires solely when the process is
            # starved of CPU for the whole window (observed >10s under
            # a fully loaded test host). Must stay WELL inside the
            # broker's unack window: a wait that straddles nack-timeout
            # would let a redelivered copy of this eval plan against a
            # pre-commit snapshot while this plan is still committing
            # (duplicate allocations until the next reconcile).
            nack = getattr(self.server.broker, "nack_timeout", 60.0)
            result = fut.wait(
                timeout=min(30.0, nack * 0.5) if nack > 0 else 30.0)
        if result is None:
            raise RuntimeError("plan apply failed")
        if result.refresh_index:
            # Partial commit: hand the scheduler a fresher snapshot
            # (worker.go:318-330).
            new_snap = self.server.state.snapshot_min_index(
                result.refresh_index, timeout=5.0
            )
            self.snapshot = new_snap
            return result, new_snap
        return result, None

    def update_eval(self, eval: Evaluation) -> None:
        self.server.apply_eval_update(eval)

    def create_eval(self, eval: Evaluation) -> None:
        # Stamp the snapshot the eval was created from (worker.go:378) —
        # BlockedEvals.missed_unblock depends on it.
        if not eval.snapshot_index and self.snapshot is not None:
            eval.snapshot_index = self.snapshot.index_at
        self.server.apply_eval_update(eval)

    def reblock_eval(self, eval: Evaluation) -> None:
        """Reference ReblockEval (worker.go:410): re-capture an
        already-blocked eval with an updated snapshot index."""
        eval.snapshot_index = (self.snapshot.index_at
                               if self.snapshot is not None else 0)
        self.server.apply_eval_update(eval, reblock=True)


class Worker:
    """One scheduling worker thread: drains eval batches and fans them
    out over the batched-select coordinator."""

    def __init__(self, server, worker_id: int = 0) -> None:
        self.server = server
        self.id = worker_id
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        #: drained-batch ceiling; 1 = the reference's one-eval-per-loop
        self.eval_batch = int(
            os.environ.get("NOMAD_TPU_EVAL_BATCH", 0)
        ) or getattr(server.config, "eval_batch", 1)
        #: server-owned telemetry (falls back to a private registry so a
        #: bare Worker against a stub server still records safely)
        self.metrics: MetricsRegistry = getattr(
            server, "metrics", None) or MetricsRegistry()
        self.tracer = getattr(server, "tracer", None)
        #: persistent scheduler-thread pool for the batch path (spawning
        #: B threads per batch measured ~0.3 ms each — a real tax at
        #: millisecond-scale evals). Guarded by _pool_lock: created by
        #: the worker thread, read by shutdown() from the main thread.
        self._pool = None
        self._pool_lock = threading.Lock()
        #: drain-cadence hold window (see DRAIN_WINDOW_ENV): a fixed
        #: env-pinned value, or adaptive from the dispatch timeline's
        #: measured per-dispatch host overhead (confined to the worker
        #: thread — only _run/_drain touch the cache fields)
        env = os.environ.get(DRAIN_WINDOW_ENV)
        self._window_fixed: Optional[float] = None
        if env is not None:
            try:
                self._window_fixed = max(float(env), 0.0) / 1e3
            except ValueError:
                self._window_fixed = None
        self._window_cached = 0.0
        self._window_next = 0.0
        if self._window_fixed is not None:
            self.metrics.set_gauge("drain.window_ms",
                                   self._window_fixed * 1e3)

    @property
    def batch_stats(self) -> Dict[str, float]:
        """Cumulative coordinator stats (bench/test introspection) —
        registry-backed, so the worker thread and readers never race on
        a plain dict."""
        return self.metrics.counters(prefix=f"worker.{self.id}.batch.")

    # ---- lifecycle ----

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._run, name=f"worker-{self.id}", daemon=True
        )
        self._thread.start()

    def shutdown(self) -> None:
        self._stop.set()
        with self._pool_lock:
            pool = self._pool
        if pool is not None:
            pool.shutdown(wait=False)

    def join(self, timeout: float = 2.0) -> None:
        if self._thread is not None:
            self._thread.join(timeout)

    def _run(self) -> None:
        """Pipelined drain loop. While batch k runs its fused kernel +
        plan applies, batch k+1's schedulers are already doing their
        (GIL-bound) reconcile+compile on the pool — they park at their
        coordinator, which cannot dispatch until we call run() after
        batch k completes, so k+1 never places against k's un-applied
        claims. Within a batch the coordinator pipelines too: waiters
        get lazy outputs at kernel launch (select_batch._BatchOut), so
        k's plan applies overlap k's own in-flight chain, and k+1's
        dispatch refreshes the device view as a row-delta against the
        cached buffers instead of re-uploading the hot tensors."""
        inflight = None  # (coord, futs, items) started but not finished
        try:
            while not self._stop.is_set():
                groups = self._drain(block=(inflight is None))
                batch, group_of = [], []
                for gi, g in enumerate(groups):
                    for item in g:
                        batch.append(item)
                        group_of.append(gi)
                started = None
                if batch and (len(batch) > 1 or inflight is not None) \
                        and batch[0][0].type in BATCHABLE_TYPES:
                    started = self.start_batch(batch, group_of=group_of)
                    batch = None
                    # the drain's static-mask lookups, the partition's
                    # and the batch's: once a drain, not 64 times
                    self.server.count_footprints()
                if inflight is not None:
                    if started is not None:
                        # speculative dispatch (ISSUE 15): when batch
                        # k's fused dispatch launches inside
                        # finish_batch below, it offers batch k+1 a
                        # speculative launch against its predicted
                        # carry — k+1's kernel queues behind k's on
                        # device while k's plans commit; k+1's
                        # coordinator certifies at the top of its own
                        # finish_batch, after every k plan committed
                        inflight[0].successor = started[0]
                    self.finish_batch(*inflight)
                    inflight = None
                if started is not None:
                    inflight = started
                elif batch:
                    # non-batchable eval (system/_core) or an idle-queue
                    # single: run synchronously, nothing else in flight
                    for ev, tok in batch:
                        self.process_one(ev, tok)
        finally:
            # a started batch must always be driven to completion —
            # otherwise its schedulers stay parked at the coordinator
            # forever and their evals are never acked/nacked
            if inflight is not None:
                self.finish_batch(*inflight)

    def _drain(self, block: bool) -> List[List[Tuple[Evaluation, str]]]:
        """Adaptive drain cadence (ISSUE 12): one broker call drains up
        to `eval_batch` evals partitioned into conflict groups (disjoint
        node footprints → parallel wave lanes in the fused dispatch).
        A loaded queue holds the drain open for the adaptive window so
        the dispatch carries as many evals as the window gathers; an
        idle queue returns its single eval immediately — today's
        latency. The hold window also runs while a predecessor batch is
        in flight, where waiting is literally free (the drained batch's
        host pack cannot dispatch before the in-flight kernel anyway)."""
        hold = self._hold_window() if self.eval_batch > 1 else 0.0
        return self.server.broker.dequeue_batch(
            SCHEDULER_TYPES, self.eval_batch,
            timeout=0.5 if block else 0.0,
            hold_s=hold, batch_types=BATCHABLE_TYPES)

    def _hold_window(self) -> float:
        """Seconds the drain may hold a non-empty, non-full batch open.
        Fixed by NOMAD_TPU_DRAIN_WINDOW_MS when set; otherwise the mean
        measured per-dispatch host overhead (pipeline.host_ms — what an
        extra dispatch would cost, so waiting that long to avoid one is
        break-even), capped at DRAIN_WINDOW_CAP_MS. Zero until the
        timeline has samples: an unmeasured path never adds latency."""
        if self._window_fixed is not None:
            return self._window_fixed
        now = time.monotonic()
        if now < self._window_next:
            return self._window_cached
        self._window_next = now + _DRAIN_WINDOW_REFRESH_S
        summ = self.metrics.histogram("pipeline.host_ms").summary()
        w = 0.0
        if summ["count"]:
            w = min(summ["mean"], DRAIN_WINDOW_CAP_MS) / 1e3
        self._window_cached = w
        self.metrics.set_gauge("drain.window_ms", w * 1e3)
        return w

    # ---- one evaluation ----

    def process_one(self, eval: Evaluation, token: str,
                    coordinator=None, order: int = 0,
                    snapshot=None) -> None:
        """dequeue → wait-for-index → schedule → ack/nack (worker.go:105)."""
        broker = self.server.broker
        tracer = self.tracer
        if tracer is not None:
            # dequeue → scheduler start (batch drain + thread handoff)
            tracer.span_from_mark(eval.id, "dequeue", "claim")
        try:
            snap = snapshot
            if snap is None:
                t0 = time.monotonic()
                with host_span("snapshot"):
                    snap = self.server.state.snapshot_min_index(
                        max(eval.modify_index, eval.job_modify_index),
                        timeout=5.0)
                if tracer is not None:
                    tracer.record(eval.id, "snapshot", start=t0)
            if snap is None:
                broker.nack(eval.id, token)
                return
            ctx = EvalContext(self.server, eval, token, snap)
            eval.snapshot_index = snap.index_at
            sched = self._make_scheduler(eval, snap, ctx)
            if coordinator is not None and isinstance(sched,
                                                      GenericScheduler):
                sched.select_coordinator = coordinator
                sched.select_order = order
                if tracer is not None:
                    # the eval parks at the coordinator: its schedule
                    # span splits into prepare / park / result_wait /
                    # plan_build on this thread
                    tracer.host_arm()
            t0 = time.monotonic()
            if tracer is not None:
                tracer.host_begin("prepare")
            try:
                sched.process(eval)
            finally:
                if tracer is not None:
                    tracer.host_flush(eval.id)
            if tracer is not None:
                tracer.record(eval.id, "schedule", start=t0)
            if eval.type == "_core":
                # Core schedulers don't drive update_eval themselves —
                # a successful pass completes the eval here.
                import copy

                done = copy.copy(eval)
                done.status = "complete"
                self.server.state.upsert_eval(done)
            broker.ack(eval.id, token)
        except Exception:
            import traceback

            traceback.print_exc()
            try:
                broker.nack(eval.id, token)
            except ValueError:
                pass

    # ---- a batch of evaluations (the TPU fan-out) ----

    def process_batch(self, items: List[Tuple[Evaluation, str]]) -> None:
        """Run a batch start-to-finish (non-pipelined callers/tests)."""
        self.finish_batch(*self.start_batch(items))

    def start_batch(self, items: List[Tuple[Evaluation, str]],
                    group_of: Optional[List[int]] = None):
        """Launch each eval's scheduler on the persistent pool. The
        schedulers reconcile+compile immediately but PARK at the
        coordinator — no placement happens until finish_batch() drives
        the coordinator (the pipelining hook). `group_of[i]` is item
        i's broker conflict-group id (disjoint node footprints);
        the coordinator runs disjoint groups as parallel wave lanes
        inside one fused dispatch. None (tests, non-broker callers)
        means unknown — everything rides one sequential chain."""
        from concurrent.futures import ThreadPoolExecutor

        from .select_batch import SelectCoordinator

        with self._pool_lock:
            if self._pool is None:
                # 2× batch width: a pipelined successor batch starts its
                # host phase while the predecessor still occupies its
                # slots
                self._pool = ThreadPoolExecutor(
                    max_workers=max(2 * self.eval_batch, 2),
                    thread_name_prefix=f"worker-{self.id}-eval")
            pool = self._pool
        # one snapshot serves the whole batch: every eval's min-index is
        # satisfied by construction (its registration bumped the store
        # before the broker handed it out), and snapshot construction is
        # a measurable per-eval cost at scale
        need = max(max(ev.modify_index, ev.job_modify_index)
                   for ev, _ in items)
        t0 = time.monotonic()
        with host_span("snapshot"):
            snap = self.server.state.snapshot_min_index(need, timeout=5.0)
        if self.tracer is not None:
            t1 = time.monotonic()
            for ev, _ in items:  # one resolution serves the whole batch
                self.tracer.record(ev.id, "snapshot", start=t0, end=t1)
        coord = SelectCoordinator(tracer=self.tracer,
                                  timeline=getattr(self.server,
                                                   "timeline", None),
                                  registry=self.metrics)
        # per-program footprint masks for speculative certification
        # (select_batch._certify_spec): the same estimator the broker
        # partitions with, re-read at batch start so the mask reflects
        # this batch's state (the job's current allocation rows; the
        # part the node table decides is a dict hit, ISSUE 33, so the
        # second call costs microseconds and stays where it is). None
        # (no estimator / nothing cheap bounds the eval) conflicts with
        # every stale row — sound, never fast.
        # Skipped entirely when speculation can never run (hard opt-out
        # or an active mesh): masks nobody reads are pure batch-start
        # latency.
        from ..parallel.mesh import get_active_mesh
        from .select_batch import spec_enabled

        fp_fn = (getattr(self.server, "_eval_footprint", None)
                 if spec_enabled() and get_active_mesh() is None
                 else None)
        futs = []
        fp_s = 0.0  # the estimates' own time, summed over the batch
        for order, (ev, tok) in enumerate(items):
            coord.trace_ids[order] = ev.id
            if group_of is not None:
                coord.group_ids[order] = group_of[order]
            if fp_fn is not None:
                t0 = time.monotonic()
                try:
                    with host_span("footprint"):
                        coord.footprints[order] = fp_fn(ev)
                except Exception:  # noqa: BLE001 — estimate only
                    coord.footprints[order] = None
                fp_s += time.monotonic() - t0
            coord.add_thread()
            try:
                futs.append(pool.submit(
                    self._process_in_batch, ev, tok, coord, order, snap))
            except RuntimeError:
                # pool closed by a concurrent shutdown(): balance the
                # thread count so run() can terminate, and give the eval
                # back to the broker
                coord.thread_done()
                try:
                    self.server.broker.nack(ev.id, tok)
                except ValueError:
                    pass
        if fp_fn is not None:
            self.metrics.add_sample("sched.footprint_ms", fp_s * 1e3)
        return coord, futs, items

    def finish_batch(self, coord, futs, items) -> None:
        """Drive the coordinator's fused dispatches until every eval in
        the batch has acked/nacked."""
        coord.run()
        for f in futs:
            f.result()
        prefix = f"worker.{self.id}.batch."
        for k, v in coord.stats.items():
            self.metrics.inc(prefix + k, v)
        self.metrics.inc(prefix + "batches")
        self.metrics.inc(prefix + "evals", len(items))

    def _process_in_batch(self, eval: Evaluation, token: str,
                          coord, order: int, snap) -> None:
        try:
            self.process_one(eval, token, coordinator=coord, order=order,
                             snapshot=snap)
        finally:
            coord.thread_done()

    def _make_scheduler(self, eval: Evaluation, snap, planner):
        """Reference scheduler.NewScheduler factory (scheduler.go:34)."""
        if eval.type == "_core":
            from .core_sched import CoreScheduler

            return CoreScheduler(self.server, snap)
        if eval.type == "system":
            return SystemScheduler(snap, planner, snap.cluster)
        return GenericScheduler(
            snap, planner, snap.cluster, is_batch=(eval.type == "batch")
        )
