"""Eval-lifecycle span tracer.

Each evaluation's trip through the control plane — broker enqueue →
dequeue → worker claim → snapshot resolution → batch pack → kernel
dispatch → plan apply → ack — is recorded as monotonic-clock spans
keyed by the eval id (the trace id). Queryable per eval via
`/v1/evaluation/:id/trace` and aggregated into per-phase latency
histograms on the owning registry (`eval.phase.<name>_ms`), so the
next perf round targets the measured bottleneck instead of the
suspected one (VERDICT r5: the e2e miss was attributed only by a
cumulative `view_ms` counter).

The reference has no per-eval tracer; the span taxonomy maps its
structures 1:1 — `queue_wait` is eval_broker.go Enqueue→Dequeue,
`plan_apply` is worker.go SubmitPlan→applyPlan, `ack` Ack. Traces live
in a bounded LRU (evictions are telemetry loss, never an error), and
every recorder is a no-op for ids the tracer never saw enqueued, so
cold paths (restored evals, tests driving the broker directly) cost a
dict miss.

Phase taxonomy (what each span bounds):

- `queue_wait`  broker enqueue → worker dequeue (queue depth + serialization)
- `claim`       dequeue → scheduler start (batch drain + thread handoff)
- `snapshot`    state.snapshot_min_index (MVCC view resolution)
- `schedule`    scheduler process() total (reconcile + compile + select + plan)
- `plan_apply`  submit_plan → PlanResult (queue hop + verify + commit)
- `ack`         broker ack/nack point (zero-length terminator)

A fused dispatch's own intervals — `pack` (coordinator param
stack/pack), `delta_apply` (device cluster-view refresh: delta row
update or full upload) and `kernel` (launch → first read) — are recorded
ONCE a dispatch, on its `DispatchTimeline` record, which names the evals
that rode it; `get()` joins them into each of those evals' traces
(ISSUE 38: copying them to each of ≤32 evals was ~96 records a dispatch
on the thread that paces the drain).

On the fused batch path `schedule` is also split into what the
scheduler thread was doing (ISSUES 26, 38), so that `schedule` =
`prepare` + `park` + `result_wait` + `plan_build` + `plan_apply` +
`finish`, each starting at the instant the one before it ended:

- `prepare`     process() start (or a refreshed plan's return) → the
                coordinator's select() entry (reconcile, compile_tg)
- `park`        select() entry → this request's event set (rendezvous,
                the predecessor batch, a speculative hold)
- `result_wait` this waiter's time inside the lazy holder's resolve()
                (lock wait, or the blocking device→host fetch)
- `plan_build`  select() return → the next select() / submit_plan()
                entry (the per-allocation loop of scheduler/generic.py)
- `finish`      submit_plan()'s return with no refreshed plan → process()'s
                return: the eval's status update (`_set_status` →
                `update_eval` → store upsert → event publish)
- `device_offer` inside `plan_build`, only for an eval whose group asks
                for a device: the time its offers spent drawing instance
                ids (DeviceAllocator + assign_task_devices), summed into
                one span per eval (ISSUE 28)

`prepare` and `plan_build` are phases in which the thread never blocks
on purpose: their `time.thread_time()` and wall deltas also sum into
the counters `sched.phase_cpu_ms` / `sched.phase_wall_ms`, whose ratio
says how much of "host work" is waiting for the GIL or a store lock.
`finish` takes store and event-broker locks, so it stays out of them.
These are in the eval's own trace and the histograms, not in the
cross-process SpanStore: mirroring them cost 8 % of singles' rate.

`host_span(name)` puts the same phases (each `device_offer` by itself;
and the coordinator's, the applier's, the HTTP handlers' and the
collector's) into the JAX profiler's host plane as `nomad/<name>`, on
the device trace's clock.
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional

from .metrics import MetricsRegistry
from .tracectx import SpanStore, TraceContext, new_span_id

#: canonical span order for display/aggregation
PHASES = ("queue_wait", "claim", "snapshot", "schedule", "pack",
          "delta_apply", "kernel", "plan_apply", "ack",
          "prepare", "park", "result_wait", "plan_build", "finish",
          "device_offer")
#: the scheduler thread's phases whose CPU and wall count into
#: `sched.phase_cpu_ms` / `sched.phase_wall_ms` (they never block on purpose)
_UNBLOCKED = ("prepare", "plan_build")

#: (`jax.profiler.TraceAnnotation`, JAX's profile state), looked up on
#: first use so that importing this module never imports JAX; False
#: where JAX is absent
_profiler = None
_NO_SPAN = contextlib.nullcontext()


def host_span(name: str):
    """Context manager that writes `nomad/<name>` into the host plane of
    a `jax.profiler` trace (`start_trace` / `trace`) while one is being
    taken; a null context otherwise, and without JAX.

    `TraceAnnotation` is not free when no profile runs: the binding lets
    go of the GIL, so under contention every span hands the interpreter
    to another thread (23 ms a span with four busy threads; 4 % of the
    single-allocation rate on the chip, PERF.md §6). Hence the look at
    JAX's own session state first — a private name: where a later JAX
    has moved it, every span is annotated again, slow but right."""
    global _profiler
    if _profiler is None:
        try:
            from jax._src import profiler as _p

            _profiler = (_p.TraceAnnotation,
                         getattr(_p, "_profile_state", None))
        except ImportError:  # a client-only agent, a plugin host
            _profiler = False
    if not _profiler:
        return _NO_SPAN
    annotation, state = _profiler
    if state is not None and state.profile_session is None:
        return _NO_SPAN
    return annotation("nomad/" + name)


class _Trace:
    __slots__ = ("spans", "marks", "wall_anchor", "mono_anchor", "ctx")

    def __init__(self) -> None:
        self.spans: List[Dict] = []
        self.marks: Dict[str, float] = {}
        self.wall_anchor = time.time()
        self.mono_anchor = time.monotonic()
        #: distributed-trace binding (ISSUE 17): the eval's own span
        #: context, bound once at broker enqueue from the ingress-
        #: minted ids riding the Evaluation struct. When set, every
        #: phase span this tracer records is mirrored into the process
        #: SpanStore as `eval.<phase>`, parented under the eval span.
        self.ctx: "TraceContext | None" = None


class EvalTracer:
    """Bounded, thread-safe per-eval span store + phase histograms."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 capacity: int = 512,
                 spans: Optional[SpanStore] = None,
                 source: str = "") -> None:
        self.registry = registry
        self.capacity = max(int(capacity), 1)
        self.spans = spans
        self.source = source
        self._lock = threading.Lock()
        self._traces: "OrderedDict[str, _Trace]" = OrderedDict()
        #: per scheduler thread: the phases of the eval it is running
        self._tls = threading.local()
        self._phase_hists: Dict[str, object] = {}
        #: the server's `DispatchTimeline` (lib/transfer.py), whose
        #: records hold the fused dispatches' pack / delta_apply / kernel
        #: intervals and the ids of the evals that rode each
        self.timeline = None
        if registry is not None:
            self._phase_cpu = registry.counter("sched.phase_cpu_ms")
            self._phase_wall = registry.counter("sched.phase_wall_ms")
            # device offers (scheduler/generic.py): made, and made again
            # after a rejection at commit or on a reselected node
            registry.counter("sched.device_offers")
            registry.counter("sched.device_offer_retries")
            # every offer, and those that built no per-node index (the
            # group asks for no port and no device): added once an eval
            registry.counter("sched.offers")
            registry.counter("sched.offers_skipped")

    # ---- recording ----

    def begin(self, trace_id: str) -> None:
        """Start (or refresh) a trace — called at broker enqueue."""
        with self._lock:
            tr = self._traces.get(trace_id)
            if tr is None:
                tr = self._traces[trace_id] = _Trace()
                while len(self._traces) > self.capacity:
                    self._traces.popitem(last=False)
            else:
                self._traces.move_to_end(trace_id)
            tr.marks["enqueue"] = time.monotonic()

    def bind(self, trace_id: str, ctx: Optional[TraceContext]) -> None:
        """Attach the eval's distributed span context (first bind wins
        — nack redeliveries must not re-parent an in-flight trace;
        no-op for unknown ids or a None ctx)."""
        if ctx is None:
            return
        with self._lock:
            tr = self._traces.get(trace_id)
            if tr is not None and tr.ctx is None:
                tr.ctx = ctx

    def binding(self, trace_id: str) -> Optional[TraceContext]:
        with self._lock:
            tr = self._traces.get(trace_id)
            return tr.ctx if tr is not None else None

    def emit_root(self, trace_id: str) -> None:
        """Record the eval's ROOT span (enqueue anchor → now) into the
        SpanStore — called once at the terminal ack/fail point, after
        the final phase span mirrored."""
        with self._lock:
            tr = self._traces.get(trace_id)
            if tr is None or tr.ctx is None:
                return
            ctx, wall0 = tr.ctx, tr.wall_anchor
        if self.spans is not None:
            self.spans.record(
                "eval", trace_id=ctx.trace_id, span_id=ctx.span_id,
                parent_span_id=ctx.parent_span_id, start_unix=wall0,
                end_unix=time.time(), source=self.source,
                detail={"eval_id": trace_id})

    def mark(self, trace_id: str, name: str) -> None:
        """Store a named monotonic timestamp (no-op for unknown ids)."""
        with self._lock:
            tr = self._traces.get(trace_id)
            if tr is not None:
                tr.marks[name] = time.monotonic()

    def record(self, trace_id: str, phase: str,
               start: Optional[float] = None,
               end: Optional[float] = None) -> None:
        """Append a span; monotonic start/end default to now (a
        zero-length point span). Feeds the phase histogram either way."""
        now = time.monotonic()
        start = now if start is None else start
        end = now if end is None else end
        dur_ms = max(end - start, 0.0) * 1e3
        if self.registry is not None:
            self.registry.add_sample(f"eval.phase.{phase}_ms", dur_ms)
        with self._lock:
            tr = self._traces.get(trace_id)
            if tr is None:
                return
            tr.spans.append({"phase": phase, "start": start, "end": end})
            ctx = tr.ctx
            # monotonic → wall against the trace's anchors, so the
            # mirrored span lines up with spans from other processes
            wall0 = tr.wall_anchor + (start - tr.mono_anchor)
            wall1 = tr.wall_anchor + (end - tr.mono_anchor)
        if ctx is not None and self.spans is not None:
            self.spans.record(
                "eval." + phase, trace_id=ctx.trace_id,
                span_id=new_span_id(), parent_span_id=ctx.span_id,
                start_unix=wall0, end_unix=wall1, source=self.source,
                detail={"eval_id": trace_id})

    def span_from_mark(self, trace_id: str, mark: str, phase: str) -> None:
        """Record `phase` spanning the stored mark → now (no-op when the
        mark is missing — the eval predates the tracer)."""
        with self._lock:
            tr = self._traces.get(trace_id)
            start = tr.marks.get(mark) if tr is not None else None
        if start is not None:
            self.record(trace_id, phase, start=start)

    def span(self, trace_id: str, phase: str) -> "_SpanCtx":
        return _SpanCtx(self, trace_id, phase)

    # ---- the scheduler thread's own phases (fused batch path) ----
    #
    # One eval's phases all run on ONE scheduler thread, so they are
    # gathered in thread-local state without a lock and recorded in one
    # go by `host_flush` when `schedule` ends: between a batch's release
    # and its acks 32 such threads contend for the GIL, and every shared
    # lock taken there lengthens the batch (PERF.md §6, PR 26). They are
    # not mirrored into the SpanStore for the same reason.

    def host_arm(self) -> None:
        """The calling thread starts an eval that parks at a coordinator."""
        tls = self._tls
        tls.phases, tls.open = [], None
        tls.cpu_s = tls.wall_s = 0.0

    def host_begin(self, phase: str, at: Optional[float] = None) -> None:
        """Open `phase` (`prepare` / `plan_build` / `finish`) on the
        calling thread at monotonic `at` (now by default: a caller that
        just closed the phase before passes its end, so that the two
        meet), closing the one still open; a no-op on a thread that is
        not armed."""
        tls = self._tls
        if getattr(tls, "phases", None) is None:
            return
        self.host_end(at)
        ann = host_span(phase)
        ann.__enter__()
        cpu0 = time.thread_time() if phase in _UNBLOCKED else None
        tls.open = (phase, time.monotonic() if at is None else at, cpu0,
                    ann)

    def host_end(self, at: Optional[float] = None) -> Optional[float]:
        """Close the calling thread's open phase, if any, at monotonic
        `at` (now by default); returns the instant it closed, or None
        when none was open."""
        tls = self._tls
        cur = getattr(tls, "open", None)
        if cur is None:
            return None
        phase, t0, cpu0, ann = cur
        tls.open = None
        end = time.monotonic() if at is None else at
        if cpu0 is not None:
            tls.cpu_s += time.thread_time() - cpu0
            tls.wall_s += end - t0
        ann.__exit__(None, None, None)
        tls.phases.append((phase, t0, end))
        return end

    def host_add(self, phase: str, start: float, end: float) -> None:
        """A phase the caller timed itself (`park`, `result_wait`)."""
        phases = getattr(self._tls, "phases", None)
        if phases is not None:
            phases.append((phase, start, end))

    def host_flush(self, trace_id: str,
                   end: Optional[float] = None) -> None:
        """Disarm the calling thread, closing its open phase at `end`,
        and record what it gathered: the `eval.phase.<name>_ms`
        histograms, the eval's spans, and the unblocked phases' CPU and
        wall time into `sched.phase_cpu_ms` / `sched.phase_wall_ms`."""
        tls = self._tls
        if getattr(tls, "phases", None) is None:
            return
        self.host_end(end)
        phases, tls.phases = tls.phases, None
        if not phases:
            return
        reg = self.registry
        if reg is not None:
            for phase, start, end in phases:
                h = self._phase_hists.get(phase)
                if h is None:
                    h = self._phase_hists[phase] = reg.histogram(
                        f"eval.phase.{phase}_ms")
                h.add(max(end - start, 0.0) * 1e3)
            self._phase_cpu.inc(tls.cpu_s * 1e3)
            self._phase_wall.inc(tls.wall_s * 1e3)
        with self._lock:
            tr = self._traces.get(trace_id)
            if tr is not None:
                tr.spans.extend({"phase": p, "start": s, "end": e}
                                for p, s, e in phases)

    # ---- querying ----

    def get(self, trace_id: str) -> Optional[Dict]:
        """Ordered span view: offsets are seconds since the trace's
        enqueue anchor (monotonic deltas stamped onto a wall anchor),
        the intervals of the fused dispatches the eval rode joined in
        from `timeline` where one is set."""
        with self._lock:
            tr = self._traces.get(trace_id)
            if tr is None:
                return None
            spans = [dict(s) for s in tr.spans]
            anchor_mono = tr.mono_anchor
            anchor_wall = tr.wall_anchor
        if self.timeline is not None:
            spans.extend({"phase": p, "start": s, "end": e}
                         for p, s, e in self.timeline.eval_spans(trace_id))
        spans.sort(key=lambda s: (s["start"], s["end"]))
        out = []
        for s in spans:
            out.append({
                "phase": s["phase"],
                "start_s": round(s["start"] - anchor_mono, 6),
                "duration_ms": round((s["end"] - s["start"]) * 1e3, 3),
            })
        return {"trace_id": trace_id, "anchor_unix": round(anchor_wall, 3),
                "spans": out}

    def trace_ids(self) -> List[str]:
        with self._lock:
            return list(self._traces)


class _SpanCtx:
    __slots__ = ("tracer", "trace_id", "phase", "_t0")

    def __init__(self, tracer: EvalTracer, trace_id: str, phase: str):
        self.tracer = tracer
        self.trace_id = trace_id
        self.phase = phase
        self._t0 = 0.0

    def __enter__(self) -> "_SpanCtx":
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self.tracer.record(self.trace_id, self.phase, start=self._t0)
