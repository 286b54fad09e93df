"""Batched select dispatch — the control plane's road onto the chained
placement kernel.

The reference scales eval throughput with NumCPU worker goroutines racing
on MVCC snapshots (`nomad/server.go:1419-1451`, `nomad/worker.go:105`);
collisions surface as plan rejections (`nomad/plan_apply.go:437`). The
TPU build batches instead: one worker drains up to B evals from the
broker, runs each eval's scheduler in a short-lived thread, and this
coordinator fuses their `TPUStack.select` dispatches into ONE
`place_task_group_chain` call (kernels/placement.py) — a scan over the
program axis that carries (used, dyn_free), so programs in a batch see
each other's placements and cannot over-commit a node (SURVEY §7
hard-part (e): conflict-aware eval batching).

Determinism: programs chain in the evals' broker-drain order (each
request carries its batch position), so within a dispatch a batched
server places exactly what a sequential one would, regardless of
thread timing (tests/test_select_batch.py asserts this equivalence end
to end for the single-round case, which is every eval's first select).
Later rounds (multi-TG jobs, refresh retries, reselect) place against
the LIVE device view at dispatch time: a program's own plan-relative
deltas (compile_tg) already encode its earlier placements/stops, so
re-applying them on top of a cross-round carry would double-count —
instead, cross-round conflicts fall to plan-apply verification exactly
like the reference's optimistic worker race (`nomad/plan_apply.go:437`).

Rendezvous protocol: scheduler threads park in `select()`; the
coordinator dispatches when every live thread is parked (the common
case — each scheduler issues exactly one select) or when a short window
expires (stragglers blocked elsewhere, e.g. in plan-apply). A thread may
park again for later rounds (multi-TG jobs, plan-refresh retries); the
loop runs until every thread has finished.

Pipelined dispatch (ISSUE 5): a dispatch packs FIRST, resolves the
device view at the last instant (a delta row-update against the cached
buffers, not a re-upload — scheduler/stack.py device_arrays), launches
the chain, and releases its waiters with LAZY outputs. Waiters
materialize as the kernel lands and roll into their plan applies; the
coordinator thread is immediately free to pack the next round of
parked programs against the in-flight kernel. Host pack, view refresh,
kernel, and result consumption no longer serialize on one thread.

Observability (ISSUE 6): the packed buffers transfer EXPLICITLY and
every transfer on the fused path is recorded in the process transfer
ledger (lib/transfer.py — sites `select_batch.pack_buffers`,
`select_batch.fetch`, plus the `stack.*` view sites resolved inside
the dispatch); the whole device-touching section runs under a
`jax.transfer_guard` scope so implicit transfers are logged (prod) or
fatal (tests). Each dispatch commits a record to the server's
DispatchTimeline — pack/view/kernel intervals plus the overlap/bubble
metric that says whether batch k+1's pack actually hid under batch
k's kernel.

Explainability (ISSUE 8): when any program in a dispatch asks for it,
the chain runs with `explain=True` and the PlacementExplain leaves
(nodes evaluated / per-stage filtered / per-dimension exhausted /
top-K score breakdown) ride the SAME lazy `_BatchOut` fetch — one
device→host transfer, ledger-accounted at `select_batch.fetch`,
timeline-compatible, and guard-clean like the base outputs.

Wave dispatch (ISSUE 12): the worker's broker drain arrives partitioned
into CONFLICT GROUPS (disjoint node footprints — `coord.group_ids`,
order → group id). Programs within one group still ride the sequential
conflict-aware chain, but DISJOINT groups run as parallel lanes of the
SAME fused dispatch (`place_table_wave`: vmap over lane chains, lane
carries folded into one view carry by exact per-row lane selection) —
the serial scan stops growing with mega-batch width. Bit-parity with
the sequential chain is the contract whenever footprints are truly
disjoint; a cross-lane row collision (stale footprint) is counted on
device, the dispatch's carry is rejected, and plan-apply verification
resolves the race like the reference's optimistic worker race.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..lib.trace import host_span
from ..utils import bucket as _bucket

#: process-unique dispatch tokens: view-lease keys AND the carry/plan
#: binding (structs.Plan.carry_token ↔ stack note tokens). Module-level
#: so two coordinators (multi-worker servers) can never collide.
_DISPATCH_TOKENS = itertools.count(1)

# ---- speculative wave dispatch (ISSUE 15) ----------------------------------
# Launch batch k+1's fused dispatch against the PREDICTED post-commit
# view (scheduler/stack.py spec_chain_view — the predecessor's chain
# carry over the base buffers) while batch k's plans are still
# committing; CERTIFY at commit time against the chain's stale-row set
# and keep only the program slices whose node footprints a conflicting
# commit provably did not touch — those are bit-identical to sequential
# dispatch. Everything else re-dispatches against the committed view.

#: hard opt-out: NOMAD_TPU_SPECULATE=0 disables speculative launches
SPECULATE_ENV = "NOMAD_TPU_SPECULATE"
#: how long a predecessor dispatch waits for the successor batch's
#: round-1 rendezvous before giving up on speculation (ms). The wait
#: runs on the coordinator thread while the predecessor's plans commit
#: on waiter threads — time that is otherwise the dispatch bubble.
SPEC_PARK_ENV = "NOMAD_TPU_SPEC_PARK_MS"
#: adaptive gate: disarm speculation when the rolled-back share of
#: recent launches exceeds this (a misprediction storm must degrade to
#: the plain pipelined path, not thrash re-dispatches)
SPEC_ROLLBACK_MAX_ENV = "NOMAD_TPU_SPEC_ROLLBACK_MAX"


def spec_enabled() -> bool:
    return os.environ.get(SPECULATE_ENV, "1").strip().lower() \
        not in ("0", "off", "false")


def _spec_park_s() -> float:
    try:
        return max(float(os.environ.get(SPEC_PARK_ENV, "30")), 0.0) / 1e3
    except ValueError:
        return 0.03


class SpecGate:
    """Adaptive speculation gate: a sliding window of launch outcomes;
    when the rolled-back share exceeds the threshold the gate disarms
    for a cooldown of skipped opportunities, then re-arms with a clean
    window (churn may have passed). Consecutive failed LAUNCH ATTEMPTS
    (rendezvous timeouts, residency misses) disarm it the same way — a
    host where the successor batch never parks in time must stop
    paying the park wait, not retry it per dispatch. One gate per
    cluster, shared by every coordinator batch that dispatches against
    it."""

    WINDOW = 16
    MIN_SAMPLES = 8
    COOLDOWN = 8
    MISS_LIMIT = 3

    def __init__(self, threshold: Optional[float] = None) -> None:
        if threshold is None:
            try:
                threshold = float(
                    os.environ.get(SPEC_ROLLBACK_MAX_ENV, "0.5"))
            except ValueError:
                threshold = 0.5
        self.threshold = min(max(threshold, 0.0), 1.0)
        self._lock = threading.Lock()
        self._outcomes: "deque[int]" = deque(maxlen=self.WINDOW)
        self._cooldown = 0
        self._misses = 0

    def armed(self) -> bool:
        with self._lock:
            if self._cooldown > 0:
                self._cooldown -= 1
                if self._cooldown == 0:
                    self._outcomes.clear()  # re-arm with a clean window
                return False
            o = self._outcomes
            if len(o) >= self.MIN_SAMPLES \
                    and sum(o) / len(o) > self.threshold:
                self._cooldown = self.COOLDOWN
                return False
            return True

    def record(self, rolled_back: bool) -> None:
        with self._lock:
            self._outcomes.append(1 if rolled_back else 0)
            self._misses = 0  # a real launch happened

    def record_miss(self) -> None:
        """A launch attempt paid its wait and produced nothing."""
        with self._lock:
            self._misses += 1
            if self._misses >= self.MISS_LIMIT:
                self._misses = 0
                self._cooldown = self.COOLDOWN


#: cluster → SpecGate (weak: gates die with their cluster)
_SPEC_GATES: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_SPEC_GATES_LOCK = threading.Lock()


def _gate_for(cluster) -> SpecGate:
    with _SPEC_GATES_LOCK:
        g = _SPEC_GATES.get(cluster)
        if g is None:
            g = _SPEC_GATES[cluster] = SpecGate()
        return g


class _SelectReq:
    __slots__ = ("arrays_fn", "params", "n_place", "order", "explain",
                 "event", "out", "err")

    def __init__(self, arrays_fn, params, n_place: int, order: int,
                 explain: bool = False) -> None:
        #: zero-arg callable returning the CURRENT device cluster view
        #: (TPUStack.device_arrays) — resolved at dispatch time, because
        #: under pipelining the predecessor batch's plans commit between
        #: park and dispatch
        self.arrays_fn = arrays_fn
        self.params = params
        self.n_place = n_place
        self.order = order
        #: request wants PlacementExplain outputs; a fused dispatch runs
        #: with explain when ANY of its programs asked (the leaves ride
        #: the shared lazy fetch either way)
        self.explain = explain
        self.event = threading.Event()
        #: (_BatchOut, program index | None) — the device outputs stay
        #: LAZY until a waiter (or the coordinator's stats pass) first
        #: touches them, so waiters are released while the chain kernel
        #: is still in flight
        self.out: Optional[Tuple] = None
        self.err: Optional[BaseException] = None


class _BatchOut:
    """Shared lazy holder for one dispatch's device outputs: the first
    accessor pays the single device→host fetch (blocking until the
    kernel lands) and fires `on_first_resolve(np tuple, entered, fetched,
    was_ready)` (kernel-span + timeline + fetch-ledger attribution);
    everyone else reuses the numpy copy. `entered`/`fetched` bracket
    the fetch on the monotonic clock and `was_ready` is `is_ready()` of
    the first output leaf on entry: True means the kernel had landed
    before anybody read it, so the read's end says nothing about the
    kernel's. Releasing waiters BEFORE materializing lets
    their plan construction overlap the in-flight kernel — and frees
    the coordinator thread to pack the NEXT round of parked programs
    while this kernel is still running. The fetch is `np.asarray`, an
    EXPLICIT device→host transfer under jax's transfer-guard taxonomy,
    so waiters stay clean under `transfer_guard("disallow")`."""

    __slots__ = ("_dev", "_np", "_lock", "_on_first")

    def __init__(self, dev: Tuple, on_first_resolve=None) -> None:
        self._dev = dev
        self._np = None
        self._lock = threading.Lock()
        self._on_first = on_first_resolve
        # residency ledger: the lazy device outputs are live HBM from
        # launch until the first resolver materializes them — book each
        # leaf so in-flight dispatch state is visible (and a holder
        # nobody ever resolves reads as a leak, not silence)
        from ..lib.hbm import default_hbm

        hbm = default_hbm()
        for leaf in dev:
            hbm.track("select_batch.batch_out", leaf)

    def resolve(self) -> Tuple:
        with self._lock:
            if self._np is None:
                entered = time.monotonic()
                is_ready = getattr(self._dev[0], "is_ready", None)
                was_ready = bool(is_ready()) if is_ready else None
                self._np = tuple(np.asarray(x) for x in self._dev)
                fetched = time.monotonic()
                # dropping the device refs frees the kernel outputs'
                # HBM; the residency bookings release with them
                self._dev = None
                if self._on_first is not None:
                    cb, self._on_first = self._on_first, None
                    cb(self._np, entered, fetched, was_ready)
            return self._np


class SelectCoordinator:
    """Fuses concurrent select dispatches from one eval batch."""

    #: floor on wave width: fewer lanes than this and the dispatch just
    #: rides the sequential chain (a 1-lane wave is the chain, minus a
    #: shared compile)
    _MIN_WAVE_LANES = 2
    #: ceiling on wave width: more disjoint groups than this share lanes
    _MAX_WAVE_LANES = 8

    def __init__(self, window_s: float = 0.004, tracer=None,
                 timeline=None, registry=None) -> None:
        self._cv = threading.Condition()
        self._live = 0
        self._parked: List[_SelectReq] = []
        self.window_s = window_s
        # stats: counts only, written by the coordinator-driving worker
        # thread in _dispatch (intervals live in `eval.phase.*` and
        # `pipeline.*`). Readers copy after finish_batch. pack_bytes
        # counts the packed-transport buffers independently of the
        # ledger — the attribution test cross-checks the two.
        self.stats = {"dispatches": 0, "programs": 0, "batched": 0,
                      "pack_bytes": 0}
        #: eval-lifecycle tracer + program-order → eval-id map (worker
        #: fills trace_ids in start_batch) for per-eval pack/kernel spans
        self.tracer = tracer
        self.trace_ids: Dict[int, str] = {}
        #: program-order → broker conflict-group id (worker fills in
        #: start_batch from dequeue_batch's footprint partition); absent
        #: orders conflict with everything — bare coordinators and
        #: non-broker callers keep today's sequential chain
        self.group_ids: Dict[int, int] = {}
        #: program-order → bool[n_cap] node-footprint mask (worker fills
        #: from Server._eval_footprint); certification intersects these
        #: with the chain's stale rows — absent/None conflicts with
        #: every stale row, so the program rolls back on ANY conflicting
        #: commit (always sound, never fast)
        self.footprints: Dict[int, Optional[np.ndarray]] = {}
        #: the NEXT batch's coordinator (worker wires it before driving
        #: this one): offered a speculative launch the moment this
        #: batch's fused dispatch (or certified speculation) has a
        #: chain carry to predict from
        self.successor: Optional["SelectCoordinator"] = None
        #: pending speculative dispatch awaiting certification (set by
        #: _dispatch_table(spec=True) on the predecessor's thread,
        #: consumed at the top of run())
        self._spec: Optional[dict] = None
        self._ran = False
        #: server metrics registry for the wave.* instruments (None for
        #: bare coordinators in tests — wave stats still land in .stats)
        self.registry = registry
        #: dispatch-pipeline timeline (lib/transfer.DispatchTimeline,
        #: server-owned); None for bare coordinators in tests
        self.timeline = timeline

    # ---- scheduler-thread side ----

    def add_thread(self) -> None:
        with self._cv:
            self._live += 1

    def thread_done(self) -> None:
        with self._cv:
            self._live -= 1
            self._cv.notify_all()

    def select(self, arrays_fn, params, n_place: int, order: int = 0,
               explain: bool = False):
        """Park until the coordinator dispatches this program. Returns
        (sel_rows i32[M], scores f32[M], nodes_feasible int,
        nodes_fit i32[M], explain PlacementExplain|None — numpy leaves,
        this program's slice — plus the dispatch token, None off the
        table path; the scheduler stamps it on its plan as carry_token
        so the commit window binds to THIS dispatch's carry).
        Materialization happens HERE, on the waiter thread — the
        coordinator releases waiters at kernel launch, so this blocks
        until the fused chain actually lands."""
        req = _SelectReq(arrays_fn, params, n_place, order, explain)
        tracer = self.tracer
        if tracer is not None:
            tracer.host_end()  # prepare (or the last group's build)
        t_in = time.monotonic()
        with self._cv:
            self._parked.append(req)
            self._cv.notify_all()
        with host_span("park"):
            req.event.wait()
        t_set = time.monotonic()
        if req.err is not None:
            raise req.err
        holder, i, token = req.out
        with host_span("result_wait"):
            out = holder.resolve()
        if tracer is not None:
            tracer.host_add("park", t_in, t_set)
            tracer.host_add("result_wait", t_set, time.monotonic())
            tracer.host_begin("plan_build")
        sel, score, feas, fit = out[:4]
        # a fused dispatch runs with explain when ANY program asked —
        # but a program that opted out must not receive attribution it
        # didn't request (its scheduler would record counters the
        # caller explicitly disabled). Slice the explain leaves by
        # FIELD COUNT, not to the end: a wave dispatch appends its
        # cross-lane collision scalar after them.
        ex_leaves = ()
        if explain and len(out) > 4:
            from ..kernels.placement import PlacementExplain

            ex_leaves = out[4:4 + len(PlacementExplain._fields)]
        ex = None
        if i is None:
            if ex_leaves:
                from ..kernels.placement import PlacementExplain

                ex = PlacementExplain(*ex_leaves)
            return sel, score, int(feas), fit, ex, token
        if ex_leaves:
            from ..kernels.placement import PlacementExplain

            # chained dispatch: every explain leaf has a leading
            # program axis — slice this program's row
            ex = PlacementExplain(*(leaf[i] for leaf in ex_leaves))
        return sel[i], score[i], int(feas[i]), fit[i], ex, token

    # ---- coordinator side (the worker's batch thread) ----

    def run(self) -> None:
        """Dispatch parked programs until all scheduler threads finish.

        Round 1 is a STRICT rendezvous: before the first dispatch no
        thread can be blocked anywhere but here (submit_plan only happens
        after a select), so waiting for every live thread costs nothing
        and yields one full-width chain instead of several partial ones.
        Later rounds (plan-refresh retries, multi-TG jobs) use a short
        window — batch-mates may legitimately be busy applying plans.

        When the batch was already launched SPECULATIVELY by the
        predecessor's coordinator (self._spec), the first act is
        certification: by the time the worker drives this coordinator,
        every predecessor plan has committed, so the chain's stale-row
        set is final for the speculative launch — certified program
        slices release with their speculative results, rolled-back ones
        re-dispatch against the committed view."""
        self._ran = True
        first = True
        if self._spec is not None:
            spec, self._spec = self._spec, None
            first = False  # round-1 rendezvous already happened
            try:
                with host_span("certify"):
                    self._certify_spec(spec)
            except BaseException as e:  # noqa: BLE001 — fail the waiters
                for r in spec["reqs"]:
                    if not r.event.is_set():
                        r.err = e
                        r.event.set()
        while True:
            with self._cv:
                deadline = None
                while True:
                    if self._parked:
                        if len(self._parked) >= self._live:
                            break
                        # round 1 gets a generous deadline (a stops-only
                        # eval can briefly be in submit_plan before its
                        # first select; unbounded waiting could stall on
                        # a wedged apply), later rounds a tight one
                        window = 0.1 if first else self.window_s
                        if deadline is None:
                            deadline = time.time() + window
                        remaining = deadline - time.time()
                        if remaining <= 0:
                            break
                        self._cv.wait(remaining)
                    else:
                        if self._live == 0:
                            return
                        deadline = None
                        self._cv.wait(0.05)
                batch, self._parked = self._parked, []
                first = False
            try:
                self._dispatch(batch)
            except BaseException as e:  # noqa: BLE001 — fail the waiters
                for r in batch:
                    if not r.event.is_set():
                        r.err = e
                        r.event.set()

    def _dispatch(self, batch: List[_SelectReq]) -> None:
        from ..kernels.placement import (pack_params, place_packed_chain,
                                         place_task_group_jit)
        from ..lib.transfer import default_ledger, guard_scope
        from ..parallel.mesh import pad_params, stack_params

        led = default_ledger()
        self.stats["dispatches"] += 1
        self.stats["programs"] += len(batch)
        # group by owning CLUSTER without resolving the device view yet.
        # The view is resolved exactly ONCE per group, AFTER the host
        # pack: (a) the pack overlaps the predecessor dispatch's still
        # in-flight kernel instead of serializing behind its view
        # refresh, and (b) a single resolution per dispatch means a
        # donated delta-apply can never invalidate a sibling request's
        # already-resolved buffers mid-dispatch. An arrays_fn that is
        # not a cluster-bound method (a lambda/partial caller) is
        # resolved HERE and grouped by its view's capacity buffer — the
        # pre-delta grouping rule — so same-cluster requests still fuse
        # into one conflict-aware chain instead of racing as singles.
        groups: Dict[tuple, List[_SelectReq]] = {}
        resolved: Dict[tuple, object] = {}
        for r in batch:
            owner = getattr(r.arrays_fn, "__self__", None)
            cluster = getattr(owner, "cluster", None)
            if cluster is not None:
                key = ("cluster", id(cluster))
            else:
                a = r.arrays_fn()
                key = ("arrays", id(a.capacity))
                resolved[key] = a
            groups.setdefault(key, []).append(r)
        _kernel_done = self._kernel_done_factory(led)

        for key, reqs in groups.items():
            reqs.sort(key=lambda r: r.order)
            # one fused dispatch compiles per (spec, m, explain): run
            # with explain when ANY program in the group asked — the
            # others just ignore the extra leaves
            want_ex = any(r.explain for r in reqs)
            # device-resident path first (ISSUE 10): programs whose
            # static half fits the per-cluster program table dispatch as
            # table-row indices + small dynamic rows — no packed-program
            # upload, and the chain's carry feeds the D2D plan-delta
            # update. Falls back to the legacy packed/single transport
            # on residency ceilings, caps flush races, active meshes, or
            # coordinator-less (bare arrays) callers.
            if key[0] == "cluster":
                from ..parallel.mesh import get_active_mesh

                owner = getattr(reqs[0].arrays_fn, "__self__", None)
                cluster = getattr(owner, "cluster", None)
                if cluster is not None and get_active_mesh() is None:
                    if self._dispatch_table(reqs, cluster, want_ex, led,
                                            _kernel_done):
                        continue
            if len(reqs) == 1:
                r = reqs[0]
                tv = time.monotonic()
                with led.scope() as moved, host_span("view"):
                    arrays = resolved.get(key) or r.arrays_fn()
                tk = time.monotonic()
                self._trace([r], "delta_apply", tv, tk)
                (p,), m = pad_params([r.params])
                with host_span("launch"):
                    res = place_task_group_jit(arrays, p, m,
                                               explain=want_ex)
                tl = time.monotonic()
                seq = 0
                if self.timeline is not None:
                    # zero-length pack: the single path has no packed
                    # transport; its params ride jit dispatch (see
                    # stack._to_device — deliberately outside the guard)
                    seq = self.timeline.commit(
                        programs=1, batched=False,
                        pack=(tv, tv), view=(tv, tk),
                        kernel_start=tk, launch_end=tl,
                        transfer_bytes=moved[0], transfer_count=moved[1],
                        traces=self._dist_traces([r]))
                dev = (res.sel_idx, res.sel_score,
                       res.nodes_feasible, res.nodes_fit)
                if res.explain is not None:
                    dev = dev + tuple(res.explain)
                out = _BatchOut(dev, _kernel_done([r], tk, seq))
                self._release(out, seq, [(r, None, None)])
                continue
            self.stats["batched"] += len(reqs)
            params_list = [r.params for r in reqs]
            # pad the program axis to a power of two with inert programs
            # (n_place=0, no deltas) so chain compiles are shared across
            # batch sizes instead of one per B
            b = _bucket(len(reqs), lo=2)
            if b > len(reqs):
                pad = _inert_program(params_list[0])
                params_list = params_list + [pad] * (b - len(reqs))
            t0 = time.monotonic()
            with host_span("pack"):
                stacked, m = stack_params(params_list)
                # packed transport: one buffer per dtype class instead of
                # ~40 per-leaf host→device transfers (per-transfer cost
                # not measured on an attached chip)
                ibuf, fbuf, ubuf, spec = pack_params(stacked)
            t1 = time.monotonic()
            self._trace(reqs, "pack", t0, t1)
            # Everything device-touching from here to launch runs under
            # the transfer guard (NOMAD_TPU_TRANSFER_GUARD): transfers
            # on this path are all EXPLICIT and ledger-accounted, so a
            # guard hit is an unattributed host↔device round-trip — the
            # runtime analog of a new NLJ finding.
            with guard_scope():
                import jax.numpy as jnp

                nb = ibuf.nbytes + fbuf.nbytes + ubuf.nbytes
                with led.timed("select_batch.pack_buffers", nb, count=3):
                    dibuf = jnp.asarray(ibuf)
                    dfbuf = jnp.asarray(fbuf)
                    dubuf = jnp.asarray(ubuf)
                self.stats["pack_bytes"] += nb
                t2 = time.monotonic()
                # view AFTER pack, at the last possible instant before
                # the kernel: the predecessor batch's plans have
                # committed by now, and the delta log makes this a
                # row-update instead of a full re-upload (BENCH_r05's
                # dominant e2e cost)
                with led.scope() as moved, host_span("view"):
                    arrays = resolved.get(key) or reqs[0].arrays_fn()
                tv = time.monotonic()
                self._trace(reqs, "delta_apply", t2, tv)
                with host_span("launch"):
                    dev_out = place_packed_chain(
                        arrays, dibuf, dfbuf, dubuf, spec, m,
                        explain=want_ex)
                tl = time.monotonic()
            seq = 0
            if self.timeline is not None:
                seq = self.timeline.commit(
                    programs=len(reqs), batched=True,
                    pack=(t0, t1), upload=(t1, t2), view=(t2, tv),
                    kernel_start=tv, launch_end=tl,
                    transfer_bytes=nb + moved[0],
                    transfer_count=3 + moved[1],
                    traces=self._dist_traces(reqs))
            out = _BatchOut(dev_out, _kernel_done(reqs, tv, seq))
            # release waiters at LAUNCH: each materializes the shared
            # output as the chain lands and rolls straight into its plan
            # apply, while this thread returns to run() and can pack the
            # next round of parked programs against the in-flight kernel
            self._release(out, seq,
                          [(r, i, None) for i, r in enumerate(reqs)])

    def _release(self, holder: "_BatchOut", seq: int, outs) -> None:
        """Hand `holder` to its waiters — `outs` is [(req, program
        index, token)] — and stamp the instant on the dispatch's
        timeline record: the end of `release` (of `spec_hold` for a
        speculative dispatch, which its certification releases) and
        the start of `wake`."""
        if self.timeline is not None:
            self.timeline.released(seq, time.monotonic())
        for r, i, token in outs:
            r.out = (holder, i, token)
            r.event.set()

    def _kernel_done_factory(self, led):
        """Resolver-callback factory shared by the normal dispatch path
        and the speculative one (`_dispatch_spec`) — ONE body, so the
        kernel-land bookkeeping (stats, trace, fetch ledger, timeline,
        collision flight event, carry prediction, lease release) can
        never drift between them."""

        def _kernel_done(reqs, t_launch, seq, cluster=None, token=None,
                         idxs=None, wave=False, spec_state=None):
            def cb(np_out, entered, t_end, was_ready):
                if spec_state is not None:
                    # certification reads this to account the wasted
                    # share of a rolled-back speculative kernel
                    spec_state["kernel_ms"] = (t_end - t_launch) * 1e3
                self._trace(reqs, "kernel", t_launch, t_end)
                # the device→host fetch happened HERE (np.asarray on the
                # first-resolving waiter's thread): credit it to the
                # dispatch's timeline record + the fetch ledger site
                fetch = sum(int(getattr(a, "nbytes", 0)) for a in np_out)
                led.record("select_batch.fetch", fetch,
                           count=len(np_out))
                if self.timeline is not None:
                    self.timeline.kernel_end(seq, t_end,
                                             fetch_bytes=fetch,
                                             fetch_count=len(np_out),
                                             entered=entered,
                                             was_ready=was_ready)
                if cluster is not None:
                    # table-path dispatch: the chain has landed — fill
                    # the carry note's predicted placement rows (per
                    # eval, from sel_idx) and release the view lease so
                    # the next refresh may donate again
                    from ..scheduler import stack as stack_mod

                    coll = int(np_out[-1]) if wave else 0
                    if coll:
                        if self.registry is not None:
                            self.registry.inc("wave.collisions", coll)
                        # stale-footprint spike → flight event: a burst
                        # here is the drain partition losing against
                        # cluster churn (plan-apply absorbs the race;
                        # the recorder makes the episode visible)
                        from ..lib.flight import default_flight

                        try:
                            default_flight().record(
                                "wave.collisions", key=str(seq),
                                severity="warn",
                                detail={"collisions": coll,
                                        "programs": len(reqs)})
                        except Exception:  # noqa: BLE001 — telemetry
                            pass
                    sel = np.asarray(np_out[0])
                    predicted: Dict[Optional[str], set] = {}
                    for j, r in enumerate(reqs):
                        i = idxs[j] if idxs is not None else j
                        eid = self.trace_ids.get(r.order)
                        rows = {int(x) for x in sel[i].reshape(-1)
                                if x >= 0}
                        predicted[eid] = predicted.get(eid, set()) | rows
                    if not coll:
                        # a cross-lane collision row's true combined
                        # usage exists in no lane: leave the carry note
                        # unpredicted — unadoptable, the next refresh
                        # overlays from host (view.carry_rejects);
                        # chain-held carries route through the same fill
                        stack_mod.carry_predicted(cluster, token,
                                                  predicted)
                    stack_mod.release_view(cluster, token)
            return cb

        return _kernel_done

    def _dispatch_table(self, reqs, cluster, want_ex, led,
                        _kernel_done, spec: bool = False) -> bool:
        """Dispatch one cluster group through the device program table:
        as ONE chain (`place_table_chain`), or — when the requests span
        ≥2 disjoint broker conflict groups — as a WAVE of parallel lanes
        (`place_table_wave`: the program axis is [lanes, lane length]
        and the kernel's carry is the per-row fold of the lane carries;
        `_table_layout` has the layout, the only thing that differs).
        Returns False untouched (no span, no stats, no lease, no side
        effects on reqs) when the group can't ride the table — the
        caller then runs the legacy transport.

        `spec` (ISSUE 15): resolve the view from the speculative chain
        (predicted post-commit state) instead of the committed cache,
        record the carry on the chain instead of the cache note, and
        STASH the outputs for commit-time certification instead of
        releasing the waiters — run() certifies once the predecessor's
        plans have all committed."""
        from ..kernels.placement import place_table_chain, place_table_wave
        from ..lib.transfer import guard_scope
        from ..scheduler import stack as stack_mod
        from .program_table import table_for

        lanes = self._wave_lanes(reqs)
        table = table_for(cluster)
        t0 = time.monotonic()
        with host_span("pack"):
            reqs, params_list, idxs, shape, lanes_idx = _table_layout(lanes)
            prep = table.prepare(params_list)
        if prep is None:
            return False
        t1 = time.monotonic()
        with guard_scope():
            import jax.numpy as jnp

            com = table.commit(prep, led)
            if com is None:
                return False  # caps flush raced this prepare — the
                # legacy fallback re-packs
            ti, tf, tu, ins_nb, ins_count = com
            dyn = (prep.rows, prep.dyn_i, prep.dyn_f, prep.dyn_u)
            if shape is not None:
                dyn = tuple(a.reshape(shape + a.shape[1:]) for a in dyn)
            nb = sum(a.nbytes for a in dyn)
            with led.timed("select_batch.dyn_rows", nb, count=4):
                drows, di, df, du = (jnp.asarray(a) for a in dyn)
            t2 = time.monotonic()
            # view AFTER pack, at the last possible instant before the
            # kernel (the predecessor batch's plans have committed and,
            # when its carry survived, resolve here as a zero-transfer
            # buffer adoption). The dispatch token leases the resolved
            # buffers ATOMICALLY with the resolve — a concurrent
            # refresh can then never donate them out from under the
            # launch below.
            token = next(_DISPATCH_TOKENS)
            try:
                with led.scope() as moved, host_span("view"):
                    if spec:
                        arrays = stack_mod.spec_chain_view(cluster, token)
                        if arrays is None:
                            return False  # nothing predictable — the
                            # caller re-parks and the batch dispatches
                            # normally once the predecessor commits
                    else:
                        arrays = reqs[0].arrays_fn(lease_token=token)
                # the launch is certain from here: only now is the pack
                # counted, so a miss above leaves nothing for the real
                # dispatch that follows it to count twice
                self._trace(reqs, "pack", t0, t1)
                if len(reqs) > 1:
                    self.stats["batched"] += len(reqs)
                self.stats["pack_bytes"] += nb + ins_nb
                tv = time.monotonic()
                self._trace(reqs, "delta_apply", t2, tv)
                place = place_table_chain if shape is None \
                    else place_table_wave
                with host_span("launch"):
                    out, carry = place(
                        arrays, ti, tf, tu, drows, di, df, du,
                        prep.sspec, prep.dspec, prep.m, explain=want_ex)
                tl = time.monotonic()
            except BaseException:
                # the lease is normally released by the first resolver's
                # kernel_end; a failed launch has no resolvers
                stack_mod.release_view(cluster, token)
                raise
        spec_state = None
        if spec:
            spec_state = {"reqs": reqs, "idxs": idxs, "cluster": cluster,
                          "token": token, "lanes": lanes_idx,
                          "kernel_ms": 0.0}
        if shape is not None and self.registry is not None:
            self.registry.inc("wave.dispatches")
            self.registry.inc("wave.programs", len(reqs))
            # the bucketed [lanes, lane length] axis: programs + inert pads
            self.registry.inc("wave.slots", shape[0] * shape[1])
            self.registry.add_sample("wave.lanes", len(lanes))
            self.registry.add_sample("wave.lane_len",
                                     max(len(l) for l in lanes))
        return self._launched(
            reqs, idxs, cluster, token, arrays, out, carry, spec_state,
            _kernel_done,
            dict(programs=len(reqs), batched=len(reqs) > 1,
                 pack=(t0, t1), upload=(t1, t2), view=(t2, tv),
                 kernel_start=tv, launch_end=tl,
                 transfer_bytes=nb + ins_nb + moved[0],
                 transfer_count=4 + ins_count + moved[1],
                 speculative=spec))

    def _launched(self, reqs, idxs, cluster, token, arrays, out, carry,
                  spec_state, _kernel_done, record: dict) -> bool:
        """The `release` interval of a table dispatch, chain (`idxs`
        None) or wave (`idxs` = each request's [lane, position] slot):
        timeline record, carry note, lazy holder, then the waiters are
        released — or, for a speculative launch (`spec_state`), stashed
        for commit-time certification."""
        from ..lib.hbm import default_hbm
        from ..scheduler import stack as stack_mod

        spec = spec_state is not None
        with host_span("release"):
            seq = 0
            if self.timeline is not None:
                seq = self.timeline.commit(**record)
            # carry note: once this dispatch's outputs land and its
            # plans commit, the next refresh may adopt the chain's
            # (used, dyn_free) carry instead of re-uploading the
            # committed rows. The token (already leased at resolve)
            # also rides the waiters' results onto their plans
            # (carry_token): a commit window covers the carry only when
            # it came from THIS dispatch. Residency: the carry arrays
            # are held HBM until the next refresh adopts (re-sites them
            # into the view) or rejects (drops them — the booking
            # releases with the buffers).
            hbm = default_hbm()
            hbm.track("select_batch.carry", carry[0])
            hbm.track("select_batch.carry", carry[1])
            evals = [self.trace_ids.get(r.order) for r in reqs]
            stop_rows = set()
            for r in reqs:
                p = r.params
                for arr in (p.delta_idx, p.pclr_idx, p.pset_idx):
                    a = np.asarray(arr).reshape(-1)
                    stop_rows.update(int(x) for x in a[a >= 0])
            if spec:
                stack_mod.spec_chain_advance(cluster, token, evals,
                                             stop_rows, carry[0], carry[1])
            else:
                stack_mod.note_dispatch_carry(cluster, token, arrays,
                                              evals, stop_rows, carry[0],
                                              carry[1])
            holder = _BatchOut(
                tuple(out),
                _kernel_done(reqs, record["kernel_start"], seq,
                             cluster=cluster, token=token, idxs=idxs,
                             wave=idxs is not None,
                             spec_state=spec_state))
            if spec:
                spec_state["holder"] = holder
                spec_state["seq"] = seq
                self._spec = spec_state
                if self.registry is not None:
                    self.registry.inc("spec.launches")
                if self.timeline is not None:
                    self.timeline.released(seq, time.monotonic(),
                                           held=True)
                return True
            self._release(holder, seq,
                          [(r, j if idxs is None else idxs[j], token)
                           for j, r in enumerate(reqs)])
        # the launched dispatch has a chain carry to predict from: offer
        # the NEXT batch a speculative launch against it, overlapping
        # this batch's plan commits with its successor's kernel
        self._offer_spec(cluster)
        return True

    def _wave_lanes(self, reqs) -> List[list]:
        """Partition a cluster group's requests into wave lanes from the
        broker's conflict groups. Returns [reqs] (single lane — the
        sequential chain) unless ≥2 disjoint groups exist and every
        request has a known group: an order with no group id conflicts
        with everything, so its whole dispatch stays sequential.

        Groups pack into at most `_MAX_WAVE_LANES` lanes, longest-first
        onto the least-loaded lane (LPT): the vmapped scan's length is
        the LONGEST lane, so balancing lanes is what actually shortens
        the serial chain. Concatenating disjoint
        groups inside one lane is always safe — a lane is sequential,
        and sequential is correct for any footprint relation."""
        if not self.group_ids:
            return [reqs]
        groups: Dict[int, list] = {}
        for r in reqs:
            gid = self.group_ids.get(r.order)
            if gid is None:
                return [reqs]
            groups.setdefault(gid, []).append(r)
        if len(groups) < self._MIN_WAVE_LANES:
            return [reqs]
        n_lanes = min(len(groups), self._MAX_WAVE_LANES)
        lanes: List[list] = [[] for _ in range(n_lanes)]
        for g in sorted(groups.values(), key=len, reverse=True):
            min(lanes, key=len).extend(g)
        return lanes

    # ---- speculative launch + commit-time certification (ISSUE 15) ----

    def _offer_spec(self, cluster) -> None:
        """A fused table dispatch just launched (or certified): its
        chain carry predicts the post-commit view. Offer the successor
        batch a speculative launch against it — the successor's kernel
        then queues right behind this one on device while this batch's
        plans commit on the waiter threads. Speculation must never fail
        the real path: any error just means no speculation."""
        succ = self.successor
        if succ is None or succ is self:
            return
        try:
            succ.try_spec_launch(cluster)
        except Exception:  # noqa: BLE001 — speculative only
            pass

    def try_spec_launch(self, cluster) -> bool:
        """Speculatively dispatch this coordinator's round-1 batch
        against the predicted post-commit view of `cluster`. Called on
        the PREDECESSOR batch's coordinator thread (the shared worker
        thread — run() has not been entered yet, so there is no
        dispatch race). Waits briefly for the round-1 rendezvous (the
        schedulers are compiling on the pool); aborts — leaving the
        batch parked for the normal path — unless every live thread is
        parked, every request is bound to `cluster`, the adaptive gate
        is armed, and the chain has a carry to predict from."""
        if not spec_enabled() or self._ran or self._spec is not None:
            return False
        from ..parallel.mesh import get_active_mesh

        if get_active_mesh() is not None:
            return False
        gate = _gate_for(cluster)
        if not gate.armed():
            return False
        deadline = time.time() + _spec_park_s()
        with self._cv:
            while True:
                if self._parked and len(self._parked) >= self._live:
                    break
                remaining = deadline - time.time()
                if remaining <= 0:
                    # the wait was paid for nothing — consecutive
                    # misses disarm the gate (see SpecGate)
                    gate.record_miss()
                    return False
                self._cv.wait(min(remaining, 0.01))
            batch = list(self._parked)
            for r in batch:
                owner = getattr(r.arrays_fn, "__self__", None)
                if getattr(owner, "cluster", None) is not cluster:
                    return False
            self._parked = []
        batch.sort(key=lambda r: r.order)
        ok = False
        try:
            ok = self._dispatch_spec(batch, cluster)
        finally:
            if not ok:
                gate.record_miss()
                # nothing launched: re-park untouched for run()'s
                # normal dispatch
                with self._cv:
                    self._parked = batch + self._parked
                    self._cv.notify_all()
        return ok

    def _dispatch_spec(self, batch, cluster) -> bool:
        from ..lib.transfer import default_ledger

        led = default_ledger()
        # the SAME resolver callback as the normal path (collision
        # flight events, carry-prediction fill — chain-aware — and
        # lease release included); only the dispatch entry differs
        _kernel_done = self._kernel_done_factory(led)
        want_ex = any(r.explain for r in batch)
        if not self._dispatch_table(batch, cluster, want_ex, led,
                                    _kernel_done, spec=True):
            return False
        self.stats["dispatches"] += 1
        self.stats["programs"] += len(batch)
        return True

    def _certify_spec(self, spec) -> None:
        """Commit-time certification: the predecessor batch's plans have
        ALL committed (the worker finishes batch k before driving this
        coordinator), so the chain's stale-row set is final for this
        launch. A program slice keeps its speculative result iff its
        lane prefix is clean: no program at or before it in its lane
        has a footprint touching a stale row (later programs in a lane
        saw earlier ones' placements through the in-lane carry, so a
        rollback cascades down its lane — disjoint lanes are
        untouched). Rolled-back slices re-dispatch against the
        committed view; `spec.redispatch_programs` counts them
        exactly."""
        from ..scheduler import stack as stack_mod

        reqs = spec["reqs"]
        cluster = spec["cluster"]
        holder = spec["holder"]
        idxs = spec["idxs"]
        token = spec["token"]
        reg = self.registry
        try:
            stale = stack_mod.spec_chain_certify(cluster)
        except Exception:  # noqa: BLE001 — unprovable == roll back
            stale = None
        rolled: set = set()
        if stale is None:
            rolled = set(range(len(reqs)))
        elif stale:
            for lane in spec["lanes"]:
                for pos, i in enumerate(lane):
                    fp = self.footprints.get(reqs[i].order)
                    if self._fp_hit(fp, stale):
                        rolled.update(lane[pos:])
                        break
        self._release(holder, spec["seq"],
                      [(reqs[i], i if idxs is None else idxs[i], token)
                       for i in range(len(reqs)) if i not in rolled])
        if not rolled:
            if reg is not None:
                reg.inc("spec.certified")
            if self.timeline is not None:
                self.timeline.spec_resolve(spec["seq"], "certified")
            _gate_for(cluster).record(False)
            # hand the certified HEAD carry to the view cache instead
            # of dropping it at chain end: a refresh landing mid-chain
            # or after the chain winds down adopts the chain's folded
            # view and overlays only the genuinely-foreign delta
            # (stack.spec_chain_publish_carry / _chain_carry_overlay)
            stack_mod.spec_chain_publish_carry(cluster)
            # chain continues: this dispatch's carry predicts the next
            # post-commit view while THESE plans commit
            self._offer_spec(cluster)
            return
        # ---- rollback ----
        # resolve the holder on THIS thread: the kernel must land so
        # its wasted share is known, the view lease releases, and a
        # fully rolled-back dispatch leaves no live device outputs
        # (the HBM leak gate covers exactly this path)
        holder.resolve()
        kms = float(spec.get("kernel_ms") or 0.0)
        wasted = kms * len(rolled) / max(len(reqs), 1)
        if reg is not None:
            reg.inc("spec.rolled_back")
            reg.inc("spec.redispatch_programs", len(rolled))
            reg.inc("spec.wasted_kernel_ms", wasted)
        if self.timeline is not None:
            self.timeline.spec_resolve(
                spec["seq"], "rolled_back",
                wasted_frac=len(rolled) / max(len(reqs), 1))
        _gate_for(cluster).record(True)
        rejected = stack_mod.spec_chain_last_rejected(cluster)
        stack_mod.spec_chain_reset(cluster)
        from ..lib.flight import default_flight

        try:
            default_flight().record(
                "spec.rollback", key=str(spec["seq"]), severity="warn",
                detail={"programs": len(rolled), "batch": len(reqs),
                        "stale_rows": (sorted(stale)[:8]
                                       if stale else None),
                        "rejected_rows": (sorted(rejected)[:8]
                                          if rejected else None),
                        "wasted_kernel_ms": round(wasted, 3)})
        except Exception:  # noqa: BLE001 — telemetry only
            pass
        # re-dispatch ONLY the affected slices against the committed
        # view (normal path: fresh refresh, fresh carry note — the
        # chain re-seeds from it via the launch hook)
        self._dispatch([reqs[i] for i in sorted(rolled)])

    @staticmethod
    def _fp_hit(fp, stale) -> bool:
        """Does a program's footprint mask touch any stale row? An
        unknown footprint (None) conflicts with everything; a stale row
        past the mask's length post-dates its estimate and counts as a
        hit (sound, and node growth resets the chain anyway)."""
        if fp is None:
            return bool(stale)
        n = fp.shape[0]
        return any(r >= n or bool(fp[r]) for r in stale)

    def _trace(self, reqs: List[_SelectReq], phase: str,
               start: float, end: float) -> None:
        """Per-eval span for a fused phase: every program in the batch
        rode the same host pack / device dispatch, so each gets the
        batch's interval (monotonic clock)."""
        if self.tracer is None:
            return
        for r in reqs:
            tid = self.trace_ids.get(r.order)
            if tid is not None:
                self.tracer.record(tid, phase, start=start, end=end)

    def _dist_traces(self, reqs: List[_SelectReq]) -> List[str]:
        """Distributed trace ids (lib/tracectx.py) of the evals riding a
        dispatch, deduped in batch order — stamped onto the
        DispatchTimeline record so the per-process pipeline view ties
        back into the cross-process trace tree."""
        if self.tracer is None:
            return []
        out: List[str] = []
        for r in reqs:
            tid = self.trace_ids.get(r.order)
            ctx = self.tracer.binding(tid) if tid is not None else None
            if ctx is not None and ctx.trace_id not in out:
                out.append(ctx.trace_id)
        return out


def _table_layout(lanes):
    """Lay a table dispatch's lanes (`_wave_lanes`) out on the program
    axis. Pure: no device, no lock. Returns (reqs in axis order, the
    params list padded with inert programs, idxs, shape, lanes_idx):
    `lanes_idx` holds each lane's positions in `reqs`. One lane is the
    flat chain: `idxs` and `shape` are None and the axis is bucketed to
    a power of two so chain compiles are shared across batch sizes. ≥2
    lanes are a wave: `shape` = (lane count, lane length), both
    bucketed, `idxs[j]` = `reqs[j]`'s slot lane*length+position, short
    lanes and the fully-inert pad lanes filled with the pad, which
    shares the first program's static table row and folds as a no-op."""
    reqs = [r for lane in lanes for r in lane]
    lane_len = _bucket(max(len(lane) for lane in lanes), lo=2)
    n_lanes = _bucket(len(lanes), lo=2) if len(lanes) > 1 else 1
    pad = _inert_program(reqs[0].params) \
        if n_lanes * lane_len > len(reqs) else None
    params_list: List = []
    idxs: List[int] = []
    lanes_idx: List[List[int]] = []
    for li, lane in enumerate(lanes):
        lanes_idx.append(list(range(len(idxs), len(idxs) + len(lane))))
        idxs.extend(li * lane_len + pi for pi in range(len(lane)))
        params_list.extend(r.params for r in lane)
        params_list.extend([pad] * (lane_len - len(lane)))
    params_list.extend([pad] * ((n_lanes - len(lanes)) * lane_len))
    if n_lanes == 1:
        return reqs, params_list, None, None, lanes_idx
    return reqs, params_list, idxs, (n_lanes, lane_len), lanes_idx


def _inert_program(p):
    """A zero-effect pad program: places nothing (n_place=0) and carries
    no plan-relative deltas, so the chain's (used, dyn_free) carry passes
    through it unchanged. Only DYNAMIC fields are touched — n_place=0
    already makes the (static) ask/n_dyn unreachable (no step is active,
    so nothing is ever added to the carry), and keeping the static half
    bit-identical to the template program lets the pad share its device
    program-table row instead of inserting a near-duplicate."""
    z = np.zeros_like
    return p._replace(
        n_place=np.int32(0),
        delta_idx=np.full_like(np.asarray(p.delta_idx), -1),
        delta_res=z(np.asarray(p.delta_res)),
        pclr_idx=np.full_like(np.asarray(p.pclr_idx), -1),
        pclr_port=np.full_like(np.asarray(p.pclr_port), -1),
        pset_idx=np.full_like(np.asarray(p.pset_idx), -1),
        pset_port=np.full_like(np.asarray(p.pset_port), -1),
    )
