"""Host↔device transfer ledger + dispatch-pipeline timeline.

Two instruments that make the control plane's host↔device gap
measurable instead of folklore (what a round trip costs on an attached
chip is not measured; these count and attribute them):

- `TransferLedger` — per-call-site accounting of every transfer on the
  dispatch path (bytes, count, cumulative host-side ms). Call sites are
  dotted names (`stack.hot_delta`, `select_batch.pack_buffers`); the
  taxonomy is documented in README's observability section. The ledger
  is process-global (`default_ledger()`) for the same reason the
  `view.*` counters are: TPUStack is built per-eval from snapshots that
  carry no server reference.

  Completeness contract: every transfer the dispatch path performs is
  EXPLICIT (`jax.device_put`/`jnp.asarray` in, `np.asarray(dev)` out)
  and recorded at a ledger site. `jax.transfer_guard` is the enforcement
  half — implicit transfers (a numpy leaf silently uploaded at jit
  dispatch, a stray device scalar compared on host) are exactly the
  transfers the ledger CANNOT see, so the guard logs them in production
  (`NOMAD_TPU_TRANSFER_GUARD=log`) and hard-fails them in tests
  (`disallow`, tests/test_transfer.py). This is the runtime complement
  to nomadlint's static NLJ rules: NLJ catches host syncs visible in the
  AST, the guard catches the ones only dispatch can see.

- `DispatchTimeline` — a bounded ring of per-dispatch records (pack /
  view-resolve / kernel intervals on one monotonic clock) with an
  overlap/bubble metric: how much of dispatch k's host-side pack
  actually hid under dispatch k-1's in-flight kernel (`overlap_ms`), and
  how long the device sat idle between consecutive kernels
  (`bubble_ms`). PR 3's lazy `_BatchOut` release made this unreadable
  from the coarse `EvalTracer` spans — waiters attribute kernel_ms from
  whichever thread resolves first, so the per-eval trace can no longer
  say whether pipelining overlapped anything. Served on
  `/v1/scheduler/timeline` (index long-poll, the `/v1/event/stream`
  idiom) and `operator timeline`.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from .metrics import MetricsRegistry, prometheus_line

#: env knob for the production transfer-guard policy. "log" makes JAX
#: log every implicit transfer on the guarded dispatch path; "disallow"
#: turns them into hard errors (the test policy — see guard_scope).
GUARD_ENV = "NOMAD_TPU_TRANSFER_GUARD"


def guard_level() -> str:
    """Sanitized policy from the env: "allow" (default), "log", or
    "disallow". Unknown values read as "allow" — telemetry knobs must
    never brick the dispatch path."""
    lvl = os.environ.get(GUARD_ENV, "").strip().lower()
    return lvl if lvl in ("log", "disallow") else "allow"


@contextlib.contextmanager
def guard_scope(level: Optional[str] = None):
    """`jax.transfer_guard` context for the BATCHED dispatch path, a
    no-op at the default "allow" level (zero cost when unconfigured).

    Only the fused batched path runs under the guard: its transfers are
    all explicit + ledger-accounted, so any guard hit is a regression.
    The single-program fallback path deliberately stays outside — its
    ~40-leaf params pytree rides jit-dispatch implicit transfer by
    design (scheduler/stack.py `_to_device`), and guarding it would make
    `disallow` unusable as a test policy for the path that matters."""
    lvl = level if level is not None else guard_level()
    if lvl == "allow":
        yield
        return
    import jax

    with jax.transfer_guard(lvl):
        yield


# ---- transfer ledger -------------------------------------------------------


class _Site:
    __slots__ = ("bytes", "count", "ms")

    def __init__(self) -> None:
        self.bytes = 0
        self.count = 0
        self.ms = 0.0


_SCOPE_TLS = threading.local()


class TransferLedger:
    """Thread-safe per-site transfer accounting.

    `record(site, nbytes, seconds)` accumulates into the site row and —
    when a registry is attached — mirrors the totals into `transfer.*`
    counters (`transfer.bytes`, `transfer.count`, `transfer.ms`), the
    quick-look companions to the per-site breakdown.

    `scope()` additionally captures records made BY THE CALLING THREAD
    while the scope is open — the coordinator wraps its view resolution
    in one to attribute the delta-apply bytes to the dispatch record
    without double-booking concurrent workers' transfers.

    Timing is host-side call time around the transfer API; device
    copies are asynchronous, so `ms` bounds dispatch cost, not wire
    time — byte counts are the cross-host-comparable number.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None) -> None:
        self._lock = threading.Lock()
        self._sites: Dict[str, _Site] = {}
        self.registry = registry

    # -- recording --

    def record(self, site: str, nbytes: int, seconds: float = 0.0,
               count: int = 1) -> None:
        ms = seconds * 1e3
        with self._lock:
            s = self._sites.get(site)
            if s is None:
                s = self._sites[site] = _Site()
            s.bytes += int(nbytes)
            s.count += count
            s.ms += ms
        if self.registry is not None:
            self.registry.inc("transfer.bytes", nbytes)
            self.registry.inc("transfer.count", count)
            self.registry.inc("transfer.ms", ms)
        acc = getattr(_SCOPE_TLS, "acc", None)
        if acc is not None:
            acc[0] += int(nbytes)
            acc[1] += count

    @contextlib.contextmanager
    def timed(self, site: str, nbytes: int, count: int = 1):
        """Record `nbytes` at `site` with the wrapped block's wall time."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.record(site, nbytes, time.perf_counter() - t0, count)

    @contextlib.contextmanager
    def scope(self):
        """Capture (bytes, count) recorded by THIS thread inside the
        block; yields a 2-item list mutated in place. Nested scopes both
        observe inner records."""
        prev = getattr(_SCOPE_TLS, "acc", None)
        acc = [0, 0]
        _SCOPE_TLS.acc = acc
        try:
            yield acc
        finally:
            _SCOPE_TLS.acc = prev
            if prev is not None:
                prev[0] += acc[0]
                prev[1] += acc[1]

    # -- export --

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {name: {"bytes": s.bytes, "count": s.count,
                           "ms": round(s.ms, 3)}
                    for name, s in self._sites.items()}

    def totals(self) -> Tuple[int, int, float]:
        """(bytes, count, ms) across every site."""
        with self._lock:
            return (sum(s.bytes for s in self._sites.values()),
                    sum(s.count for s in self._sites.values()),
                    round(sum(s.ms for s in self._sites.values()), 3))

    def top_sites(self, n: int = 5) -> List[Dict[str, object]]:
        """Heaviest call sites by bytes, descending."""
        snap = self.snapshot()
        out = [{"site": name, **vals} for name, vals in snap.items()]
        out.sort(key=lambda e: (-e["bytes"], e["site"]))
        return out[:n]

    def prometheus(self, prefix: str = "nomad") -> str:
        """Labeled text exposition: one series per site per instrument
        (`nomad_transfer_bytes_total{site="stack.hot_delta"} 123`).
        Site names ride a label — not the metric name — so dashboards
        aggregate with sum by()/topk() instead of name regexes."""
        snap = self.snapshot()
        if not snap:
            return ""
        lines: List[str] = []
        for metric, key in (("transfer_bytes_total", "bytes"),
                            ("transfer_count_total", "count"),
                            ("transfer_ms_total", "ms")):
            name = f"{prefix}_{metric}" if prefix else metric
            lines.append(f"# TYPE {name} counter")
            for site in sorted(snap):
                lines.append(prometheus_line(name, {"site": site},
                                             float(snap[site][key])))
        return "\n".join(lines) + "\n"


_default_ledger = TransferLedger()


def default_ledger() -> TransferLedger:
    """Process-global ledger (the `view.*`-counter precedent): transfer
    sites live in per-eval stacks and module-level kernels that carry no
    server reference. Registry mirroring goes to the process-global
    registry lazily so importing this module stays jax-free and cheap."""
    if _default_ledger.registry is None:
        from .metrics import default_registry

        _default_ledger.registry = default_registry()
    return _default_ledger


# ---- dispatch-pipeline timeline --------------------------------------------


def _ms(s: Optional[float], e: Optional[float]) -> Optional[float]:
    return (None if s is None or e is None
            else round(max(e - s, 0.0) * 1e3, 3))


def _read_parts(rec: dict) -> Dict[str, Optional[float]]:
    """A record's launch → first-read interval in its five parts (ms;
    None where an instant was never stamped: a synthetic record, a
    normal dispatch's `spec_hold_ms`)."""
    stash = rec["held_at"] if rec["held_at"] is not None \
        else rec["released_at"]
    return {
        "launch_ms": _ms(rec["kernel_start"], rec["launch_end"]),
        "release_ms": _ms(rec["launch_end"], stash),
        "spec_hold_ms": _ms(rec["held_at"], rec["released_at"]),
        "wake_ms": _ms(rec["released_at"], rec["entered_at"]),
        "fetch_block_ms": _ms(rec["entered_at"], rec["kernel_end"]),
    }


class DispatchTimeline:
    """Bounded ring of per-dispatch pipeline records + overlap math.

    One record per coordinator dispatch: host pack interval, device-view
    resolve interval, the launch → FIRST READ interval (`kernel_start` →
    `kernel_end`; the end arrives asynchronously — whichever waiter
    materializes the lazy `_BatchOut` first reports it), transfer
    bytes/count for the dispatch (host→device at commit, the device→host
    fetch added at kernel end).

    `kernel_end` is the instant the first reader had the outputs on the
    host, NOT the instant the kernel ended on the device: the device's
    own end is the profiler's to give (the benchmark's
    `kernel_device_ms`). Under speculation the outputs are read a whole
    batch after they landed. What the host did between launch and first
    read is split into five intervals that sum to `kernel_ms`:

      launch_ms       inside the jitted placement call (jit-cache lookup
                      and enqueue; a retrace shows here)
      release_ms      that call's return → waiters released, or stashed
                      for certification (carry note, stop rows, holder)
      spec_hold_ms    a speculative dispatch's stash → its release by
                      certification; absent for a normal dispatch
      wake_ms         release → the first resolver enters `resolve()`
      fetch_block_ms  the first resolver's time inside `np.asarray`
                      (device not done yet + device→host copy), with
                      `was_ready` = the first output leaf's `is_ready()`
                      on entry

    Derived per record, once its PREDECESSOR's kernel interval is
    complete:

      overlap_ms  how much of this dispatch's pre-kernel host side
                  (pack + packed-buffer upload + view resolve) hid
                  under the previous dispatch's in-flight kernel — the
                  pipelining win, ~0 when dispatches serialize
      bubble_ms   device idle between the previous kernel landing and
                  this one launching — the pipeline stall the kernel
                  can't hide

    The first record (no predecessor in the ring) carries null for both
    and is excluded from aggregates. Where the PREDECESSOR's read did
    not block (`was_ready` true) its `kernel_end` is only an upper bound
    of the kernel's end: the record's overlap/bubble are then flagged
    `bounds_only` (overlap an upper, bubble a lower bound), left out of
    the `pipeline.overlap_ms` / `pipeline.bubble_ms` histograms and of
    `summary()`'s aggregates, and counted in
    `pipeline.kernel_end_unknown`. Records export monotonic offsets
    against a wall anchor exactly like lib/trace.py traces.

    `records_after(index, timeout)` is the event-broker long-poll shape
    (`server/events.py events_after`): strictly increasing `seq`, blocks
    until a record past `index` exists or the timeout lapses. Ring
    eviction is telemetry loss, never an error.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 capacity: int = 256) -> None:
        self.registry = registry
        self._cv = threading.Condition()
        self._ring: "deque[dict]" = deque(maxlen=max(int(capacity), 2))
        self._seq = 0
        self.wall_anchor = time.time()
        self.mono_anchor = time.monotonic()

    # -- recording (coordinator side) --

    def commit(self, *, programs: int, batched: bool,
               pack: Tuple[float, float], view: Tuple[float, float],
               kernel_start: float, transfer_bytes: int,
               transfer_count: int,
               upload: Optional[Tuple[float, float]] = None,
               speculative: bool = False,
               traces: Optional[List[str]] = None,
               launch_end: Optional[float] = None) -> int:
        """Append a dispatch record at kernel launch; returns its seq.
        `pack`/`upload`/`view` are monotonic (start, end) intervals —
        `upload` is the explicit packed-buffer host→device transfer
        between pack and view (zero-length when absent), kept as its
        own phase so the upload cost lands in a named bucket instead
        of leaking into bubble_ms.

        `speculative` marks a dispatch launched against the predicted
        post-commit view (ISSUE 15); its outcome arrives later via
        `spec_resolve` and a rolled-back kernel is accounted as WASTED
        device time, never as useful overlap."""
        if upload is None:
            upload = (pack[1], pack[1])
        reg = self.registry
        with self._cv:
            self._seq += 1
            seq = self._seq
            rec = {
                "seq": seq, "programs": int(programs),
                "batched": bool(batched),
                "pack_start": pack[0], "pack_end": pack[1],
                "upload_start": upload[0], "upload_end": upload[1],
                "view_start": view[0], "view_end": view[1],
                "kernel_start": kernel_start, "kernel_end": None,
                # launch → first read, split (class docstring)
                "launch_end": launch_end, "held_at": None,
                "released_at": None, "entered_at": None,
                "was_ready": None, "bounds_only": False,
                "transfer_bytes": int(transfer_bytes),
                "transfer_count": int(transfer_count),
                "overlap_ms": None, "bubble_ms": None,
                "speculative": bool(speculative),
                "spec_outcome": None,
                # distributed trace ids of the evals whose programs ride
                # this dispatch — ties the timeline record into the
                # cross-process trace tree (lib/tracectx.py).
                "traces": [t for t in (traces or []) if t],
            }
            self._ring.append(rec)
            self._finalize_locked(seq)
            self._cv.notify_all()
        if reg is not None:
            reg.inc("pipeline.dispatches")
            reg.inc("pipeline.programs", programs)
            reg.inc("pipeline.transfer_bytes", transfer_bytes)
            reg.inc("pipeline.transfer_count", transfer_count)
            reg.add_sample("pipeline.pack_ms",
                           max(pack[1] - pack[0], 0.0) * 1e3)
            reg.add_sample("pipeline.upload_ms",
                           max(upload[1] - upload[0], 0.0) * 1e3)
            reg.add_sample("pipeline.view_ms",
                           max(view[1] - view[0], 0.0) * 1e3)
            # the whole pre-kernel host side (pack + upload + view):
            # overlap_pct's denominator
            reg.add_sample("pipeline.host_ms",
                           max(view[1] - pack[0], 0.0) * 1e3)
        return seq

    def released(self, seq: int, at: float, held: bool = False) -> None:
        """The dispatch's waiters were released at `at` — or, `held`, its
        outputs were stashed for certification (a speculative dispatch
        is released later, by its verdict). No-op for evicted records."""
        with self._cv:
            rec = self._find_locked(seq)
            if rec is not None:
                rec["held_at" if held else "released_at"] = at

    def kernel_end(self, seq: int, end: Optional[float] = None,
                   fetch_bytes: int = 0, fetch_count: int = 0,
                   entered: Optional[float] = None,
                   was_ready: Optional[bool] = None) -> None:
        """Close a dispatch's launch → first-read interval (called from
        the first `_BatchOut` resolver, which entered `resolve()` at
        `entered` and found the outputs `was_ready` or not) and fold the
        device→host fetch into its transfer totals. No-op for evicted
        records."""
        end = time.monotonic() if end is None else end
        reg = self.registry
        kms = None
        parts = {}
        with self._cv:
            rec = self._find_locked(seq)
            if rec is None:
                return
            if rec["kernel_end"] is None:
                rec["kernel_end"] = end
                rec["entered_at"] = entered
                rec["was_ready"] = was_ready
                if rec["held_at"] is not None \
                        and rec["released_at"] is None:
                    # a fully rolled-back speculation: read by its
                    # certifier, released to nobody
                    rec["released_at"] = entered
                kms = max(end - rec["kernel_start"], 0.0) * 1e3
                parts = _read_parts(rec)
            rec["transfer_bytes"] += int(fetch_bytes)
            rec["transfer_count"] += int(fetch_count)
            self._finalize_locked(seq + 1)
            self._cv.notify_all()
        if reg is not None:
            if kms is not None:
                reg.add_sample("pipeline.kernel_ms", kms)
            for name, v in parts.items():
                if v is not None:
                    reg.add_sample("pipeline." + name, v)
            if fetch_bytes or fetch_count:
                reg.inc("pipeline.transfer_bytes", fetch_bytes)
                reg.inc("pipeline.transfer_count", fetch_count)

    def spec_resolve(self, seq: int, outcome: str,
                     wasted_frac: Optional[float] = None) -> None:
        """Certification verdict for a speculative dispatch record:
        "certified" (results adopted — the overlap it bought is real)
        or "rolled_back" with `wasted_frac` = the rolled-back share of
        its programs (1.0 when omitted). The wasted share of the kernel
        is summed into the summary's `spec.wasted_kernel_ms`; a FULLY
        rolled-back record leaves the overlap/bubble aggregates (its
        kernel hid nothing useful), a partial one stays — its certified
        slices made the kernel's overlap real work. Resolution happens
        BEFORE any successor record commits (the coordinator certifies
        before it offers the next launch), so successor finalization
        sees the verdict. No-op for evicted records."""
        reg = self.registry
        with self._cv:
            rec = self._find_locked(seq)
            if rec is None:
                return
            rec["spec_outcome"] = outcome
            frac = 0.0
            if outcome == "rolled_back":
                frac = 1.0 if wasted_frac is None else \
                    min(max(float(wasted_frac), 0.0), 1.0)
            rec["spec_wasted_frac"] = frac
            if frac >= 1.0 and rec["overlap_ms"] is not None:
                # its own host-side prep hid under the predecessor's
                # kernel, but it produced nothing adopted — that hiding
                # bought nothing
                rec["overlap_ms"] = 0.0
            self._cv.notify_all()
        if reg is not None:
            reg.inc("pipeline.spec_certified"
                    if outcome == "certified"
                    else "pipeline.spec_rolled_back")

    def _find_locked(self, seq: int) -> Optional[dict]:
        # recent seqs live at the right end; scan backwards
        for rec in reversed(self._ring):
            if rec["seq"] == seq:
                return rec
            if rec["seq"] < seq:
                break
        return None

    def _finalize_locked(self, seq: int) -> None:
        """Fill overlap/bubble for the record with this seq, once its
        PREDECESSOR's kernel interval is complete. Only one record can
        become finalizable per event — the newly committed one (its
        predecessor may already be done) or the successor of the kernel
        that just ended — so callers pass that seq instead of this
        method rescanning the ring under the long-poll lock on every
        dispatch. Whichever of commit()/kernel_end() arrives second
        computes. Overlap intersects the record's WHOLE pre-kernel host
        interval (pack start → view end, upload included) with the
        predecessor's kernel — the honest "how much host work did the
        in-flight kernel hide" number."""
        rec = self._find_locked(seq)
        if rec is None or rec["overlap_ms"] is not None:
            return
        prev = self._find_locked(seq - 1)
        if prev is None or prev["kernel_end"] is None:
            return
        overlap = (min(rec["view_end"], prev["kernel_end"])
                   - max(rec["pack_start"], prev["kernel_start"]))
        if prev.get("spec_outcome") == "rolled_back" \
                and prev.get("spec_wasted_frac", 1.0) >= 1.0:
            # host work hidden under a FULLY wasted kernel is not a
            # pipelining win — the attribution stays honest
            overlap = 0.0
        rec["overlap_ms"] = round(max(overlap, 0.0) * 1e3, 3)
        rec["bubble_ms"] = round(max(
            rec["kernel_start"] - prev["kernel_end"], 0.0) * 1e3, 3)
        # the predecessor's outputs had landed before anybody read
        # them: its kernel ended at some unknown earlier instant
        rec["bounds_only"] = prev["was_ready"] is True
        if self.registry is None:
            return
        if rec["bounds_only"]:
            self.registry.inc("pipeline.kernel_end_unknown")
        else:
            self.registry.add_sample("pipeline.overlap_ms",
                                     rec["overlap_ms"])
            self.registry.add_sample("pipeline.bubble_ms",
                                     rec["bubble_ms"])

    # -- querying --

    def _export(self, rec: dict) -> dict:
        a = self.mono_anchor
        ms = _ms
        return {
            "seq": rec["seq"], "programs": rec["programs"],
            "batched": rec["batched"],
            "start_s": round(rec["pack_start"] - a, 6),
            # wall-clock stamp (monotonic delta on the wall anchor, the
            # lib/trace.py anchor_unix idiom) so records correlate with
            # external logs without knowing the process anchor
            "start_unix": round(
                self.wall_anchor + (rec["pack_start"] - a), 3),
            "pack_ms": ms(rec["pack_start"], rec["pack_end"]),
            "upload_ms": ms(rec["upload_start"], rec["upload_end"]),
            "view_ms": ms(rec["view_start"], rec["view_end"]),
            "kernel_ms": ms(rec["kernel_start"], rec["kernel_end"]),
            **_read_parts(rec),
            "was_ready": rec["was_ready"],
            "overlap_ms": rec["overlap_ms"],
            "bubble_ms": rec["bubble_ms"],
            "bounds_only": rec["bounds_only"],
            "speculative": rec.get("speculative", False),
            "spec_outcome": rec.get("spec_outcome"),
            "spec_wasted_frac": rec.get("spec_wasted_frac"),
            "transfer_bytes": rec["transfer_bytes"],
            "transfer_count": rec["transfer_count"],
            # pre-kernel host side total; with kernel_ms and bubble_ms
            # this accounts the dispatch's wall time phase-complete
            "host_ms": ms(rec["pack_start"], rec["view_end"]),
        }

    def records_after(self, index: int,
                      timeout: float = 0.0) -> Tuple[int, List[dict]]:
        """Records with seq > `index`; blocks up to `timeout` when none
        are ready (the /v1/event/stream long-poll half)."""
        deadline = time.time() + timeout
        while True:
            with self._cv:
                out = [self._export(r) for r in self._ring
                       if r["seq"] > index]
                if out or timeout <= 0:
                    return self._seq, out
                remaining = deadline - time.time()
                if remaining <= 0:
                    return self._seq, []
                self._cv.wait(min(remaining, 1.0))

    def last_index(self) -> int:
        with self._cv:
            return self._seq

    def summary(self) -> Dict[str, object]:
        """Aggregate view over the retained ring (the /v1/metrics
        `pipeline` section): dispatch count, overlap_pct (overlap as a
        share of pre-kernel host time, over records that HAVE a
        predecessor), bubble/kernel totals, per-dispatch transfer
        means."""
        with self._cv:
            recs = [self._export(r) for r in self._ring]
            seq = self._seq
        n = len(recs)
        # rolled-back speculative work is wasted device time: each
        # record's kernel contributes its ROLLED SHARE to the wasted
        # sum, and only FULLY rolled-back records leave the
        # overlap/bubble aggregates (a partially certified dispatch's
        # kernel did real work)
        def _frac(r):
            f = r["spec_wasted_frac"]
            return 1.0 if f is None else f

        rolled = [r for r in recs if r["spec_outcome"] == "rolled_back"]
        paired = [r for r in recs if r["overlap_ms"] is not None
                  and not r["bounds_only"]
                  and not (r["spec_outcome"] == "rolled_back"
                           and _frac(r) >= 1.0)]
        pack_ms = sum(r["host_ms"] or 0.0 for r in paired)
        overlap = sum(r["overlap_ms"] for r in paired)
        bubble = sum(r["bubble_ms"] for r in paired)
        kernel = [r["kernel_ms"] for r in recs
                  if r["kernel_ms"] is not None]
        spec = {
            "launched": sum(1 for r in recs if r["speculative"]),
            "certified": sum(1 for r in recs
                             if r["spec_outcome"] == "certified"),
            "rolled_back": len(rolled),
            "wasted_kernel_ms": round(
                sum((r["kernel_ms"] or 0.0) * _frac(r)
                    for r in rolled), 3),
        }
        return {
            "last_seq": seq,
            "dispatches": n,
            "kernel_end_unknown": sum(1 for r in recs
                                      if r["bounds_only"]),
            "spec": spec,
            "overlap_pct": round(100.0 * overlap / pack_ms, 2)
            if pack_ms else 0.0,
            "overlap_ms_total": round(overlap, 3),
            "bubble_ms_total": round(bubble, 3),
            "bubble_ms_mean": round(bubble / len(paired), 3)
            if paired else 0.0,
            "kernel_ms_mean": round(sum(kernel) / len(kernel), 3)
            if kernel else 0.0,
            "transfer_bytes_per_dispatch": round(
                sum(r["transfer_bytes"] for r in recs) / n, 1)
            if n else 0.0,
            "transfer_count_per_dispatch": round(
                sum(r["transfer_count"] for r in recs) / n, 1)
            if n else 0.0,
        }
