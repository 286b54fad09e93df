"""The process's JAX backend: which platform it runs on, and where its
compiled programs are cached.

An accelerator belongs to one process at a time. The process that
schedules states its platform once, at start (`resolve()` — called by
`Server.start`, `chip_smoke.py`, the benchmark's launcher and the driver
entry), and
everything else in that process that needs to know about the device
(`client/fingerprint.py`, `client/devicemanager.py`) asks `resolved()`
instead of starting a child that would want the same chip.

The platform is what the operator states: `JAX_PLATFORMS=cpu` means CPU,
explicitly (tests, rehearsals). Anything else requires an accelerator —
JAX itself warns and carries on on the CPU when it cannot have one, so
`resolve()` turns that into an error instead of a scheduler that serves
from the wrong device for the life of the process.
"""
from __future__ import annotations

import gc
import logging
import os
import threading
import time
from typing import List, NamedTuple, Optional, Tuple

log = logging.getLogger("nomad_tpu.backend")

#: <checkout>/.xla_cache — a FIXED path: the directory is part of the
#: cache key, so a cache that moves never hits
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".xla_cache")


class Backend(NamedTuple):
    """What `jax.devices()` reported when this process took the device."""

    platform: str                  # jax.devices()[0].platform
    device_kind: str               # jax.devices()[0].device_kind
    device_ids: Tuple[str, ...]    # str(d.id) for d in jax.devices()

    @property
    def count(self) -> int:
        return len(self.device_ids)


_resolved: Optional[Backend] = None


def cpu_requested() -> bool:
    """The one reader of "did the operator ask for the CPU platform"."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def resolved() -> Optional[Backend]:
    """The backend this process holds, or None if it never took one
    (a client-only agent, a plugin host). Never initialises JAX."""
    return _resolved


def held_devices_silent() -> str:
    """"" while every device this process holds still answers the
    runtime, else the reason. The in-process health probe: one
    `memory_stats()` call per device on the client this process already
    holds — no new backend, no child, cheap enough for every stats pass.
    (What a failing chip does to this call has not been seen here; an
    exception from the runtime is taken as "does not answer".)"""
    if _resolved is None:
        return "this process holds no device"
    import jax

    for d in jax.devices():
        try:
            d.memory_stats()
        except Exception as e:  # noqa: BLE001 — whatever the runtime raises
            return f"device {d.id} does not answer: {type(e).__name__}: {e}"
    return ""


def resolve() -> Backend:
    """Initialise the JAX backend — from here on this process holds the
    device — and return what it resolved to. Raises when JAX came up on
    the CPU without `JAX_PLATFORMS=cpu` having asked for it."""
    global _resolved
    if _resolved is not None:
        return _resolved
    import jax

    devs = jax.devices()
    b = Backend(platform=devs[0].platform,
                device_kind=str(devs[0].device_kind),
                device_ids=tuple(str(d.id) for d in devs))
    if b.platform == "cpu" and not cpu_requested():
        raise RuntimeError(
            "no accelerator: JAX resolved to the cpu platform and "
            "JAX_PLATFORMS=cpu was not given (is the chip held by another "
            "process?); set JAX_PLATFORMS=cpu to run on the CPU on purpose")
    log.info("jax backend: platform=%s device_kind=%s devices=%d jax=%s",
             b.platform, b.device_kind, b.count, jax.__version__)
    _resolved = b
    return b


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory
    in use. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself
    and nothing is set in code; otherwise the cache lives in
    `<checkout>/.xla_cache` (git-ignored). Call before the first compile
    (which `count_compiles` then counts)."""
    count_compiles()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


# ---- runtime counters (process registry: `runtime.*`) ----------------------

#: JAX's own duration events: a backend compile or a persistent-cache
#: load (the same event covers both), and tracing + lowering, which are
#: paid also where the backend compile is skipped
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_LOWER_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration")
_counting_compiles = False


def _on_jax_duration(event: str, duration: float, **_kw) -> None:
    from .metrics import default_registry

    if event == _COMPILE_EVENT:
        reg = default_registry()
        reg.inc("runtime.compiles")
        reg.inc("runtime.compile_ms", float(duration) * 1e3)
    elif event in _TRACE_LOWER_EVENTS:
        default_registry().inc("runtime.trace_lower_ms",
                               float(duration) * 1e3)


def count_compiles() -> None:
    """One `jax.monitoring` listener for the life of the process:
    `runtime.compiles`, `runtime.compile_ms` and `runtime.trace_lower_ms`
    (sums) in the process registry — what a warm-up cost, and whether
    anything compiled while serving."""
    global _counting_compiles
    if _counting_compiles:
        return
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
    _counting_compiles = True


class GcWatch:
    """`gc.callbacks` hook of a serving process: every collection's
    pause into the histogram `runtime.gc_pause_ms`, full (generation 2)
    collections into the counter `runtime.gc_full`, and a `nomad/gc`
    span into a running profile. It only watches the collector; what
    changes it is its counterpart `GcPolicy`, whose sweeps pass through
    this hook like any collection.

    A collection can start on a thread that holds an instrument's lock
    (a reader sorting the histogram's window allocates), so the hook
    holds its two instruments, never looks one up, and never waits for a
    lock: what it cannot record at once waits for the next collection."""

    def __init__(self, registry=None) -> None:
        from .metrics import default_registry

        reg = registry or default_registry()
        self._pause_ms = reg.histogram("runtime.gc_pause_ms")
        self._full = reg.counter("runtime.gc_full")
        self._open: Optional[tuple] = None
        self._pending: List[float] = []
        self._pending_full = 0

    def install(self) -> None:
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def remove(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        from .trace import host_span

        if phase == "start":
            span = host_span("gc")
            span.__enter__()
            self._open = (time.perf_counter(), span)
            return
        if self._open is None:
            return
        (t0, span), self._open = self._open, None
        span.__exit__(None, None, None)
        self._pending.append((time.perf_counter() - t0) * 1e3)
        if info.get("generation") == 2:
            self._pending_full += 1
        while self._pending and self._pause_ms.try_add(self._pending[-1]):
            self._pending.pop()
        if self._pending_full and self._full.try_inc(self._pending_full):
            self._pending_full = 0


#: The young generation of a process that serves as a Nomad server holds
#: the scheduler's unit of work: a 1,000-allocation plan is 20-40 thousand
#: tracked objects that survive every collection started while it is built,
#: and CPython's 700 starts ~35 of them per thousand allocations committed.
#: Chosen on the chip among (20,000, 10), (50,000, 4), (50,000, 10) and
#: (100,000, 2), side by side (PERF.md §6, PR 31): `c1m-5k.flood` spent
#: 14.2 / 13.4 / 13.5 / 11.8 % of its window in the collector (30.5 % under
#: CPython's defaults) at 4,845 / 4,772 / 4,763 / 4,800 allocs/s (3,897),
#: `c1m-5k.singles` 1.8 / 1.2 / - / 1.1 % (8.7 %) at 215 / 215 / - / 218
#: evals/s (201): the rates cannot tell the pairs apart, the collector's
#: share falls with the first threshold to the end of the range. What a
#: young pass walks is everything allocated since the last one that is
#: still alive — the count that starts it is net of what was freed — so a
#: larger generation lets more of a plan's scratch die unwalked.
GC_THRESHOLD0 = 100_000
#: young collections per generation-1 collection: a generation-1 pass walks
#: two or three young generations, 85 ms on average in `c1m-5k.flood`
GC_THRESHOLD1 = 2
#: generation 2's threshold, out of reach of its counter: what survives
#: generation 1 — the store's records, the tensor mirror's Python side,
#: compiled-program caches — is never walked on an allocation count
_GC_NEVER = 2 ** 31 - 1
#: A full sweep every so many periods of the server's GC ticker
#: (`ServerConfig.gc_interval`, 60 s: a sweep every 600 s). One sweep over
#: `c1m-5k.flood`'s store takes 2.4-2.5 s at the end of a run (7.2-7.4
#: million tracked objects) and 3.6 s at the end of a traced one (10.7
#: million): 0.4 % and 0.6 % of the period, where upstream Nomad's 300 s
#: (`EvalGCInterval` and its like) would make it 0.8 % and 1.2 %. The
#: sweeps found nothing in any run (PERF.md §5), so waiting longer for one
#: keeps no memory.
GC_SWEEP_TICKS = 10


class GcPolicy:
    """The collector's settings in a process that serves as a Nomad
    server (`Agent.start` installs one, `Agent.shutdown` removes it;
    `GcWatch` beside it watches what the collector then does).

    No automatic generation-2 collection: the long-lived store holds no
    cyclic garbage by construction (`Allocation` -> `Job`, never back), so
    walking it whenever enough objects were allocated finds nothing and
    stops every thread for its length. A young generation sized for the
    scheduler's unit of work (`GC_THRESHOLD0`). Full sweeps on the
    clock instead: `Server._run_gc_ticker` calls `tick()` when it has
    enqueued the core evals that delete evals, jobs and nodes — the moment
    garbage that only a full collection finds can appear — and every
    `GC_SWEEP_TICKS`-th tick sweeps: counter `runtime.gc_sweeps`,
    histogram `runtime.gc_sweep_ms`, and what the sweeps found in the
    counter `runtime.gc_sweep_collected`, which says whether anything
    cyclic reaches generation 2 at all.

    The thresholds belong to the process, so installs are counted: the
    first saves what stood before, the last removal puts it back."""

    _lock = threading.Lock()
    _installed = 0
    _before: Optional[Tuple[int, int, int]] = None

    def __init__(self, registry=None) -> None:
        from .metrics import default_registry

        reg = registry or default_registry()
        self._sweeps = reg.counter("runtime.gc_sweeps")
        self._sweep_ms = reg.histogram("runtime.gc_sweep_ms")
        self._collected = reg.counter("runtime.gc_sweep_collected")
        self._holds = False
        self._ticks = 0

    def install(self) -> None:
        with GcPolicy._lock:
            if self._holds:
                return
            self._holds = True
            if GcPolicy._installed == 0:
                GcPolicy._before = gc.get_threshold()
                gc.set_threshold(GC_THRESHOLD0, GC_THRESHOLD1, _GC_NEVER)
            GcPolicy._installed += 1

    def remove(self) -> None:
        with GcPolicy._lock:
            if not self._holds:
                return
            self._holds = False
            GcPolicy._installed -= 1
            if GcPolicy._installed == 0:
                gc.set_threshold(*GcPolicy._before)
                GcPolicy._before = None

    def tick(self) -> None:
        """One period of the server's GC ticker has passed."""
        self._ticks += 1
        if self._ticks % GC_SWEEP_TICKS == 0:
            self.sweep()

    def sweep(self) -> None:
        """One full collection, on the caller's thread."""
        t0 = time.perf_counter()
        found = gc.collect()
        self._sweep_ms.add((time.perf_counter() - t0) * 1e3)
        self._sweeps.inc()
        self._collected.inc(found)
