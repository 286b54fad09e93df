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
    span into a running profile.

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
