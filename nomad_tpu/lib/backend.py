"""The process's JAX backend: which platform it runs on, and where its
compiled programs are cached.

An accelerator belongs to one process at a time. The process that
schedules states its platform once, at start (`resolve()` — called by
`Server.start`, `bench.py`, `chip_smoke.py` and the driver entry), and
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

import logging
import os
from typing import NamedTuple, Optional, Tuple

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
    `<checkout>/.xla_cache` (git-ignored). Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
