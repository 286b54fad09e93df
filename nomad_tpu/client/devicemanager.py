"""Client device manager — device fingerprint + stats streams.

Behavioral reference: `client/devicemanager/manager.go:1` (plugin
instance ownership, fingerprint stream feeding node updates, stats
collection) and `plugins/device/device.go:1` (DevicePlugin contract:
Fingerprint / Reserve / Stats). The reference runs each device plugin
as a separate process streaming over gRPC; here plugins are in-process
objects with the same three-method contract, and the "streams" are the
manager's poll loops:

- **fingerprint loop** (slow cadence): re-detects device groups and
  instance health; on any change the client rewrites the node's device
  groups and re-registers, so the scheduler stops placing device asks
  onto vanished/unhealthy instances (manager.go fingerprint →
  UpdateNodeFromDevices).
- **stats loop** (fast cadence): collects per-instance stats, cached in
  the manager; the client attaches the latest map to every heartbeat
  and the servers surface it on `/v1/node/<id>` (live, not raft-logged
  — stats are ephemeral telemetry, like the reference's client stats
  endpoint).

The TPU plugin reads devices the way `fingerprint.py` does (in-process
where this agent schedules and so holds the chip, a bounded child probe
in a client-only agent); a probe failure AFTER devices were seen flips
the instances unhealthy, with the reason, instead of silently dropping
the group.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Callable, Dict, List, Optional

from ..lib.metrics import ErrorStreak
from ..structs.resources import NodeDeviceInstance, NodeDeviceResource


def parse_fake_devices(spec: str) -> List[NodeDeviceResource]:
    """The ONE parser for NOMAD_TPU_FAKE_DEVICES ("vendor/type/name:count
    [,...]") — shared by the registration-time fingerprinter
    (fingerprint.py device_env_fingerprint) and EnvDevicePlugin, so the
    two can never disagree on group shape or instance ids."""
    groups: List[NodeDeviceResource] = []
    for part in filter(None, (p.strip() for p in spec.split(","))):
        if ":" not in part:
            continue
        ident, _, cnt = part.rpartition(":")
        bits = ident.split("/")
        try:
            count = int(cnt)
        except ValueError:
            continue
        if len(bits) != 3 or count <= 0:
            continue
        groups.append(NodeDeviceResource(
            vendor=bits[0], type=bits[1], name=bits[2],
            instances=[NodeDeviceInstance(id=f"{ident}-{i}", healthy=True)
                       for i in range(count)]))
    return groups


def reservation_env(vendor: str, typ: str,
                    instance_ids: List[str]) -> Dict[str, str]:
    """Visibility env for an assigned device group — the single source
    of truth consumed by taskenv (device.go Reserve →
    ContainerReservation; the NVIDIA_VISIBLE_DEVICES analog per
    family)."""
    if vendor == "google" and typ == "tpu":
        return TpuDevicePlugin().reserve(instance_ids)
    return {}


class DevicePlugin:
    """The plugins/device/device.go contract, in-process."""

    name = "device"

    def fingerprint(self) -> List[NodeDeviceResource]:
        """Detect device groups (instances + attributes)."""
        raise NotImplementedError

    def stats(self) -> Dict[str, Dict[str, dict]]:
        """{group_id: {instance_id: {...}}} for this plugin's devices —
        group-keyed so the manager never has to re-fingerprint just to
        map instances back to groups."""
        raise NotImplementedError

    def reserve(self, instance_ids: List[str]) -> Dict[str, str]:
        """Env needed by a task to see exactly these instances
        (device.go Reserve → ContainerReservation)."""
        return {}


class TpuDevicePlugin(DevicePlugin):
    """TPU chips via the JAX runtime (the nvidia/NVML plugin analog,
    devices/gpu/nvidia/). Detection is `fingerprint.accelerator_devices`;
    stats report health + probe latency (the runtime exposes no per-chip
    utilization counters off-device)."""

    name = "tpu"

    def __init__(self) -> None:
        self._last_probe_ms: float = 0.0
        self._last_ok: float = 0.0
        self._seen: List[NodeDeviceResource] = []

    def fingerprint(self) -> List[NodeDeviceResource]:
        from .fingerprint import accelerator_devices, tpu_device_group

        t0 = time.time()
        devs, why = accelerator_devices()
        self._last_probe_ms = (time.time() - t0) * 1e3
        if devs:
            self._last_ok = time.time()
            self._seen = [tpu_device_group(devs)]
            return self._seen
        if self._seen:
            # devices were here and the probe now finds none: report
            # them unhealthy with the reason, don't vanish. Stored back
            # into _seen so the stats stream agrees with the
            # fingerprinted health instead of advertising stale healthy.
            sick = []
            for g in self._seen:
                sick.append(NodeDeviceResource(
                    vendor=g.vendor, type=g.type, name=g.name,
                    instances=[NodeDeviceInstance(id=i.id, healthy=False)
                               for i in g.instances],
                    attributes={**g.attributes,
                                "health_description":
                                    f"probe failed: {why}"},
                ))
            self._seen = sick
            return sick
        return []

    def stats(self) -> Dict[str, Dict[str, dict]]:
        out: Dict[str, Dict[str, dict]] = {}
        for g in self._seen:
            out[g.id()] = {inst.id: {
                "healthy": inst.healthy,
                "probe_ms": round(self._last_probe_ms, 1),
                "last_ok_unix": round(self._last_ok, 1),
            } for inst in g.instances}
        return out

    def reserve(self, instance_ids: List[str]) -> Dict[str, str]:
        ids = ",".join(instance_ids)
        # the TPU runtime's visibility contract (the NVIDIA_VISIBLE_
        # DEVICES analog for libtpu-backed processes)
        return {"TPU_VISIBLE_CHIPS": ids, "TPU_VISIBLE_DEVICES": ids}


class EnvDevicePlugin(DevicePlugin):
    """Declarative device groups from NOMAD_TPU_FAKE_DEVICES — the
    test/dev stand-in for out-of-process plugins. Format:
    "vendor/type/name:count[,...]". Stats are synthetic but live (they
    change every collection, proving the stream end-to-end)."""

    name = "env"

    def fingerprint(self) -> List[NodeDeviceResource]:
        return parse_fake_devices(
            os.environ.get("NOMAD_TPU_FAKE_DEVICES", ""))

    def stats(self) -> Dict[str, Dict[str, dict]]:
        out: Dict[str, Dict[str, dict]] = {}
        for g in self.fingerprint():
            out[g.id()] = {inst.id: {
                "healthy": True,
                "collected_unix": round(time.time(), 1),
            } for inst in g.instances}
        return out


class RemoteDevicePlugin(DevicePlugin):
    """Proxy running a device plugin in its own process
    (plugins/device_host.py over the plugins/base.py transport — the
    `plugins/device/device.go` per-process model). Supervised: any RPC
    failure relaunches the host; a crashing probe costs a plugin restart,
    never the agent. While the host is down, fingerprint() degrades the
    same way TpuDevicePlugin does on probe failure: last-seen devices
    flip unhealthy instead of vanishing."""

    def __init__(self, name: str, state_dir: str = "") -> None:
        self.name = name
        self.state_dir = state_dir
        self._client = None
        self._lock = threading.Lock()
        self._closed = False
        self._seen: List[NodeDeviceResource] = []

    def _ensure(self):
        import sys

        from ..plugins.base import launch_plugin

        with self._lock:
            if self._closed:
                # a stats/fingerprint call racing (or following) close()
                # must not relaunch the host as an unkillable orphan
                raise RuntimeError(f"device plugin {self.name} closed")
            if self._client is not None and self._client.alive():
                return self._client
            if self._client is not None:
                self._client.close()
            log_path = ""
            if self.state_dir:
                os.makedirs(self.state_dir, exist_ok=True)
                log_path = os.path.join(self.state_dir,
                                        f"device_{self.name}.log")
            self._client = launch_plugin(
                [sys.executable, "-m", "nomad_tpu.plugins.device_host",
                 self.name], log_path=log_path)
            return self._client

    def fingerprint(self) -> List[NodeDeviceResource]:
        from ..plugins.device_host import groups_from_wire

        try:
            wire = self._ensure().call("Device.fingerprint", timeout=30.0)
        except Exception:  # noqa: BLE001 — host down: degrade, relaunch
            # next pass
            if not self._seen:
                return []
            sick = [NodeDeviceResource(
                vendor=g.vendor, type=g.type, name=g.name,
                instances=[NodeDeviceInstance(id=i.id, healthy=False)
                           for i in g.instances],
                attributes={**g.attributes,
                            "health_description": "device plugin down"},
            ) for g in self._seen]
            self._seen = sick
            return sick
        groups = groups_from_wire(wire)
        if groups:
            self._seen = groups
        return groups

    def stats(self) -> Dict[str, Dict[str, dict]]:
        try:
            return self._ensure().call("Device.stats", timeout=15.0) or {}
        except Exception:  # noqa: BLE001 — stats are best-effort
            return {}

    def reserve(self, instance_ids: List[str]) -> Dict[str, str]:
        return self._ensure().call("Device.reserve", list(instance_ids),
                                   timeout=15.0) or {}

    def close(self, kill_plugin: bool = True) -> None:
        with self._lock:
            self._closed = True
            client, self._client = self._client, None
        if client is None:
            return
        if kill_plugin:
            try:
                client.call("Device.shutdown", timeout=5.0)
            except Exception:  # noqa: BLE001 — force below
                pass
            client.kill()
        else:
            client.close()


class DeviceManager:
    """devicemanager/manager.go analog: owns the plugins, runs the
    fingerprint + stats loops, feeds the client."""

    def __init__(self,
                 on_devices: Optional[
                     Callable[[List[NodeDeviceResource]], None]] = None,
                 fingerprint_interval: float = 60.0,
                 stats_interval: float = 5.0,
                 plugins: Optional[List[DevicePlugin]] = None,
                 state_dir: str = "") -> None:
        self.on_devices = on_devices
        self.fingerprint_interval = fingerprint_interval
        self.stats_interval = stats_interval
        #: where out-of-process device-host logs live
        self.state_dir = state_dir
        #: None → the builtin set, chosen at first use (see `plugins`)
        self._plugins = plugins
        self._lock = threading.Lock()
        #: {"vendor/type/name": {instance_id: {..stats..}}}
        self._stats: Dict[str, Dict[str, dict]] = {}
        self._last_groups: Dict[str, list] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        #: loop-failure sink: registry counter + first-of-streak WARNING
        #: (a wedged manager loop must leave a visible trace)
        self._errs = ErrorStreak("client.devicemanager")

    @property
    def plugins(self) -> List[DevicePlugin]:
        """Built at first use, not at construction: the client is
        constructed before `Server.start` takes the device, and whether
        this process holds it decides where the tpu plugin may run."""
        with self._lock:
            if self._plugins is None:
                self._plugins = self._builtin()
            return self._plugins

    def _builtin(self) -> List[DevicePlugin]:
        from ..lib import backend
        from ..plugins.base import oop_requested

        def mk(name: str, cls) -> DevicePlugin:
            # out-of-process opt-in (plugins/device_host.py): the
            # reference runs every device plugin external; here it's an
            # explicit knob like NOMAD_TPU_OOP_DRIVERS
            if oop_requested("NOMAD_TPU_OOP_DEVICES", name):
                return RemoteDevicePlugin(name, state_dir=self.state_dir)
            return cls()

        plugins: List[DevicePlugin] = [mk("env", EnvDevicePlugin)]
        if not os.environ.get("NOMAD_TPU_SKIP_TPU_FINGERPRINT"):
            # one process per chip: where this process holds the device
            # a plugin host would only find it taken — read in-process
            plugins.append(mk("tpu", TpuDevicePlugin)
                           if backend.resolved() is None
                           else TpuDevicePlugin())
        return plugins

    def seed(self, groups: List[NodeDeviceResource]) -> None:
        """Adopt an externally-fingerprinted device set as the baseline
        (registration-time fingerprint.py results) so the first loop
        pass only reports REAL changes."""
        with self._lock:
            self._last_groups = {
                g.id(): sorted((i.id, i.healthy) for i in g.instances)
                for g in groups}

    # ---- fingerprint stream ----

    def _detect(self):
        """(groups, shape, changed) WITHOUT committing the shape — the
        loop commits only after the node update succeeds, so a transient
        registration failure can't eat a device transition forever."""
        groups: List[NodeDeviceResource] = []
        for p in self.plugins:
            try:
                groups.extend(p.fingerprint())
            except Exception as e:  # noqa: BLE001 — a broken plugin
                # loses only its own devices
                self._errs.record(e, f"fingerprint({p.name})")
                continue
        shape = {
            g.id(): sorted((i.id, i.healthy) for i in g.instances)
            for g in groups}
        with self._lock:
            changed = shape != self._last_groups
        return groups, shape, changed

    def _commit(self, shape: Dict[str, list]) -> None:
        with self._lock:
            self._last_groups = shape

    def fingerprint_once(self) -> Optional[List[NodeDeviceResource]]:
        """Collect groups from every plugin; returns the full set when
        ANYTHING changed since last time (committing the new baseline),
        else None."""
        groups, shape, changed = self._detect()
        self._commit(shape)
        return groups if changed else None

    # ---- stats stream ----

    def collect_stats(self) -> Dict[str, Dict[str, dict]]:
        stats: Dict[str, Dict[str, dict]] = {}
        failed = 0
        for p in self.plugins:
            try:
                stats.update(p.stats())
            except Exception as e:  # noqa: BLE001 — a broken plugin
                # loses only its own stats
                self._errs.record(e, f"stats({p.name})")
                failed += 1
        if not failed:
            # only a fully-clean pass re-arms the first-of-streak
            # WARNING — a persistently broken plugin must not log one
            # line per stats interval
            self._errs.ok()
        with self._lock:
            self._stats = stats
        return stats

    def latest_stats(self) -> Dict[str, Dict[str, dict]]:
        """Most recent stats map — attached to every client heartbeat."""
        with self._lock:
            return dict(self._stats)

    # ---- loops ----

    def start(self) -> None:
        self._thread = threading.Thread(target=self._loop,
                                        name="device-manager", daemon=True)
        self._thread.start()

    def _loop(self) -> None:
        next_fp = time.time() + self.fingerprint_interval
        while not self._stop.wait(self.stats_interval):
            try:
                # collect_stats manages the streak itself (per-plugin
                # record + ok only on a fully-clean pass)
                self.collect_stats()
            except Exception as e:  # noqa: BLE001
                self._errs.record(e, "stats pass")
            if time.time() >= next_fp:
                next_fp = time.time() + self.fingerprint_interval
                try:
                    groups, shape, changed = self._detect()
                except Exception as e:  # noqa: BLE001
                    self._errs.record(e, "fingerprint pass")
                    continue
                if not changed:
                    continue
                if self.on_devices is None:
                    self._commit(shape)
                    continue
                try:
                    self.on_devices(groups)
                except Exception as e:  # noqa: BLE001 — node update
                    # failed: do NOT commit; the next pass re-reports
                    # the change
                    self._errs.record(e, "on_devices node update")
                    continue
                self._commit(shape)

    def shutdown(self) -> None:
        self._stop.set()
        for p in self._plugins or []:
            close = getattr(p, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:  # noqa: BLE001 — best-effort
                    pass
