"""Out-of-process device plugin host.

Behavioral reference: `plugins/device/device.go` (DevicePlugin gRPC
contract: Fingerprint / Reserve / Stats) + `plugins/base/plugin.go`
(per-plugin process). The reference streams fingerprints and stats from
a separate plugin process over gRPC; this host is that process: it
instantiates ONE device plugin (builtin by name, or a third-party
`module:Class` path) and serves the three-method contract over the
msgpack-RPC plugin transport. The client-side proxy
(`client/devicemanager.py` RemoteDevicePlugin) supervises it — a
crashing device probe costs a plugin relaunch, never the agent. The
`tpu` plugin is hosted here only by a client-only agent: an agent that
schedules holds the chip itself and reads it in-process
(`DeviceManager._builtin`), because a host started from under it would
find the device taken.

Launch: ``python -m nomad_tpu.plugins.device_host <name>``.
"""
from __future__ import annotations

import sys
import threading
from typing import Dict, List


def groups_to_wire(groups) -> List[dict]:
    return [{
        "vendor": g.vendor, "type": g.type, "name": g.name,
        "attributes": dict(g.attributes or {}),
        "instances": [{"id": i.id, "healthy": i.healthy,
                       "locality": i.locality} for i in g.instances],
    } for g in groups]


def groups_from_wire(wire) -> list:
    from ..structs.resources import NodeDeviceInstance, NodeDeviceResource

    return [NodeDeviceResource(
        vendor=g.get("vendor", ""), type=g.get("type", ""),
        name=g.get("name", ""),
        attributes=dict(g.get("attributes") or {}),
        instances=[NodeDeviceInstance(
            id=i.get("id", ""), healthy=bool(i.get("healthy", True)),
            locality=i.get("locality", ""))
            for i in g.get("instances") or []],
    ) for g in wire or []]


class DeviceHost:
    """RPC endpoint wrapping one live device plugin instance."""

    def __init__(self, plugin) -> None:
        self.plugin = plugin

    def fingerprint(self) -> List[dict]:
        return groups_to_wire(self.plugin.fingerprint())

    def stats(self) -> Dict[str, Dict[str, dict]]:
        return self.plugin.stats()

    def reserve(self, instance_ids: List[str]) -> Dict[str, str]:
        return self.plugin.reserve(list(instance_ids or []))


def make_device_plugin(name: str):
    if ":" in name:
        import importlib

        mod, _, cls_name = name.partition(":")
        return getattr(importlib.import_module(mod), cls_name)()
    from ..client.devicemanager import EnvDevicePlugin, TpuDevicePlugin

    builtin = {"tpu": TpuDevicePlugin, "env": EnvDevicePlugin}
    cls = builtin.get(name)
    if cls is None:
        raise ValueError(f"unknown device plugin {name!r}")
    return cls()


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python -m nomad_tpu.plugins.device_host <plugin>",
              file=sys.stderr)
        raise SystemExit(2)
    host = DeviceHost(make_device_plugin(argv[0]))

    from .base import serve_plugin

    def register(server) -> None:
        server._plugin_stop = threading.Event()
        server.register_endpoint("Device", host)

        def shutdown() -> bool:
            server._plugin_stop.set()
            return True

        server.register("Device.shutdown", shutdown)

    serve_plugin(f"device:{argv[0]}", register)


if __name__ == "__main__":
    main()
