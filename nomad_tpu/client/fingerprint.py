"""Host fingerprinting — populate Node attributes/resources.

Behavioral reference: `client/fingerprint/` (~20 fingerprinters composed
by `fingerprint_manager.go:16,34`): arch, cpu, memory, storage, host,
nomad, signal — plus the TPU-native replacement for the reference's
NVML GPU fingerprinter (`devices/gpu/nvidia/`): `TPUFingerprint`
publishes `tpu.count`/`tpu.type` from the JAX runtime, gated so hosts
without an accelerator fingerprint cleanly.
"""
from __future__ import annotations

import os
import platform
import shutil
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

from ..structs import Node
from ..structs.resources import NodeResources


def arch_fingerprint(node: Node) -> None:
    node.attributes["cpu.arch"] = platform.machine()


def os_fingerprint(node: Node) -> None:
    node.attributes["kernel.name"] = platform.system().lower()
    node.attributes["kernel.version"] = platform.release()
    node.attributes["os.name"] = platform.system().lower()
    node.attributes["os.version"] = platform.version()


def cpu_fingerprint(node: Node) -> None:
    cores = os.cpu_count() or 1
    node.attributes["cpu.numcores"] = str(cores)
    mhz = 1000.0
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.lower().startswith("cpu mhz"):
                    mhz = float(line.split(":")[1])
                    break
    except (OSError, ValueError):
        pass
    node.attributes["cpu.frequency"] = str(int(mhz))
    total = int(cores * mhz)
    node.attributes["cpu.totalcompute"] = str(total)
    if node.node_resources.cpu == 0:
        node.node_resources.cpu = total


def memory_fingerprint(node: Node) -> None:
    total_mb = 1024
    try:
        with open("/proc/meminfo") as fh:
            for line in fh:
                if line.startswith("MemTotal:"):
                    total_mb = int(line.split()[1]) // 1024
                    break
    except (OSError, ValueError):
        pass
    node.attributes["memory.totalbytes"] = str(total_mb * 1024 * 1024)
    if node.node_resources.memory_mb == 0:
        node.node_resources.memory_mb = total_mb


def storage_fingerprint(node: Node) -> None:
    try:
        usage = shutil.disk_usage("/")
        free_mb = usage.free // (1024 * 1024)
    except OSError:
        free_mb = 1024
    node.attributes["unique.storage.bytesfree"] = str(free_mb * 1024 * 1024)
    if node.node_resources.disk_mb == 0:
        node.node_resources.disk_mb = free_mb


def network_fingerprint(node: Node) -> None:
    """Default-interface detection (client/fingerprint/network.go): pick a
    routable IP and publish a 1000-mbit link (speed detection is sysfs-
    specific; the reference also defaults when unknown)."""
    from ..lib.netutil import routable_ip
    from ..structs.network import NetworkResource

    ip = routable_ip()
    node.attributes["unique.network.ip-address"] = ip
    if not node.node_resources.networks:
        node.node_resources.networks = [NetworkResource(
            device="eth0", cidr=f"{ip}/32", ip=ip, mbits=1000)]


def host_fingerprint(node: Node) -> None:
    node.attributes["unique.hostname"] = platform.node()
    if not node.name:
        node.name = platform.node()


def nomad_fingerprint(node: Node) -> None:
    from .. import __version__

    node.attributes["nomad.version"] = __version__


def signal_fingerprint(node: Node) -> None:
    import signal as sig

    names = sorted(s.name for s in sig.Signals
                   if s.name.startswith("SIG") and "_" not in s.name)
    node.attributes["os.signals"] = ",".join(names)


class AcceleratorDevice(NamedTuple):
    """One accelerator as `jax.devices()` reports it."""

    id: str
    platform: str
    device_kind: str


def accelerator_devices() -> Tuple[List[AcceleratorDevice], str]:
    """(non-CPU devices JAX sees on this host, reason when there are none).

    One process per chip: an agent that schedules already holds the
    device (`lib/backend.py resolve()`, taken at `Server.start`), so it
    asks its own backend in-process, live on every call
    (`held_devices_silent()`) — a child probing from under it either
    finds the chip taken or, started first, takes it away from the
    scheduler. Only a process that never touches JAX (a client-only
    agent, which must leave the chip to the tasks it runs) probes in a
    bounded child."""
    from ..lib import backend

    if os.environ.get("NOMAD_TPU_SKIP_TPU_FINGERPRINT"):
        return [], "NOMAD_TPU_SKIP_TPU_FINGERPRINT is set"
    held = backend.resolved()
    if held is not None:
        if held.platform == "cpu":
            return [], "this process runs on the cpu platform"
        silent = backend.held_devices_silent()
        if silent:
            return [], silent
        return [AcceleratorDevice(i, held.platform, held.device_kind)
                for i in held.device_ids], ""
    if backend.cpu_requested():
        return [], "JAX_PLATFORMS=cpu"
    import json as _json
    import subprocess
    import sys as _sys

    try:
        budget = float(os.environ.get("NOMAD_TPU_FINGERPRINT_TIMEOUT",
                                      "30"))
    except ValueError:
        budget = 30.0
    if budget <= 0:
        budget = 30.0
    script = (
        "import jax, json; print(json.dumps("
        "[{'id': str(d.id), 'platform': d.platform, "
        "'kind': str(d.device_kind)} for d in jax.devices()]))"
    )
    try:
        r = subprocess.run([_sys.executable, "-c", script],
                           capture_output=True, timeout=budget)
    except subprocess.TimeoutExpired:
        return [], f"device probe timed out after {budget:.0f}s"
    except OSError as e:
        return [], f"device probe could not start: {e}"
    if r.returncode != 0:
        err = r.stderr.decode(errors="replace").strip().splitlines()
        return [], (f"device probe exited rc={r.returncode}: "
                    f"{err[-1] if err else 'no output'}")
    try:
        rows = _json.loads(r.stdout.decode().strip().splitlines()[-1])
    except (ValueError, IndexError):
        return [], "device probe printed no device list"
    devs = [AcceleratorDevice(d["id"], d["platform"], d["kind"])
            for d in rows if d.get("platform") != "cpu"]
    return devs, ("" if devs else "device probe found the cpu platform only")


def tpu_device_group(devs: List[AcceleratorDevice]):
    """The schedulable `google/tpu` device group for these chips (the
    device-plugin fingerprint stream analog, plugins/device/device.go
    Fingerprint + devices/gpu/nvidia/nvml/client.go:52-78) — jobs ask
    device "google/tpu" { count = N } and get instance IDs assigned."""
    from ..structs.resources import NodeDeviceInstance, NodeDeviceResource

    kind = devs[0].device_kind
    return NodeDeviceResource(
        vendor="google", type="tpu", name=kind.lower().replace(" ", "-"),
        instances=[NodeDeviceInstance(id=d.id, healthy=True) for d in devs],
        attributes={"kind": kind},
    )


def tpu_fingerprint(node: Node) -> None:
    """TPU detection via the JAX runtime (the reference's NVML analog,
    devices/gpu/nvidia/nvml/client.go:52-78). A host without an
    accelerator — or a probe that failed — leaves the node un-annotated,
    like any other fingerprint failure."""
    devs, _why = accelerator_devices()
    if not devs:
        return
    node.attributes["tpu.count"] = str(len(devs))
    node.attributes["tpu.type"] = devs[0].device_kind
    node.attributes["driver.tpu"] = "1"
    node.node_resources.devices = [
        d for d in node.node_resources.devices
        if not (d.vendor == "google" and d.type == "tpu")
    ] + [tpu_device_group(devs)]


def device_env_fingerprint(node: Node) -> None:
    """Declarative device groups from NOMAD_TPU_FAKE_DEVICES — the test/dev
    stand-in for out-of-process device plugins (plugins/device/device.go).
    Format: "vendor/type/name:count[,...]", e.g. "nvidia/gpu/1080ti:4"."""
    spec = os.environ.get("NOMAD_TPU_FAKE_DEVICES", "")
    if not spec:
        return
    from .devicemanager import parse_fake_devices

    for group in parse_fake_devices(spec):
        # re-run-safe: replace a previously-registered identical group
        node.node_resources.devices = [
            d for d in node.node_resources.devices
            if d.id() != group.id()
        ] + [group]


def cgroup_fingerprint(node: Node) -> None:
    """cgroup availability (client/fingerprint/cgroup_linux.go): version +
    mountpoint — the exec driver's isolation depends on it."""
    if os.path.isdir("/sys/fs/cgroup"):
        v2 = os.path.exists("/sys/fs/cgroup/cgroup.controllers")
        node.attributes["unique.cgroup.mountpoint"] = "/sys/fs/cgroup"
        node.attributes["unique.cgroup.version"] = "v2" if v2 else "v1"


def bridge_fingerprint(node: Node) -> None:
    """bridge kernel module (client/fingerprint/bridge_linux.go) — group
    network mode "bridge" feasibility."""
    try:
        with open("/proc/modules") as f:
            mods = f.read()
        if "\nbridge " in mods or mods.startswith("bridge "):
            node.attributes["nomad.bridge.hairpin_mode"] = "false"
            node.attributes["plugins.cni.version.bridge"] = "builtin"
    except OSError:
        pass


def cni_fingerprint(node: Node) -> None:
    """CNI plugin/config discovery (client/fingerprint/cni.go): scan the
    conf dir for network lists; names become plugins.cni.config.* attrs.
    Dir override via NOMAD_TPU_CNI_CONFIG_DIR (the agent config's
    cni_config_dir)."""
    import json as _json

    conf_dir = os.environ.get("NOMAD_TPU_CNI_CONFIG_DIR",
                              "/opt/cni/config")
    if not os.path.isdir(conf_dir):
        return
    for fn in sorted(os.listdir(conf_dir)):
        if not fn.endswith((".conflist", ".conf", ".json")):
            continue
        try:
            with open(os.path.join(conf_dir, fn)) as f:
                conf = _json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(conf, dict):
            continue  # valid JSON but not a network config
        name = conf.get("name") or fn.rsplit(".", 1)[0]
        node.attributes[f"plugins.cni.config.{name}"] = \
            os.path.join(conf_dir, fn)


def _cloud_metadata(url: str, headers: dict) -> Optional[str]:
    """One metadata read with the aggressive timeout the reference uses
    (cloud fingerprints must not stall registration off-cloud)."""
    import urllib.request

    req = urllib.request.Request(url, headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=0.5) as resp:
            return resp.read().decode().strip()
    except Exception:  # noqa: BLE001 — not on this cloud
        return None


def env_gce_fingerprint(node: Node) -> None:
    """GCE metadata (client/fingerprint/env_gce.go): machine attrs from
    the metadata service. Endpoint override via
    NOMAD_TPU_GCE_METADATA_URL (the reference honors GCE_METADATA_HOST);
    skipped entirely when neither the override nor a known-GCE marker is
    present, so bare-metal nodes never pay the probe."""
    base = os.environ.get("NOMAD_TPU_GCE_METADATA_URL", "")
    if not base:
        if not os.path.exists("/sys/class/dmi/id/product_name"):
            return
        try:
            with open("/sys/class/dmi/id/product_name") as f:
                if "Google" not in f.read():
                    return
        except OSError:
            return
        base = "http://169.254.169.254/computeMetadata/v1"
    hdr = {"Metadata-Flavor": "Google"}
    for attr, path in [("platform.gce.machine-type", "/machine-type"),
                       ("platform.gce.zone", "/zone"),
                       ("platform.gce.hostname", "/hostname"),
                       ("unique.platform.gce.id", "/id")]:
        v = _cloud_metadata(f"{base}/instance{path}", hdr)
        if v is None:
            return  # first miss → not on GCE; stop probing
        node.attributes[attr] = v.rsplit("/", 1)[-1]


def env_aws_fingerprint(node: Node) -> None:
    """EC2 metadata (client/fingerprint/env_aws.go). Endpoint override via
    NOMAD_TPU_AWS_METADATA_URL; gated on a DMI marker like GCE. Speaks
    IMDSv2 (session token) first — HttpTokens=required is the launch
    default on current EC2 — falling back to v1 plain GETs."""
    base = os.environ.get("NOMAD_TPU_AWS_METADATA_URL", "")
    root = ""
    if not base:
        marker = "/sys/class/dmi/id/board_vendor"
        try:
            with open(marker) as f:
                if "Amazon" not in f.read():
                    return
        except OSError:
            return
        root = "http://169.254.169.254"
        base = f"{root}/latest/meta-data"
    else:
        root = base.rsplit("/latest/", 1)[0] if "/latest/" in base else ""
    headers = {}
    if root:
        import urllib.request

        try:
            req = urllib.request.Request(
                f"{root}/latest/api/token", method="PUT",
                headers={"X-aws-ec2-metadata-token-ttl-seconds": "60"})
            with urllib.request.urlopen(req, timeout=0.5) as resp:
                headers = {"X-aws-ec2-metadata-token":
                           resp.read().decode().strip()}
        except Exception:  # noqa: BLE001 — IMDSv1 host: no token route
            pass
    for attr, path in [("platform.aws.instance-type", "/instance-type"),
                       ("platform.aws.placement.availability-zone",
                        "/placement/availability-zone"),
                       ("unique.platform.aws.instance-id", "/instance-id"),
                       ("unique.platform.aws.local-ipv4", "/local-ipv4")]:
        v = _cloud_metadata(f"{base}{path}", headers)
        if v is None:
            return
        node.attributes[attr] = v


def driver_fingerprints(node: Node) -> None:
    from .drivers import BUILTIN_DRIVERS

    for name, cls in BUILTIN_DRIVERS.items():
        try:
            node.attributes.update(cls().fingerprint())
        except Exception:
            pass


FINGERPRINTERS: List[Callable[[Node], None]] = [
    arch_fingerprint, os_fingerprint, cpu_fingerprint, memory_fingerprint,
    storage_fingerprint, network_fingerprint, host_fingerprint,
    nomad_fingerprint, signal_fingerprint, tpu_fingerprint,
    device_env_fingerprint, cgroup_fingerprint, bridge_fingerprint,
    cni_fingerprint, env_gce_fingerprint, env_aws_fingerprint,
    driver_fingerprints,
]


class FingerprintManager:
    """Runs every fingerprinter over the node (fingerprint_manager.go)."""

    def __init__(self, fingerprinters=None) -> None:
        self.fingerprinters = fingerprinters or FINGERPRINTERS

    def run(self, node: Node) -> Node:
        if node.node_resources is None:
            node.node_resources = NodeResources()
        for fp in self.fingerprinters:
            try:
                fp(node)
            except Exception:
                pass  # a broken fingerprinter never blocks registration
        node.compute_class()
        return node
