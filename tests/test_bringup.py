"""Bring-up contracts (ISSUE 21): the platform is what the operator
states, one process per chip, the compile cache is placed from outside,
and the chip smoke / bench fail loudly where there is no chip. All on the
CPU — what these pin is the refusal to fall back, not the device."""
import json
import os
import shutil
import subprocess
import sys
import threading

import pytest

from nomad_tpu.lib import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, cwd=REPO, env=None, timeout=120):
    return subprocess.run([sys.executable] + argv, cwd=cwd, env=env,
                          capture_output=True, timeout=timeout)


def _env(**over):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    for k, v in over.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return env


class TestCompileCache:
    def test_env_set_writes_nothing(self, monkeypatch):
        import jax

        def boom(*a, **k):
            raise AssertionError(f"jax.config.update{a} with "
                                 "JAX_COMPILATION_CACHE_DIR set")

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
        monkeypatch.setattr(jax.config, "update", boom)
        assert backend.setup_compile_cache() == "/some/where"

    def test_unset_is_checkout_cache_stable(self, monkeypatch, tmp_path):
        import jax

        writes = []
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: writes.append((k, v)))
        want = os.path.join(REPO, ".xla_cache")
        assert backend.setup_compile_cache() == want
        assert backend.setup_compile_cache() == want
        assert writes == [("jax_compilation_cache_dir", want)] * 2
        # ...and from another process started somewhere else
        r = _run(["-c", "from nomad_tpu.lib.backend import "
                        "setup_compile_cache as s; print(s())"],
                 cwd=str(tmp_path),
                 env=_env(PYTHONPATH=REPO, JAX_COMPILATION_CACHE_DIR=None))
        assert r.returncode == 0, r.stderr.decode()
        assert r.stdout.decode().strip().splitlines()[-1] == want


class TestPlatformIsStated:
    def test_server_refuses_unrequested_cpu(self, monkeypatch):
        """JAX is on the CPU in this process; without JAX_PLATFORMS=cpu
        saying so on purpose, a server that schedules must not start."""
        from nomad_tpu.server import Server, ServerConfig

        monkeypatch.setattr(backend, "_resolved", None)
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        srv = Server(ServerConfig(num_schedulers=1))
        with pytest.raises(RuntimeError, match="no accelerator"):
            srv.start()
        assert backend.resolved() is None
        assert not srv._running
        # stated: the same server starts, and the process now holds "cpu"
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        srv.start()
        try:
            assert backend.resolved().platform == "cpu"
        finally:
            srv.shutdown()

    def test_mesh_wider_than_the_host_is_an_error(self):
        import jax

        from nomad_tpu.parallel import make_mesh

        with pytest.raises(ValueError, match="devices requested"):
            make_mesh(len(jax.devices()) * 2)


class TestOneProcessPerChip:
    def test_fingerprint_reads_held_backend_in_process(self, monkeypatch):
        """An agent that schedules holds the chip: its fingerprint must
        come from its own backend, never from a child."""
        from nomad_tpu.client import fingerprint as fp
        from nomad_tpu.structs import Node

        def boom(*a, **k):
            raise AssertionError("child process under a JAX-holding parent")

        monkeypatch.setattr(subprocess, "run", boom)
        monkeypatch.setattr(subprocess, "Popen", boom)
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(backend, "_resolved", backend.Backend(
            "tpu", "TPU v5 lite", ("0", "1")))
        node = Node()
        fp.tpu_fingerprint(node)
        assert node.attributes["tpu.count"] == "2"
        assert node.attributes["tpu.type"] == "TPU v5 lite"
        (group,) = node.node_resources.devices
        assert group.id() == "google/tpu/tpu-v5-lite"
        assert [(i.id, i.healthy) for i in group.instances] == [
            ("0", True), ("1", True)]
        # a process held on the cpu has nothing to annotate — and still
        # asks no child
        monkeypatch.setattr(backend, "_resolved", backend.Backend(
            "cpu", "cpu", ("0",)))
        node = Node()
        fp.tpu_fingerprint(node)
        assert "tpu.count" not in node.attributes

    def test_held_device_that_stops_answering_flips_unhealthy(
            self, monkeypatch):
        """In-process health is asked of the live backend on every
        fingerprint, not frozen at `Server.start`: a device that stops
        answering flips its instances unhealthy with the reason, and
        comes back when it answers again."""
        import jax

        from nomad_tpu.client.devicemanager import TpuDevicePlugin

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.delenv("NOMAD_TPU_SKIP_TPU_FINGERPRINT", raising=False)
        monkeypatch.setattr(backend, "_resolved", backend.Backend(
            "tpu", "TPU v5 lite", ("0",)))
        plugin = TpuDevicePlugin()
        (group,) = plugin.fingerprint()
        assert [i.healthy for i in group.instances] == [True]

        class Dead:
            id = 0

            def memory_stats(self):
                raise RuntimeError("chip fell off the bus")

        monkeypatch.setattr(jax, "devices", lambda *a: [Dead()])
        (group,) = plugin.fingerprint()
        assert [i.healthy for i in group.instances] == [False]
        assert ("probe failed: device 0 does not answer: RuntimeError: "
                "chip fell off the bus") == \
            group.attributes["health_description"]
        assert plugin.stats()[group.id()]["0"]["healthy"] is False
        monkeypatch.undo()
        monkeypatch.delenv("NOMAD_TPU_SKIP_TPU_FINGERPRINT", raising=False)
        monkeypatch.setattr(backend, "_resolved", backend.Backend(
            "tpu", "TPU v5 lite", ("0",)))
        (group,) = plugin.fingerprint()
        assert [i.healthy for i in group.instances] == [True]

    def test_tpu_plugin_stays_in_process_when_backend_held(self,
                                                           monkeypatch):
        """NOMAD_TPU_OOP_DEVICES=tpu must not put the tpu plugin in a
        plugin host under a process that holds the device."""
        from nomad_tpu.client.devicemanager import (DeviceManager,
                                                    RemoteDevicePlugin,
                                                    TpuDevicePlugin)

        monkeypatch.setenv("NOMAD_TPU_OOP_DEVICES", "tpu")
        monkeypatch.delenv("NOMAD_TPU_SKIP_TPU_FINGERPRINT", raising=False)
        monkeypatch.setattr(backend, "_resolved", None)
        m = DeviceManager()
        assert isinstance(m.plugins[-1], RemoteDevicePlugin)
        m.shutdown()
        monkeypatch.setattr(backend, "_resolved", backend.Backend(
            "tpu", "TPU v5 lite", ("0",)))
        m = DeviceManager()
        assert isinstance(m.plugins[-1], TpuDevicePlugin)
        m.shutdown()


class TestChipSmoke:
    def test_no_chip_no_result(self):
        """JAX_PLATFORMS=cpu and no --rehearsal: non-zero, one-line
        reason, nothing that could be read as a result."""
        r = _run(["chip_smoke.py"], env=_env(JAX_PLATFORMS="cpu"),
                 timeout=120)
        assert r.returncode != 0
        assert "needs a TPU" in r.stderr.decode().strip().splitlines()[-1]
        assert "{" not in r.stdout.decode()

    def test_alone_without_the_program(self, tmp_path):
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        r = _run(["chip_smoke.py"], cwd=str(tmp_path),
                 env=_env(JAX_PLATFORMS="cpu", PYTHONPATH=None), timeout=60)
        assert r.returncode != 0
        assert "the program is not here" in r.stderr.decode()
        assert r.stdout.decode().strip() == ""

    def test_rehearsal_runs_the_same_checks(self):
        r = _run(["chip_smoke.py", "--rehearsal", "--seed", "5"],
                 env=_env(JAX_PLATFORMS=None))
        assert r.returncode == 0, r.stderr.decode()[-2000:]
        lines = r.stdout.decode().strip().splitlines()
        # the last line is the driver's verdict: these keys and no others
        assert json.loads(lines[-1]) == {
            "ok": True,
            "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
        out = json.loads(lines[-2])  # the run's record
        assert out["rehearsal"] is True
        assert out["seed"] == 5
        assert out["evals_complete"] == out["evals"] >= 36
        assert out["placements"] == out["placements_requested"] > 0
        assert out["nacks"] == out["plans_partial"] == 0
        assert out["table_dyn_rows"] > 0 and out["pack_buffers"] == 0
        assert out["carry_adopts"] + out["chain_adopts"] >= 1
        assert out["view_equals_cold_upload"] is True
        assert out["parity"]["node_agreement_pct"] == 100.0
        assert "unique-name" in out["parity"]["kinds"]
        assert "distinct-cell" in out["parity"]["kinds"]


class TestBuiltFromSource:
    def test_native_library_is_keyed_on_source_content(self):
        import hashlib

        from nomad_tpu import native

        with open(os.path.join(REPO, "native", "core.cpp"), "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:12]
        assert native._lib_path().endswith(f"libnomad_core.{digest}.so")
        st = native.status()
        if st["loaded"]:
            assert st["path"] == native._lib_path() and not st["reason"]
        else:
            assert st["reason"]  # a fallback says why

    def test_synth_ids_are_a_function_of_the_seed(self):
        import random

        from nomad_tpu.synth import (synth_alloc, synth_node,
                                     synth_service_job, synth_system_job)

        def ids(seed):
            rng = random.Random(seed)
            node = synth_node(rng, 0)
            job = synth_service_job(rng)
            return (node.id, job.id, synth_system_job(rng).id,
                    synth_alloc(rng, node, job).id)

        assert ids(3) == ids(3)
        assert all(a != b for a, b in zip(ids(3), ids(4)))


class TestCodecRegistry:
    def test_concurrent_first_decode(self):
        """A burst of first requests decodes on many threads at once; a
        reader must never see a half-built registry (found by the smoke:
        'unknown struct type Job')."""
        from nomad_tpu import mock
        from nomad_tpu.structs import codec

        tree = codec.to_wire(mock.job())
        errors = []
        start = threading.Barrier(16)

        def decode():
            start.wait(10.0)
            try:
                codec.from_wire(tree)
            except Exception as e:  # noqa: BLE001 — the failure under test
                errors.append(repr(e))

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            for _ in range(5):
                codec._REGISTRY.clear()
                threads = [threading.Thread(target=decode)
                           for _ in range(16)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(30.0)
                assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(old)
            codec.registry()
        assert errors == []


class TestHeartbeatTracker:
    """One watcher thread for every node's TTL (a thread per node cost
    ~80 GB of address space at 10K nodes and got the smoke killed)."""

    def test_many_nodes_one_thread_and_exact_expiry(self):
        import time

        from nomad_tpu.server.heartbeat import HeartbeatTracker

        expired = []
        done = threading.Event()

        def on_expire(node_id):
            expired.append(node_id)
            if len(expired) >= 1998:
                done.set()

        hb = HeartbeatTracker(ttl=0.3, on_expire=on_expire)
        hb.reset("before-start")  # disabled tracker: no deadline kept
        before = threading.active_count()
        hb.start()
        try:
            for i in range(2000):
                hb.reset(f"n{i}")
            assert threading.active_count() == before + 1
            hb.remove("n7")
            deadline = time.monotonic() + 0.25
            while time.monotonic() < deadline:
                hb.reset("n3")  # a node that keeps heartbeating
                time.sleep(0.02)
            assert done.wait(10.0)
            assert "n3" not in expired and "n7" not in expired
            assert "before-start" not in expired
            assert len(expired) == len(set(expired)) == 1998
            time.sleep(0.5)  # n3 stopped heartbeating: it expires too
            assert expired[-1] == "n3"
        finally:
            hb.shutdown()
        hb.reset("after-shutdown")
        time.sleep(0.4)
        assert "after-shutdown" not in expired

    def test_failing_expiry_does_not_end_tracking(self):
        from nomad_tpu.server.heartbeat import HeartbeatTracker

        seen = threading.Event()

        def on_expire(node_id):
            if node_id == "bad":
                raise RuntimeError("boom")
            seen.set()

        hb = HeartbeatTracker(ttl=0.05, on_expire=on_expire)
        hb.start()
        try:
            hb.reset("bad")
            hb.reset("good")
            assert seen.wait(10.0)
        finally:
            hb.shutdown()


class TestPerTestLimit:
    """tests/conftest.py: a test that never returns fails alone."""

    def test_a_stuck_test_fails_with_its_stacks_and_the_next_one_runs(
            self, tmp_path):
        conftest = os.path.join(REPO, "tests", "conftest.py")
        (tmp_path / "conftest.py").write_text(
            "import importlib.util\n"
            f"spec = importlib.util.spec_from_file_location('c', {conftest!r})\n"
            "c = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(c)\n"
            "c.TEST_LIMIT_S = 2.0\n"
            "pytest_runtest_protocol = c.pytest_runtest_protocol\n")
        (tmp_path / "test_stuck.py").write_text(
            "import threading\n"
            "def test_waits_forever(): threading.Event().wait()\n"
            "def test_after_it(): pass\n")
        r = _run(["-m", "pytest", "-q", "-p", "no:cacheprovider", "-p",
                  "no:xdist", "-p", "no:randomly", str(tmp_path)],
                 cwd=str(tmp_path), env=_env(JAX_PLATFORMS="cpu"),
                 timeout=60)
        out = r.stdout.decode() + r.stderr.decode()
        assert r.returncode == 1, out[-3000:]
        assert "1 failed, 1 passed" in out, out[-3000:]
        assert "test_waits_forever exceeded the per-test limit of 2 s" in out
        # every thread's stack, the stuck frame among them
        assert "Current thread 0x" in out
        assert 'test_stuck.py", line 2 in test_waits_forever' in out
