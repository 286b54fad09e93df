"""Cloud/CNI fingerprinters (reference: client/fingerprint/env_gce.go,
env_aws.go, cni.go) — driven against a local fake metadata server."""
import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from nomad_tpu.client.fingerprint import (cni_fingerprint,
                                          env_aws_fingerprint,
                                          env_gce_fingerprint)
from nomad_tpu.structs import Node


@pytest.fixture()
def metadata_server():
    routes = {}

    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            body = routes.get(self.path)
            if body is None:
                self.send_response(404)
                self.end_headers()
                return
            data = body.encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    yield routes, f"http://127.0.0.1:{httpd.server_address[1]}"
    httpd.shutdown()
    httpd.server_close()


class TestCloudFingerprints:
    def test_gce(self, metadata_server, monkeypatch):
        routes, base = metadata_server
        routes.update({
            "/instance/machine-type":
                "projects/1/machineTypes/n2-standard-8",
            "/instance/zone": "projects/1/zones/us-central1-a",
            "/instance/hostname": "vm1.c.proj.internal",
            "/instance/id": "12345",
        })
        monkeypatch.setenv("NOMAD_TPU_GCE_METADATA_URL", base)
        node = Node()
        env_gce_fingerprint(node)
        assert node.attributes["platform.gce.machine-type"] \
            == "n2-standard-8"
        assert node.attributes["platform.gce.zone"] == "us-central1-a"
        assert node.attributes["unique.platform.gce.id"] == "12345"

    def test_gce_not_on_cloud_is_silent(self, metadata_server,
                                        monkeypatch):
        routes, base = metadata_server  # no routes → 404s
        monkeypatch.setenv("NOMAD_TPU_GCE_METADATA_URL", base)
        node = Node()
        env_gce_fingerprint(node)
        assert not any(k.startswith("platform.gce")
                       for k in node.attributes)

    def test_aws(self, metadata_server, monkeypatch):
        routes, base = metadata_server
        routes.update({
            "/instance-type": "m5.large",
            "/placement/availability-zone": "us-east-1b",
            "/instance-id": "i-abc123",
            "/local-ipv4": "10.0.0.7",
        })
        monkeypatch.setenv("NOMAD_TPU_AWS_METADATA_URL", base)
        node = Node()
        env_aws_fingerprint(node)
        assert node.attributes["platform.aws.instance-type"] == "m5.large"
        assert node.attributes["unique.platform.aws.local-ipv4"] \
            == "10.0.0.7"

    def test_aws_imdsv2_token_flow(self, monkeypatch):
        """HttpTokens=required hosts 401 plain GETs; the fingerprinter
        must fetch a session token first."""
        TOKEN = "tok-123"
        routes = {"/latest/meta-data/instance-type": "c6i.large",
                  "/latest/meta-data/placement/availability-zone": "eu-1a",
                  "/latest/meta-data/instance-id": "i-v2",
                  "/latest/meta-data/local-ipv4": "10.1.1.1"}

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_PUT(self):
                if self.path == "/latest/api/token":
                    data = TOKEN.encode()
                    self.send_response(200)
                    self.send_header("Content-Length", str(len(data)))
                    self.end_headers()
                    self.wfile.write(data)
                    return
                self.send_response(404)
                self.end_headers()

            def do_GET(self):
                if self.headers.get("X-aws-ec2-metadata-token") != TOKEN:
                    self.send_response(401)
                    self.end_headers()
                    return
                body = routes.get(self.path)
                if body is None:
                    self.send_response(404)
                    self.end_headers()
                    return
                data = body.encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

        httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            base = f"http://127.0.0.1:{httpd.server_address[1]}" \
                   "/latest/meta-data"
            monkeypatch.setenv("NOMAD_TPU_AWS_METADATA_URL", base)
            node = Node()
            env_aws_fingerprint(node)
            assert node.attributes["platform.aws.instance-type"] \
                == "c6i.large"
            assert node.attributes["unique.platform.aws.instance-id"] \
                == "i-v2"
        finally:
            httpd.shutdown()
            httpd.server_close()

    def test_unreachable_metadata_is_silent(self, monkeypatch):
        """A dead endpoint must leave no attrs (CI may itself run on a
        cloud VM, so pin the URL instead of relying on DMI markers)."""
        monkeypatch.setenv("NOMAD_TPU_GCE_METADATA_URL",
                           "http://127.0.0.1:9")  # discard port: refused
        monkeypatch.setenv("NOMAD_TPU_AWS_METADATA_URL",
                           "http://127.0.0.1:9")
        node = Node()
        env_gce_fingerprint(node)
        env_aws_fingerprint(node)
        assert not any(k.startswith("platform.")
                       for k in node.attributes)


class TestCniFingerprint:
    def test_conflist_discovered(self, tmp_path, monkeypatch):
        (tmp_path / "mynet.conflist").write_text(json.dumps(
            {"name": "mynet", "cniVersion": "0.4.0", "plugins": []}))
        (tmp_path / "junk.txt").write_text("ignored")
        monkeypatch.setenv("NOMAD_TPU_CNI_CONFIG_DIR", str(tmp_path))
        node = Node()
        cni_fingerprint(node)
        assert node.attributes["plugins.cni.config.mynet"] \
            == str(tmp_path / "mynet.conflist")
        assert len([k for k in node.attributes
                    if k.startswith("plugins.cni.config.")]) == 1


class TestTpuFingerprintBounded:
    """The child probe of a process that holds no JAX backend (a
    client-only agent). The in-process path of a scheduling agent is in
    tests/test_bringup.py."""

    @pytest.fixture(autouse=True)
    def _no_backend_held(self, monkeypatch):
        from nomad_tpu.lib import backend

        monkeypatch.setattr(backend, "_resolved", None)
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)

    def test_hung_probe_leaves_node_unannotated(self, monkeypatch):
        """A hanging accelerator runtime must not block fingerprinting:
        the subprocess probe times out, says so, and the agent moves on."""
        import subprocess

        from nomad_tpu.client import fingerprint as fp

        def fake_run(*a, **k):
            raise subprocess.TimeoutExpired(cmd=a[0], timeout=k["timeout"])

        monkeypatch.setattr(subprocess, "run", fake_run)
        node = Node()
        fp.tpu_fingerprint(node)  # must return promptly, not raise
        assert "tpu.count" not in node.attributes
        devs, why = fp.accelerator_devices()
        assert devs == [] and "timed out" in why

    def test_failed_probe_says_why(self, monkeypatch):
        import subprocess

        from nomad_tpu.client import fingerprint as fp

        class R:
            returncode = 1
            stdout = b""
            stderr = b"Traceback ...\nRuntimeError: TPU is already in use\n"

        monkeypatch.setattr(subprocess, "run", lambda *a, **k: R())
        devs, why = fp.accelerator_devices()
        assert devs == []
        assert "rc=1" in why and "already in use" in why

    def test_probe_result_annotates_devices(self, monkeypatch):
        import json
        import subprocess

        from nomad_tpu.client import fingerprint as fp

        rows = [{"id": "0", "platform": "tpu", "kind": "TPU v5 lite"}]

        class R:
            returncode = 0
            stdout = json.dumps(rows).encode()
            stderr = b""

        monkeypatch.setattr(subprocess, "run", lambda *a, **k: R())
        node = Node()
        fp.tpu_fingerprint(node)
        assert node.attributes["tpu.count"] == "1"
        assert node.attributes["tpu.type"] == "TPU v5 lite"
        assert node.node_resources.devices[0].vendor == "google"
        assert node.node_resources.devices[0].instances[0].id == "0"

    def test_cpu_request_skips_probe(self, monkeypatch):
        import subprocess

        from nomad_tpu.client import fingerprint as fp

        def boom(*a, **k):
            raise AssertionError("no probe when the operator asked for cpu")

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.setattr(subprocess, "run", boom)
        node = Node()
        fp.tpu_fingerprint(node)
        assert "tpu.count" not in node.attributes
