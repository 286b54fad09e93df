"""Plugin handshake + lifecycle (reference `plugins/base/base.go`,
go-plugin client/server handshake).

Protocol: the host launches the plugin subprocess (detached, own session,
stdout piped). The plugin binds a loopback TCP port, prints ONE handshake
line to stdout

    NOMAD_TPU_PLUGIN|<protocol-version>|<plugin-type>|<host>:<port>

then redirects its stdio to its log file and serves msgpack-RPC frames
(`nomad_tpu/rpc/transport.py`) forever. The host parses the line, connects
an `RpcClient`, and — like go-plugin's ReattachConfig — can persist
`{pid, addr}` and reconnect after a host restart via `reattach_plugin`.
"""
from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..rpc.transport import RpcClient, RpcError

HANDSHAKE_MAGIC = "NOMAD_TPU_PLUGIN"
PLUGIN_PROTOCOL_VERSION = 1
_HANDSHAKE_TIMEOUT = 15.0


class PluginLaunchError(RuntimeError):
    pass


class PluginClient:
    """Live connection to a plugin subprocess (go-plugin Client analog)."""

    def __init__(self, addr: Tuple[str, int], pid: int,
                 plugin_type: str = "",
                 proc: Optional[subprocess.Popen] = None) -> None:
        self.addr = addr
        self.pid = pid
        self.plugin_type = plugin_type
        self._proc = proc  # set when launched (not reattached): reaps
        self._rpc = RpcClient(addr[0], addr[1])

    def call(self, method: str, *args, timeout: Optional[float] = 10.0):
        return self._rpc.call(method, *args, timeout=timeout)

    def alive(self) -> bool:
        """Is the plugin *process* alive (regardless of our connection)?"""
        if self._proc is not None:
            return self._proc.poll() is None  # also reaps on exit
        try:
            os.kill(self.pid, 0)
            return True
        except (ProcessLookupError, PermissionError):
            return False

    def reattach_config(self) -> Dict[str, object]:
        """Persistable record for `reattach_plugin` (ReattachConfig)."""
        return {"pid": self.pid, "addr": list(self.addr),
                "type": self.plugin_type}

    def close(self) -> None:
        self._rpc.close()

    def kill(self, grace_s: float = 2.0) -> None:
        """Terminate the plugin process (go-plugin Client.Kill)."""
        self.close()
        try:
            os.kill(self.pid, 15)
        except (ProcessLookupError, PermissionError):
            return
        deadline = time.time() + grace_s
        while time.time() < deadline:
            if not self.alive():
                return
            time.sleep(0.05)
        try:
            os.kill(self.pid, 9)
        except (ProcessLookupError, PermissionError):
            pass
        if self._proc is not None:
            try:
                self._proc.wait(2.0)  # reap
            except Exception:
                pass


def launch_plugin(argv: List[str], env: Optional[Dict[str, str]] = None,
                  log_path: str = "", cwd: Optional[str] = None
                  ) -> PluginClient:
    """Spawn a plugin subprocess and complete the handshake.

    The child runs in its own session (start_new_session) so it is NOT in
    the host's process group and survives the host's death — that is what
    makes task recovery after an agent restart possible.
    """
    child_env = dict(os.environ)
    child_env[HANDSHAKE_MAGIC] = str(PLUGIN_PROTOCOL_VERSION)
    if log_path:
        child_env["NOMAD_TPU_PLUGIN_LOG"] = log_path
    if env:
        child_env.update(env)
    proc = subprocess.Popen(
        argv, env=child_env, cwd=cwd,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        stdin=subprocess.DEVNULL, start_new_session=True,
    )

    line_holder: List[str] = []

    def read_handshake():
        try:
            raw = proc.stdout.readline()
            line_holder.append(raw.decode("utf-8", "replace").strip())
        except Exception:
            pass

    t = threading.Thread(target=read_handshake, daemon=True)
    t.start()
    t.join(_HANDSHAKE_TIMEOUT)
    proc.stdout.close()
    line = line_holder[0] if line_holder else ""
    parts = line.split("|")
    if len(parts) != 4 or parts[0] != HANDSHAKE_MAGIC:
        try:
            proc.kill()
        except OSError:
            pass
        raise PluginLaunchError(
            f"bad plugin handshake from {argv[0]}: {line!r}")
    version, ptype, addr = parts[1], parts[2], parts[3]
    if int(version) != PLUGIN_PROTOCOL_VERSION:
        proc.kill()
        raise PluginLaunchError(f"plugin protocol mismatch: {version}")
    host, port = addr.rsplit(":", 1)
    return PluginClient((host, int(port)), proc.pid, ptype, proc=proc)


def reattach_plugin(reattach: Dict[str, object]) -> Optional[PluginClient]:
    """Reconnect to a still-running plugin from a persisted reattach
    record; None when the plugin is gone (task lost with it)."""
    pid = int(reattach.get("pid", 0))
    addr = reattach.get("addr") or []
    if not pid or len(addr) != 2:
        return None
    try:
        os.kill(pid, 0)
    except (ProcessLookupError, PermissionError):
        return None
    try:
        return PluginClient((str(addr[0]), int(addr[1])), pid,
                            str(reattach.get("type", "")))
    except (ConnectionError, OSError):
        return None


def oop_requested(env_var: str, name: str,
                  config: Optional[Dict] = None) -> bool:
    """Shared out-of-process opt-in rule for driver/device plugins:
    explicit `out_of_process` in the plugin's operator config wins,
    else the env var ("name1,name2" or "all")."""
    if config and "out_of_process" in config:
        return bool(config["out_of_process"])
    spec = os.environ.get(env_var, "")
    names = {s.strip() for s in spec.split(",") if s.strip()}
    return "all" in names or name in names


def serve_plugin(plugin_type: str, register) -> None:
    """Plugin-side main: bind, handshake on stdout, serve forever.

    `register(server)` installs endpoint handlers on the RpcServer. Called
    by plugin __main__ entrypoints (e.g. `nomad_tpu.plugins.executor`).
    """
    from ..rpc.transport import RpcServer

    server = RpcServer("127.0.0.1", 0)
    register(server)
    server.start()
    sys.stdout.write(
        f"{HANDSHAKE_MAGIC}|{PLUGIN_PROTOCOL_VERSION}|{plugin_type}|"
        f"{server.addr[0]}:{server.addr[1]}\n")
    sys.stdout.flush()

    # After the handshake stdout/stderr must not touch the (soon dead)
    # pipe: redirect to the log file, or /dev/null.
    log_path = os.environ.get("NOMAD_TPU_PLUGIN_LOG") or os.devnull
    fd = os.open(log_path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
    os.dup2(fd, 1)
    os.dup2(fd, 2)
    os.close(fd)

    # Serve until explicitly told to exit (Executor.destroy sets this).
    stop = getattr(server, "_plugin_stop", None)
    if stop is None:
        stop = threading.Event()
        server._plugin_stop = stop
    stop.wait()
    server.shutdown()


__all__ = ["HANDSHAKE_MAGIC", "PLUGIN_PROTOCOL_VERSION", "PluginClient",
           "PluginLaunchError", "RpcError", "launch_plugin",
           "reattach_plugin", "serve_plugin"]
