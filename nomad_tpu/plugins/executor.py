"""Out-of-process task executor plugin.

Behavioral reference: `drivers/shared/executor/` — `executor.go` (Launch /
Wait / Shutdown / Exec / Stats contract), `executor_plugin.go` (served as
a plugin over the wire), `executor_linux.go` (isolation), `pid_collector.go`
(process stats). One executor process per task; it is the task's parent,
lives in its own session, and therefore survives the agent: after an agent
restart the driver reattaches via the persisted {pid, addr} record and the
task never noticed (`RecoverTask`, `plugins/drivers/driver.go`).

Log capture: the executor owns the task's stdout/stderr pipes and writes
the rotating `<task>.{stdout,stderr}.N` files itself (the reference splits
this into a separate logmon plugin; folding it into the executor keeps one
process per task while preserving the property that log capture survives
agent restarts — the actual deviation is documented in client/logmon.py).

Run as: python -m nomad_tpu.plugins.executor
"""
from __future__ import annotations

import contextlib
import os
import select
import signal as _signal
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

from . import isolation
from .base import serve_plugin

from ..client.drivers.base import SIGNALS as _signals


class ExecutorService:
    """The per-task executor endpoint (executor.go Executor interface)."""

    #: after the task has exited, an executor nobody talks to for this
    #: long exits on its own — without it, every agent killed mid-task
    #: leaks one plugin process per task forever (observed: 156 orphans
    #: on a busy dev box). Generous enough that an agent restart's
    #: recover window (seconds–minutes) never races it.
    IDLE_GRACE_S = 900.0
    #: launch() waits this long for the bootstrap to exec the command
    #: (interpreter start, cgroup/namespace/chroot set-up: well under 1 s)
    BOOTSTRAP_WAIT_S = 10.0

    def __init__(self) -> None:
        self._proc: Optional[subprocess.Popen] = None
        self._exit: Optional[Dict[str, object]] = None
        self._exit_ev = threading.Event()
        self._cgroup: Optional[isolation.Cgroup] = None
        self._spec: Dict[str, object] = {}
        self._applied: Dict[str, object] = {}
        self._pumps: List[threading.Thread] = []
        self._stop_plugin: Optional[threading.Event] = None
        self._last_rpc = time.time()
        self._inflight = 0
        self._act_lock = threading.Lock()
        threading.Thread(target=self._idle_reaper, name="idle-reaper",
                         daemon=True).start()

    @contextlib.contextmanager
    def _touch(self):
        """RPC-activity scope: the reaper only counts idle time with no
        call in flight (wait() long-polls for hours while attached)."""
        with self._act_lock:
            self._last_rpc = time.time()
            self._inflight += 1
        try:
            yield
        finally:
            with self._act_lock:
                self._last_rpc = time.time()
                self._inflight -= 1

    def _idle_reaper(self) -> None:
        try:
            grace = float(os.environ.get("NOMAD_TPU_EXECUTOR_IDLE_GRACE",
                                         str(self.IDLE_GRACE_S)))
        except ValueError:  # malformed override must not disable reaping
            grace = self.IDLE_GRACE_S
        while True:
            time.sleep(min(grace / 4, 5.0))
            with self._act_lock:
                idle = (self._inflight == 0
                        and time.time() - self._last_rpc > grace)
            task_over = self._proc is None or self._exit is not None
            if idle and task_over:
                # never launched, or task done and nobody attached: go.
                # Only when serving as a real plugin (stop event wired by
                # main()) — in-process uses of this class must never be
                # able to kill their host.
                stop = self._stop_plugin
                if stop is not None:
                    if self._cgroup:  # same cleanup destroy() performs
                        try:
                            self._cgroup.destroy()
                        except Exception:  # noqa: BLE001
                            pass
                    stop.set()
                    return

    # -- contract ----------------------------------------------------------

    def launch(self, spec: Dict[str, object]) -> Dict[str, object]:
        """executor.go Launch: start the task under the requested isolation.

        spec: command, args, env, cwd, user, task_id,
              stdout_prefix/stderr_prefix (rotating file prefixes),
              logs_dir, max_files, max_file_size_mb,
              memory_mb, cpu_shares, pids_max,
              isolation: {cgroup, namespaces, pid_namespace, chroot,
                          chroot_paths, rlimit_memory, nice}
        """
        if self._proc is not None:
            raise RuntimeError("task already launched")
        self._spec = spec
        # a fresh run invalidates any predecessor's exit record — a
        # stale one would let recovery report the OLD run's result for a
        # lost in-flight run
        stale = self._exit_record_path()
        if stale is not None:
            try:
                os.unlink(stale)
            except OSError:
                pass
        iso = spec.get("isolation") or {}
        caps = isolation.capabilities()
        applied: Dict[str, object] = {"cgroup": None, "namespaces": False,
                                      "pid_namespace": False, "chroot": False,
                                      "rlimit_memory": False}

        task_id = str(spec.get("task_id") or f"task-{os.getpid()}")
        cg_name = task_id.replace("/", "_")

        init_spec: Dict[str, object] = {
            "command": spec["command"],
            "args": spec.get("args") or [],
            "env": spec.get("env") or {},
            "cwd": spec.get("cwd") or None,
            "user": spec.get("user") or None,
            "nice": iso.get("nice", 0),
        }

        if iso.get("cgroup") and caps["cgroup"]:
            self._cgroup = isolation.Cgroup(cg_name)
            self._cgroup.create(
                memory_mb=int(spec.get("memory_mb") or 0),
                cpu_shares=int(spec.get("cpu_shares") or 0),
                pids_max=int(spec.get("pids_max") or 0),
            )
            init_spec["cgroup"] = {"name": cg_name,
                                   "version": self._cgroup.version}
            applied["cgroup"] = self._cgroup.version
        if iso.get("rlimit_memory"):
            init_spec["rlimit_memory_mb"] = int(spec.get("memory_mb") or 0)
            applied["rlimit_memory"] = True
        if iso.get("namespaces") and caps["namespaces"]:
            init_spec["namespaces"] = True
            applied["namespaces"] = True
            if iso.get("pid_namespace"):
                init_spec["pid_namespace"] = True
                applied["pid_namespace"] = True
        if iso.get("netns"):
            init_spec["netns"] = iso["netns"]
            applied["netns"] = iso["netns"]
        if iso.get("chroot") and caps["chroot"] and applied["namespaces"]:
            init_spec["chroot"] = iso["chroot"]
            init_spec["chroot_paths"] = iso.get("chroot_paths")
            init_spec["chroot_cwd"] = iso.get("chroot_cwd")
            applied["chroot"] = True
        self._applied = applied

        import json

        out = self._rotator(spec, "stdout")
        err = self._rotator(spec, "stderr")
        # launch() returns when the bootstrap has exec'd the command (or
        # died trying): taskinit holds this pipe's write end close-on-exec,
        # so end-of-file here IS the exec. Returning at once let a stop()
        # in the bootstrap's first ~0.4 s (interpreter start + imports)
        # TERM the bootstrap itself — a task that traps TERM then "died by
        # TERM" without ever having run.
        ready_r, ready_w = os.pipe()
        init_spec["ready_fd"] = ready_w
        # taskinit must import nomad_tpu regardless of the task's env;
        # the spec rides in an env var (no tempfile lifetime races)
        boot_env = {**os.environ,
                    "PYTHONPATH": os.pathsep.join(p for p in sys.path if p),
                    "NOMAD_TASKINIT_SPEC": json.dumps(init_spec)}
        try:
            self._proc = subprocess.Popen(
                [sys.executable, "-m", "nomad_tpu.plugins.taskinit"],
                stdout=subprocess.PIPE if out else subprocess.DEVNULL,
                stderr=subprocess.PIPE if err else subprocess.DEVNULL,
                stdin=subprocess.DEVNULL,
                env=boot_env,
                pass_fds=(ready_w,),
            )
        except BaseException:
            os.close(ready_r)
            raise
        finally:
            os.close(ready_w)
        with os.fdopen(ready_r, "rb") as ready:  # nothing is ever written
            select.select([ready], [], [], self.BOOTSTRAP_WAIT_S)
        for stream, rot in ((self._proc.stdout, out),
                            (self._proc.stderr, err)):
            if stream is None or rot is None:
                continue

            def pump(stream=stream, rot=rot):
                # read1, NOT read: BufferedReader.read(n) blocks until n
                # bytes or EOF, which would hide a long-running task's
                # sparse output until it exits (logs/`alloc logs -f`
                # must see lines as they are written)
                for chunk in iter(lambda: stream.read1(8192), b""):
                    try:
                        rot.write(chunk)
                    except Exception:
                        break
                stream.close()
                rot.close()

            t = threading.Thread(target=pump, daemon=True)
            t.start()
            self._pumps.append(t)

        threading.Thread(target=self._reap, daemon=True).start()
        return {"pid": self._proc.pid, "applied": applied,
                # single source of truth for the record location: the
                # driver stores this verbatim (no parallel derivation)
                "exit_record": self._exit_record_path() or ""}

    def _rotator(self, spec, stream: str):
        from ..client.logmon import FileRotator

        logs_dir = spec.get("logs_dir")
        prefix = spec.get(f"{stream}_prefix")
        if not logs_dir or not prefix:
            return None
        return FileRotator(
            logs_dir, prefix,
            max_files=int(spec.get("max_files") or 10),
            max_file_size=int(spec.get("max_file_size_mb") or 10)
            * 1024 * 1024,
        )

    def _reap(self) -> None:
        code = self._proc.wait()
        for t in self._pumps:
            t.join(timeout=2.0)
        oom = self._cgroup.oom_killed() if self._cgroup else False
        if code < 0:
            rec = {"exit_code": 0, "signal": -code,
                   "oom_killed": oom, "err": ""}
        else:
            rec = {"exit_code": code, "signal": 0,
                   "oom_killed": oom, "err": ""}
        # persist BEFORE publishing: the idle reaper keys on self._exit,
        # and must never kill the process between exit and the record
        # landing on disk
        self._persist_exit(rec)
        # cgroup stays for post-mortem stats; removed on destroy
        self._exit = rec
        self._exit_ev.set()

    def _exit_record_path(self) -> Optional[str]:
        logs_dir = self._spec.get("logs_dir")
        task_id = str(self._spec.get("task_id") or "")
        if not logs_dir or not task_id:
            return None
        safe = task_id.replace("/", "_")
        return os.path.join(str(logs_dir), f".{safe}.exit.json")

    def _persist_exit(self, rec: Dict[str, object]) -> None:
        """Durable exit record: if this executor self-reaps before the
        agent ever comes back, recovery reads the result from disk
        instead of re-running a completed (possibly non-idempotent)
        task."""
        path = self._exit_record_path()
        if path is None:
            return
        import json as _json

        try:
            tmp = f"{path}.tmp"
            with open(tmp, "w") as f:
                _json.dump(rec, f)
            os.replace(tmp, path)
        except OSError:
            pass  # logs dir gone: nothing to persist into

    def wait(self, timeout_s: Optional[float] = None
             ) -> Optional[Dict[str, object]]:
        """executor.go Wait — blocks (RPC server runs one thread per
        request, so long waits don't starve other calls)."""
        if self._exit_ev.wait(timeout_s):
            return self._exit
        return None

    def status(self) -> Dict[str, object]:
        return {
            "pid": self._proc.pid if self._proc else 0,
            "running": self._proc is not None and self._exit is None,
            "exit": self._exit,
            "applied": self._applied,
        }

    def stop(self, sig: str = "SIGTERM", grace_s: float = 5.0
             ) -> Optional[Dict[str, object]]:
        """executor.go Shutdown: signal, grace period, then SIGKILL."""
        if self._proc is None or self._exit is not None:
            return self._exit
        signum = _signals.get(sig, _signal.SIGTERM)
        try:
            os.killpg(self._proc.pid, signum)
        except (ProcessLookupError, PermissionError):
            try:
                self._proc.send_signal(signum)
            except ProcessLookupError:
                pass
        if not self._exit_ev.wait(grace_s):
            try:
                os.killpg(self._proc.pid, _signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            if self._cgroup:
                self._cgroup.kill_all()
            self._exit_ev.wait(2.0)
        return self._exit

    def signal(self, sig: str = "SIGHUP") -> bool:
        """executor.go Signal: deliver without initiating shutdown."""
        if self._proc is None or self._exit is not None:
            return False
        signum = _signals.get(sig)
        if signum is None:
            raise ValueError(f"unknown signal {sig!r}")
        try:
            os.killpg(self._proc.pid, signum)
        except (ProcessLookupError, PermissionError):
            try:
                self._proc.send_signal(signum)
            except ProcessLookupError:
                return False
        return True

    def stats(self) -> Dict[str, object]:
        """pid_collector.go analog: cgroup stats + /proc fallback."""
        out: Dict[str, object] = {"pids": {}}
        if self._cgroup:
            out.update(self._cgroup.stats())
        if self._proc and self._exit is None:
            try:
                with open(f"/proc/{self._proc.pid}/statm") as fh:
                    pages = int(fh.read().split()[1])
                out.setdefault("memory_bytes",
                               pages * os.sysconf("SC_PAGE_SIZE"))
            except (OSError, IndexError, ValueError):
                pass
        return out

    def exec_cmd(self, command: str, args: List[str],
                 timeout_s: float = 30.0) -> Dict[str, object]:
        """executor_linux.go Exec (nsenter path): run a command INSIDE
        the task's isolation context — its namespaces, chroot, and
        cgroup — not just with its cwd/env. Powers `nomad alloc exec`;
        a chrooted task's exec must see the chroot root, and the
        command's resource usage must land in the task's cgroup. Falls
        back to plain cwd/env when the task holds no isolation (raw_exec)
        or is already dead."""
        spec = self._spec
        applied = self._applied or {}
        preexec = None
        cwd = spec.get("cwd") or None
        if (self._proc is not None and self._exit is None
                and (applied.get("namespaces") or applied.get("cgroup"))):
            pid = self._proc.pid
            cg = self._cgroup
            inner_cwd = (spec.get("isolation") or {}).get("chroot_cwd") \
                if applied.get("chroot") else (spec.get("cwd") or "/")
            if applied.get("chroot"):
                # startup race: an exec issued before taskinit finishes
                # pivoting would join a not-yet-chrooted context and
                # escape the sandbox — wait (bounded) for the pivot and
                # FAIL CLOSED if it never materializes
                pivoted = False
                deadline = time.time() + 5.0
                while time.time() < deadline:
                    try:
                        if os.readlink(f"/proc/{pid}/root") != "/":
                            pivoted = True
                            break
                    except OSError:
                        break  # task died: fail below, never on host
                    time.sleep(0.05)
                if not pivoted:
                    return {"exit_code": -1, "stdout": "",
                            "stderr": "task context unavailable "
                                      "(chroot not entered or task "
                                      "dead) — refusing host exec"}
            # fail-closed requirements: the contexts the task is KNOWN
            # to hold must be entered or the exec must not run
            need_ns = ["ipc", "uts", "mnt"] \
                if applied.get("namespaces") else []

            def preexec():  # noqa: F811 — child-side context entry
                isolation.enter_task_context(
                    pid, cg, chdir_to=inner_cwd or "/",
                    required_ns=need_ns,
                    require_root=bool(applied.get("chroot")))

            cwd = None  # the preexec pivot owns the working directory
        try:
            r = subprocess.run(
                [command] + [str(a) for a in args or []],
                cwd=cwd,
                env={**os.environ, **(spec.get("env") or {})},
                capture_output=True, timeout=timeout_s,
                preexec_fn=preexec,
            )
            return {"exit_code": r.returncode,
                    "stdout": r.stdout.decode("utf-8", "replace"),
                    "stderr": r.stderr.decode("utf-8", "replace")}
        except subprocess.TimeoutExpired:
            return {"exit_code": -1, "stdout": "", "stderr": "timeout"}
        except (subprocess.SubprocessError, OSError) as e:
            # preexec_fn raised: the child aborted BEFORE exec — the
            # command never ran anywhere (fail-closed containment)
            return {"exit_code": -1, "stdout": "",
                    "stderr": f"could not enter task context: {e}"}

    def destroy(self) -> bool:
        """Kill the task if needed, clean the cgroup, exit the plugin."""
        if self._proc is not None and self._exit is None:
            self.stop("SIGKILL", 0.0)
        if self._cgroup:
            self._cgroup.destroy()
        # an explicitly destroyed task must not be resurrectable as
        # "completed" from its record
        rec = self._exit_record_path()
        if rec is not None:
            try:
                os.unlink(rec)
            except OSError:
                pass
        if self._stop_plugin is not None:
            # give the RPC response a beat to flush before exiting
            threading.Timer(0.2, self._stop_plugin.set).start()
        return True


def main() -> None:
    service = ExecutorService()

    def register(server) -> None:
        stop = threading.Event()
        server._plugin_stop = stop
        service._stop_plugin = stop
        # every RPC marks activity so the idle reaper never fires while
        # a driver is attached (incl. long-poll wait())
        def track(fn):
            def wrapped(*a, **k):
                with service._touch():
                    return fn(*a, **k)

            wrapped.__name__ = getattr(fn, "__name__", "handler")
            return wrapped

        server.register_endpoint("Executor", service, wrap=track)

    serve_plugin("executor", register)


if __name__ == "__main__":
    main()
