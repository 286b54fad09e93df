"""Executor-backed drivers: tasks run under an out-of-process executor.

Behavioral reference: `drivers/rawexec/driver.go` + `drivers/exec/driver.go`
both launch their task via the shared executor plugin
(`drivers/shared/executor/executor_plugin.go`); the driver holds a plugin
client, persists a reattach record inside the TaskHandle's driver_state
(`plugins/drivers/task_handle.go`), and `RecoverTask` reconnects after an
agent restart — the task itself never stops. This module is that exact
shape: `launch_plugin` → `Executor.launch` → handle with
{reattach, task_pid}; `recover_task` → `reattach_plugin` → live handle.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional

from ...plugins.base import (PluginClient, PluginLaunchError, launch_plugin,
                             reattach_plugin)
from .base import DriverPlugin, ExitResult, TaskConfig, TaskHandle

import sys


class ExecutorTaskHandle(TaskHandle):
    """TaskHandle bound to a live executor plugin client."""

    def __init__(self, task_id: str, driver: str, client: PluginClient,
                 driver_state: Optional[dict] = None) -> None:
        super().__init__(task_id, driver, driver_state)
        self.client = client
        self._waiter = threading.Thread(target=self._wait_loop, daemon=True)
        self._waiter.start()

    def _wait_loop(self) -> None:
        while True:
            try:
                res = self.client.call("Executor.wait", 3600.0,
                                       timeout=3630.0)
            except Exception as e:
                # executor died under us → task died with it
                self.set_exit(ExitResult(exit_code=-1,
                                         err=f"executor lost: {e}"))
                return
            if res is not None:
                self.set_exit(ExitResult(
                    exit_code=int(res.get("exit_code", 0)),
                    signal=int(res.get("signal", 0)),
                    oom_killed=bool(res.get("oom_killed")),
                    err=str(res.get("err", "")),
                ))
                return


class ExecutorBackedDriver(DriverPlugin):
    """Shared Start/Stop/Destroy/Recover over the executor plugin."""

    name = "executor"

    #: subclass knob — what isolation the executor should apply
    def _isolation(self, cfg: TaskConfig) -> Dict[str, object]:
        # even the un-isolated raw_exec joins the alloc's netns when the
        # group uses bridge networking (the netns is alloc-level
        # plumbing, not task-level isolation)
        return {"netns": cfg.netns} if cfg.netns else {}

    def _launch_spec(self, cfg: TaskConfig) -> Dict[str, object]:
        rc = cfg.raw_config
        command = rc.get("command")
        if not command:
            raise ValueError(f"{self.name} requires config.command")
        logs_dir = os.path.dirname(cfg.stdout_path) if cfg.stdout_path else ""

        def rot_prefix(path: str, stream: str) -> str:
            # "<task>.stdout.N" → "<task>.stdout" (FileRotator prefix)
            if path:
                return os.path.basename(path).rsplit(".", 1)[0]
            return f"{cfg.name}.{stream}"

        return {
            "task_id": cfg.id,
            "command": str(command),
            "args": [str(a) for a in rc.get("args", [])],
            "env": {**os.environ, **cfg.env},
            "cwd": cfg.task_dir or None,
            "user": cfg.user or None,
            "logs_dir": logs_dir,
            "stdout_prefix": rot_prefix(cfg.stdout_path, "stdout"),
            "stderr_prefix": rot_prefix(cfg.stderr_path, "stderr"),
            "max_files": cfg.max_files,
            "max_file_size_mb": cfg.max_file_size_mb,
            "memory_mb": cfg.memory_mb,
            "cpu_shares": cfg.cpu_mhz,
            "pids_max": int(rc.get("pids_max", 0) or 0),
            "isolation": self._isolation(cfg),
        }

    def start_task(self, cfg: TaskConfig) -> TaskHandle:
        log_path = ""
        if cfg.task_dir:
            log_path = os.path.join(cfg.task_dir, "executor.log")
        client = launch_plugin(
            [sys.executable, "-m", "nomad_tpu.plugins.executor"],
            # the executor must import nomad_tpu wherever it starts
            env={"PYTHONPATH": os.pathsep.join(p for p in sys.path if p)},
            log_path=log_path,
        )
        try:
            res = client.call("Executor.launch", self._launch_spec(cfg),
                              timeout=30.0)
        except Exception:
            client.kill()
            raise
        handle = ExecutorTaskHandle(
            cfg.id, self.name, client,
            driver_state={
                "reattach": client.reattach_config(),
                "task_pid": res.get("pid"),
                "applied": res.get("applied"),
                # durable exit record the executor writes at task exit —
                # recovery falls back to it when the (self-reaped)
                # executor is gone, instead of re-running the task. The
                # executor names the file; stored verbatim.
                "exit_record": res.get("exit_record", ""),
            },
        )
        return handle

    def recover_task(self, task_id: str,
                     driver_state: dict) -> Optional[TaskHandle]:
        """plugins/drivers RecoverTask: reattach to the live executor;
        fall back to the durable exit record when the executor already
        self-reaped (its task had FINISHED — returning None there would
        make the restart loop re-run a completed task); None only when
        the task's fate is genuinely unknown."""
        client = reattach_plugin(driver_state.get("reattach") or {})
        if client is None:
            return self._recover_from_record(task_id, driver_state)
        try:
            st = client.call("Executor.status", timeout=5.0)
        except Exception:
            # executor died between reattach and the status RPC (e.g.
            # its idle grace expired right now): same fallback
            client.close()
            return self._recover_from_record(task_id, driver_state)
        handle = ExecutorTaskHandle(task_id, self.name, client,
                                    driver_state=driver_state)
        if not st.get("running") and st.get("exit") is not None:
            # already exited while we were away; waiter will fetch the
            # same result, nothing else to do
            pass
        return handle

    def _recover_from_record(self, task_id: str,
                             driver_state: dict) -> Optional[TaskHandle]:
        rec_path = driver_state.get("exit_record") or ""
        if not rec_path or not os.path.exists(rec_path):
            return None
        import json as _json

        try:
            with open(rec_path) as f:
                rec = _json.load(f)
        except (OSError, ValueError):
            return None
        handle = TaskHandle(task_id, self.name, driver_state=driver_state)
        handle.set_exit(ExitResult(
            exit_code=int(rec.get("exit_code", 0)),
            signal=int(rec.get("signal", 0)),
            oom_killed=bool(rec.get("oom_killed")),
            err=str(rec.get("err", ""))))
        return handle

    def stop_task(self, handle: TaskHandle, timeout_s: float = 5.0,
                  signal: str = "SIGTERM") -> None:
        client = getattr(handle, "client", None)
        if client is None or not handle.is_running():
            return
        try:
            client.call("Executor.stop", signal, timeout_s,
                        timeout=timeout_s + 10.0)
        except Exception:
            pass
        handle.wait(2.0)

    def destroy_task(self, handle: TaskHandle, force: bool = False) -> None:
        client = getattr(handle, "client", None)
        if handle.is_running() and not force:
            raise RuntimeError("task still running; use force")
        destroyed_via_rpc = False
        if client is not None:
            try:
                client.call("Executor.destroy", timeout=10.0)
                destroyed_via_rpc = True  # executor retired its record
            except Exception:
                pass
            client.close()
        if not destroyed_via_rpc:
            # executor gone (record-backed handle) or the destroy RPC
            # failed: retire the record ourselves so the destroyed task
            # can't be resurrected as "completed" later
            rec = handle.driver_state.get("exit_record") or ""
            if rec:
                try:
                    os.unlink(rec)
                except OSError:
                    pass

    def inspect_task(self, handle: TaskHandle) -> dict:
        base = super().inspect_task(handle)
        stats = self.stats_task(handle)
        if stats:
            base["stats"] = stats
        base["driver_state"] = handle.driver_state
        return base

    def stats_task(self, handle: TaskHandle) -> dict:
        """pid_collector.go analog via the executor RPC."""
        client = getattr(handle, "client", None)
        if client is None:
            return {}
        try:
            return client.call("Executor.stats", timeout=5.0) or {}
        except Exception:  # noqa: BLE001 — executor may be gone
            return {}

    def signal_task(self, handle: TaskHandle, sig: str = "SIGHUP") -> bool:
        """driver SignalTask (plugins/drivers/driver.go) — powers
        `alloc signal`."""
        client = getattr(handle, "client", None)
        if client is None or not handle.is_running():
            raise RuntimeError("task is not running")
        return bool(client.call("Executor.signal", sig, timeout=10.0))

    def exec_task(self, handle: TaskHandle, command: str,
                  args: Optional[List[str]] = None,
                  timeout_s: float = 30.0) -> dict:
        """driver Exec (plugins/drivers/driver.go ExecTaskStreaming's
        non-streaming core) — powers `alloc exec`."""
        client = getattr(handle, "client", None)
        if client is None:
            raise RuntimeError("no live executor for task")
        return client.call("Executor.exec_cmd", command, args or [],
                           timeout_s, timeout=timeout_s + 10.0)


class RawExecDriver(ExecutorBackedDriver):
    """drivers/rawexec/driver.go — no isolation beyond its own session."""

    name = "raw_exec"


class ExecDriver(ExecutorBackedDriver):
    """drivers/exec/driver.go — full available isolation: cgroups,
    namespaces (+pid), chroot when privileged
    (`executor_linux.go:27-31`)."""

    name = "exec"

    def _isolation(self, cfg: TaskConfig) -> Dict[str, object]:
        rc = cfg.raw_config
        iso: Dict[str, object] = {
            "cgroup": True,
            "rlimit_memory": True,
            "namespaces": True,
            "pid_namespace": bool(rc.get("pid_namespace", True)),
            "nice": 0,
        }
        if rc.get("chroot", False):
            iso["chroot"] = cfg.task_dir
            paths = rc.get("chroot_paths")
            if paths:
                iso["chroot_paths"] = [str(p) for p in paths]
        if cfg.netns:
            # alloc network hook: join the pre-created per-alloc netns
            # (networking_bridge_linux.go; client/network.py)
            iso["netns"] = cfg.netns
        return iso
