"""Task bootstrap: the process the executor forks to become the task.

Reference analog: libcontainer's nsenter/standard_init_linux.go — the
in-between stage that enters namespaces, joins cgroups, applies limits,
drops privileges, then execs the real task command. Run as

    python -m nomad_tpu.plugins.taskinit <spec.json>

so the setup happens in a fresh single-threaded process (doing unshare +
mounts in a `preexec_fn` of the multi-threaded executor would risk
post-fork malloc deadlocks).

The spec arrives as JSON in $NOMAD_TASKINIT_SPEC (argv[1] fallback for
direct invocation).

Spec (JSON):
  command, args, env, cwd, user
  cgroup: {name, version}            join this (pre-created) cgroup
  rlimit_memory_mb, rlimit_nofile
  nice
  namespaces: bool                   unshare mount+IPC+UTS
  pid_namespace: bool                extra CLONE_NEWPID + fork layer
  chroot: str | null                 chroot into this dir (bind list below)
  chroot_paths: [str] | null

With pid_namespace the exec'd task is necessarily a *child* (CLONE_NEWPID
applies to children of the unshare caller), so this process stays resident
as a minimal init: it forwards SIGTERM/SIGINT, reaps, and exits with the
task's code — the executor's view (one pid, one exit) is unchanged.
"""
from __future__ import annotations

import json
import os
import signal
import sys

from . import isolation


def _exec_task(spec: dict) -> None:
    cmd = spec["command"]
    args = [cmd] + [str(a) for a in spec.get("args", [])]
    env = spec.get("env") or {}
    cwd = spec.get("cwd")
    if cwd:
        os.chdir(cwd)
    # rlimits go last: RLIMIT_AS below the Python VM's own VA size would
    # make any further fork/allocation fail — exec resets the image, so
    # the limit only ever constrains the task itself
    isolation.apply_rlimits(spec.get("rlimit_memory_mb", 0),
                            spec.get("rlimit_nofile", 0))
    os.execvpe(cmd, args, env)


def main() -> None:
    raw = os.environ.pop("NOMAD_TASKINIT_SPEC", "")
    if raw:
        spec = json.loads(raw)
    else:
        with open(sys.argv[1]) as fh:
            spec = json.load(fh)

    # the executor's launch() returns at end-of-file on this pipe: the
    # exec closes it (and so does dying before it)
    ready_fd = spec.get("ready_fd")
    if ready_fd is not None:
        os.set_inheritable(ready_fd, False)

    os.setsid()

    cg = spec.get("cgroup")
    if cg:
        g = isolation.Cgroup.attach_existing(cg["name"], cg.get("version"))
        g.add_pid(os.getpid())

    if spec.get("nice"):
        try:
            os.nice(int(spec["nice"]))
        except OSError:
            pass

    # load libc BEFORE entering namespaces (see isolation._get_libc —
    # nothing may spawn helper children once CLONE_NEWPID is unshared)
    isolation._get_libc()

    netns_path = spec.get("netns")
    if netns_path:
        # join the alloc's PRE-CREATED network namespace (bridge
        # networking, client/network.py) BEFORE unsharing the others —
        # setns(CLONE_NEWNET) applies to this process immediately
        fd = os.open(netns_path, os.O_RDONLY)
        try:
            rc = isolation._get_libc().setns(fd, 0)
            if rc != 0:
                raise OSError(f"setns({netns_path}) failed")
        finally:
            os.close(fd)

    flags = 0
    if spec.get("namespaces"):
        flags |= os.CLONE_NEWNS | os.CLONE_NEWIPC | os.CLONE_NEWUTS
    if spec.get("pid_namespace"):
        flags |= os.CLONE_NEWPID
    if flags:
        os.unshare(flags)
        if flags & os.CLONE_NEWNS:
            isolation.make_mounts_private()

    chroot_dir = spec.get("chroot")
    if chroot_dir and spec.get("namespaces"):
        isolation.setup_chroot(chroot_dir, spec.get("chroot_paths"))
        spec["cwd"] = spec.get("chroot_cwd") or "/"

    if spec.get("pid_namespace"):
        # become init of the new pid namespace via one fork; stay behind
        # as signal-forwarder/reaper
        pid = os.fork()
        if pid == 0:
            if spec.get("namespaces"):
                try:
                    isolation.mount_proc("/proc")
                except OSError:
                    pass
            if spec.get("user"):
                isolation.drop_user(spec["user"])
            _exec_task(spec)
            os._exit(127)

        def forward(signum, _frame):
            try:
                os.kill(pid, signum)
            except ProcessLookupError:
                pass

        signal.signal(signal.SIGTERM, forward)
        signal.signal(signal.SIGINT, forward)
        if ready_fd is not None:
            os.close(ready_fd)  # ours; the child's goes with its exec
        while True:
            try:
                done, status = os.waitpid(pid, 0)
            except InterruptedError:
                continue
            except ChildProcessError:
                os._exit(0)
            if done == pid:
                if os.WIFSIGNALED(status):
                    # propagate death-by-signal to the executor
                    signal.signal(os.WTERMSIG(status), signal.SIG_DFL)
                    os.kill(os.getpid(), os.WTERMSIG(status))
                os._exit(os.WEXITSTATUS(status))
    else:
        if spec.get("user"):
            isolation.drop_user(spec["user"])
        _exec_task(spec)


if __name__ == "__main__":
    main()
