"""Server-side node TTL heartbeats.

Behavioral reference: `nomad/heartbeat.go` (nodeHeartbeater :34,
resetHeartbeatTimer :90, invalidateHeartbeat :135): one TTL deadline per
node; a missed heartbeat marks the node down and triggers node evals (wired
by the server's `on_expire`).

The deadlines live in ONE `DelayHeap` watched by ONE thread, not in a
`threading.Timer` each: a timer is a thread, with its reserved stack, per
node — at 10,000 nodes ~80 GB of address space, which a machine with a
memory limit answers with SIGKILL.

What one thread costs: expiries run ONE AFTER ANOTHER on the watcher,
where a timer per node (the reference's one `AfterFunc` per node) runs
them side by side. Losing a rack of 1,000 nodes at once is 1,000
sequential `on_expire` calls — each a status write plus node evals — and
a deadline that falls due meanwhile waits behind them: nodes are declared
down late, never early and never not at all. If the commit latency of a
status write makes that wait matter, hand each expired batch to a small
pool or to one batched status update; not measured, so not built."""
from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Optional

from ..lib.delayheap import DelayHeap

log = logging.getLogger("nomad_tpu.server.heartbeat")


class HeartbeatTracker:
    def __init__(self, ttl: float, on_expire: Callable[[str], None]) -> None:
        self.ttl = ttl
        self.on_expire = on_expire
        self._cv = threading.Condition()
        self._deadlines = DelayHeap()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        with self._cv:
            if self._thread is not None:
                return
            self._thread = threading.Thread(
                target=self._run, name="heartbeat-ttl", daemon=True)
            self._thread.start()

    def shutdown(self) -> None:
        with self._cv:
            self._thread = None  # the watcher sees it is no longer current
            self._deadlines = DelayHeap()
            self._cv.notify_all()

    def reset(self, node_id: str) -> None:
        """(Re)arm the TTL deadline for a node (heartbeat.go:90)."""
        with self._cv:
            if self._thread is None:
                return
            idle = self._deadlines.peek() is None
            until = time.monotonic() + self.ttl
            if not self._deadlines.update(node_id, until):
                self._deadlines.push(node_id, until)
            if idle:
                # the TTL is one constant, so a reset only ever moves a
                # deadline later: the watcher's wake-up stays right unless
                # it was sleeping on an empty heap
                self._cv.notify_all()

    def remove(self, node_id: str) -> None:
        with self._cv:
            self._deadlines.remove(node_id)

    def _run(self) -> None:
        me = threading.current_thread()
        while True:
            with self._cv:
                if self._thread is not me:
                    return
                # popped under the same lock reset() takes: a heartbeat
                # racing its own expiry either re-arms first (the stale
                # entry is skipped) or arrives after the node is declared
                expired = self._deadlines.pop_expired(time.monotonic())
                if not expired:
                    head = self._deadlines.peek()
                    self._cv.wait(None if head is None else max(
                        head.wait_until - time.monotonic(), 0.0))
                    continue
            for item in expired:
                try:
                    self.on_expire(item.key)
                except Exception:  # noqa: BLE001 — one node's failed
                    # invalidation must not end TTL tracking for the rest
                    log.exception("heartbeat expiry of node %s failed",
                                  item.key)
