"""The allocations of every node row as arrays, in the order the state
lists them on their node: what the system scheduler packs its candidate
table from (`scheduler/system.py SystemTable.pack`) without walking the
rows' Allocation objects.

Each allocation the tensors have seen on a row takes a slot: one row of
`vals` (its row, the cpu / memory / disk of its `usage_row`, its job's
priority, its interned (namespace, job) and (namespace, job, task group)
ids, its group's migrate `max_parallel`, whether it is live and whether
it has a job) and one entry in its row's `row_slots` list. A row's list
keeps the order the state's per-node map keeps (`allocs_by_node`): a slot
joins at the end when its allocation first lands on the row, an update
leaves it where it is, a terminal allocation keeps its place (not live)
until it is deleted, and a move to another node takes it to the end of
that one. A freed slot is reused.

Derived state, never a second source: `ClusterTensors` keeps it on the
paths that keep `alloc_usage` and the port ledger, and a restore rebuilds
it through them. A row whose node's allocations the tensors cannot have
seen in full — an allocation upserted while its node had no row, or a
node removed with allocations and added again — is `partial`, and the
pack walks its objects.

Readers take no lock: `seq` is odd while a writer is inside, and a reader
that finds it moved across its reads reads again (`ClusterTensors` bumps
it around every method that writes here or to `capacity`).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from ..structs.alloc import Allocation

#: columns of `AllocIndex.vals`
ROW, CPU, MEM, DISK, PRIO, JOB, GROUP, MAX_PAR, LIVE, HAS_JOB = range(10)
N_COLS = 10


def max_parallel(alloc: Allocation) -> int:
    """The migrate `max_parallel` of the allocation's task group in the
    job it runs (0: none, or no job)."""
    job = alloc.job
    tg = job.lookup_task_group(alloc.task_group) if job is not None else None
    ms = tg.migrate_strategy if tg is not None else None
    return ms.max_parallel if ms else 0


class AllocIndex:
    """Slots of the allocations on every row (module docstring)."""

    def __init__(self, n_rows: int) -> None:
        self.vals = np.zeros((0, N_COLS), np.float64)
        #: slot -> allocation id (None: free)
        self.ids: List[Optional[str]] = []
        self.slot_of: Dict[str, int] = {}
        self.free: List[int] = []
        self.row_slots: List[List[int]] = [[] for _ in range(n_rows)]
        #: interned (namespace, job) and (namespace, job, task group)
        self.jobs: Dict[Tuple[str, str], int] = {}
        self.groups: Dict[Tuple[str, str, str], int] = {}
        self.group_keys: List[Tuple[str, str, str]] = []
        self.partial: Set[int] = set()
        #: node ids whose allocations a row could not hold (no row then)
        self.unrowed: Set[str] = set()
        self.seq = 0
        # the first slots come with the index, not inside the first plan
        # commit, which holds the plan applier's mutex
        self._grow()

    def _grow(self) -> None:
        n = len(self.ids)
        cap = max(2 * n, 1024)
        vals = np.zeros((cap, N_COLS), np.float64)
        vals[:n] = self.vals
        self.vals = vals
        self.ids.extend([None] * (cap - n))
        self.free.extend(range(cap - 1, n - 1, -1))

    def grow_rows(self, n_rows: int) -> None:
        self.row_slots.extend([] for _ in range(n_rows - len(self.row_slots)))

    def group_id(self, ns: str, job_id: str, tg: str) -> int:
        key = (ns, job_id, tg)
        g = self.groups.get(key)
        if g is None:
            g = self.groups[key] = len(self.group_keys)
            self.group_keys.append(key)
        return g

    def job_id(self, ns: str, job_id: str) -> int:
        key = (ns, job_id)
        j = self.jobs.get(key)
        if j is None:
            j = self.jobs[key] = len(self.jobs)
        return j

    def put(self, aid: str, row: int, alloc: Allocation,
            usage: Optional[np.ndarray]) -> None:
        """`alloc` on `row`: live with `usage` (its `usage_row`), or not
        live where `usage` is None."""
        s = self._slot(aid, row)     # first: it may grow `vals`
        if usage is None:
            self.vals[s, LIVE] = 0.0
            return
        job = alloc.job
        ns, jid = alloc.namespace, alloc.job_id
        self.vals[s] = (
            row, usage[0], usage[1], usage[2],
            job.priority if job is not None else 0, self.job_id(ns, jid),
            self.group_id(ns, jid, alloc.task_group), max_parallel(alloc),
            1.0, job is not None)

    def place(self, allocs: List[Allocation], rows: List[int],
              usages: List[np.ndarray]) -> None:
        """`put` of a plan's new live allocations on `rows` with their
        `usages` (`ClusterTensors.upsert_placements`), the job's fields
        read once a run of one job and no slot looked up twice."""
        while len(self.free) < len(allocs):
            self._grow()
        vals, ids, free = self.vals, self.ids, self.free
        slot_of, row_slots = self.slot_of, self.row_slots
        job, jkey, groups = None, None, {}
        for a, row, u in zip(allocs, rows, usages):
            aid = a.id
            if aid in slot_of:
                self.put(aid, row, a, u)
                continue
            if a.job is not job or (a.namespace, a.job_id) != jkey:
                job, jkey, groups = a.job, (a.namespace, a.job_id), {}
                prio = job.priority if job is not None else 0
                jix = self.job_id(*jkey)
            grp = groups.get(a.task_group)
            if grp is None:
                grp = groups[a.task_group] = (
                    self.group_id(*jkey, a.task_group), max_parallel(a))
            s = free.pop()
            slot_of[aid] = s
            ids[s] = aid
            row_slots[row].append(s)
            vals[s] = (row, u[0], u[1], u[2], prio, jix, grp[0], grp[1],
                       1.0, job is not None)

    def _slot(self, aid: str, row: int) -> int:
        """The allocation's slot on `row`: a new one at the end of the
        row's list, or its own, taken to the end of `row`'s list if it
        was on another row."""
        s = self.slot_of.get(aid)
        if s is None:
            if not self.free:
                self._grow()
            s = self.free.pop()
            self.slot_of[aid] = s
            self.ids[s] = aid
            self.vals[s, ROW] = row
            self.row_slots[row].append(s)
        elif self.vals[s, ROW] != row:
            self.row_slots[int(self.vals[s, ROW])].remove(s)
            self.vals[s, ROW] = row
            self.row_slots[row].append(s)
        return s

    def drop(self, aid: str) -> None:
        """The allocation leaves the index (deleted, or its node has no
        row)."""
        s = self.slot_of.pop(aid, None)
        if s is None:
            return
        self.row_slots[int(self.vals[s, ROW])].remove(s)
        self.vals[s] = 0.0
        self.ids[s] = None
        self.free.append(s)

    def drop_row(self, row: int, node_id: str) -> None:
        """The node on `row` is removed: its slots go, and the state that
        keeps its allocations makes the row partial if the node comes
        back."""
        slots = self.row_slots[row]
        if slots or row in self.partial:
            self.unrowed.add(node_id)
        for s in slots:
            del self.slot_of[self.ids[s]]
            self.vals[s] = 0.0
            self.ids[s] = None
            self.free.append(s)
        self.row_slots[row] = []
        self.partial.discard(row)

    def node_row(self, row: int, node_id: str) -> None:
        """A row is taken by `node_id`."""
        if node_id in self.unrowed:
            self.partial.add(row)
