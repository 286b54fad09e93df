"""The plain reference: Nomad's generic-scheduler placement in NumPy float64.

One `Select` for one allocation of one job over the whole node table, step
by step as the reference's iterator chain has it (`scheduler/stack.go`
GenericStack.Select, `feasible.go`, `rank.go`, `spread.go`; the repo's scalar
copy is `nomad_tpu/scheduler/oracle.py`, which this file neither imports nor
calls): datacenter, constraints, distinct_hosts, distinct_property, fit in
cpu / memory / disk, free GPUs; then the mean of the score components that
apply to a node — bin-pack fitness, job anti-affinity, node affinity, spread.

It reads the benchmark's own plain records (`cluster.py`), nothing the
program has made. `rnd` rounds after every arithmetic step: the identity for
the reference, a round trip through bfloat16 for the control (the nearest
precision below the kernels' float32 at `Precision.HIGHEST`).

A system job (`scheduler/system_sched.go`, with `SchedulerConfiguration`'s
system preemption on, upstream's default) ranks nothing: every node of its
datacenters that passes its constraints gets one allocation, where the ask
fits as the state stands (`system_nodes`: `must`) or fits once the live
allocations that it may evict are gone (`may_preempt`). It may evict an
allocation whose job's priority is at least `PRIORITY_DELTA` below its own
(`preemption.go`: candidates within a delta of 10 are skipped).
`judge_preemption` says why a victim set on one node is wrong: a victim not
live there, one of too high a priority, an eviction where the job fit
without one, a set after which it still does not fit, and a set that is not
minimal as upstream's `filterSuperset` makes it minimal: it adds the
victims back by their distance to the ask (`basic_distance`), far to near,
until the ask fits, so the victim nearest the ask is one the job needs.
That is all it guarantees: a farther victim may be one the job could do
without (ask 290 MHz on a full node, victims of 110 and 290 MHz: the 290
alone would do, and upstream evicts both). It decides legality,
sufficiency and that minimality; it does not decide which set upstream's
greedy order picks.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from cluster import JOB_DEFAULTS, Cluster, job_priority

BINPACK_MAX_FIT_SCORE = 18.0
#: a victim's job priority is at most the preempting job's less this
PRIORITY_DELTA = 10


def exact(a):
    return a


def bf16(a):
    """Round to bfloat16 and back: eight significant bits."""
    import ml_dtypes

    return np.asarray(a, dtype=np.float64).astype(
        ml_dtypes.bfloat16).astype(np.float64)


def _compare(op: str, col: np.ndarray, rval: str) -> np.ndarray:
    """`feasible.go` checkConstraint for a literal right side: equality on
    strings, and the four orderings LEXICAL, as the reference has them."""
    if op in ("=", "==", "is"):
        return col == rval
    if op in ("!=", "not"):
        return col != rval
    if op == "<":
        return col < rval
    if op == "<=":
        return col <= rval
    if op == ">":
        return col > rval
    if op == ">=":
        return col >= rval
    raise ValueError(f"operand {op!r} is not in the benchmark's jobs")


class Reference:
    """Cluster state as the serial schedule leaves it, one Select, and a
    system job's nodes and victims."""

    def __init__(self, cluster: Cluster) -> None:
        self.c = cluster
        self.used = cluster.used.copy()
        self.gpu_free = cluster.gpus.copy()
        #: job id -> {node index: allocations of that job there}
        self.own: Dict[str, Dict[int, int]] = {}
        self._static: Dict[str, np.ndarray] = {}
        #: the live allocations that have an id, for the system job and its
        #: victims: id -> (node, (cpu, memory, disk, GPUs), priority). The
        #: fillers are the default job's (`launcher.load_cluster`), at its
        #: priority, entered when a system job or an eviction first asks.
        self._live: Dict[str, Tuple[int, tuple, int]] = {}
        self._fillers_entered = False

    # ---- state ----

    def place(self, job: dict, node: int, alloc_id: Optional[str] = None
              ) -> None:
        self.used[node] += (job["cpu"], job["memory"], job["disk"])
        self.gpu_free[node] -= job["gpus"]
        own = self.own.setdefault(job["id"], {})
        own[node] = own.get(node, 0) + 1
        if alloc_id is not None:
            self._live[alloc_id] = (node, (job["cpu"], job["memory"],
                                           job["disk"], job["gpus"]),
                                    job_priority(job))

    def evict(self, alloc_id: str) -> bool:
        """Free a live allocation; False where there is none of that id."""
        rec = self.live().pop(alloc_id, None)
        if rec is None:
            return False
        node, ask, _prio = rec
        self.used[node] -= ask[:3]
        self.gpu_free[node] += ask[3]
        return True

    def live(self) -> Dict[str, Tuple[int, tuple, int]]:
        """id -> (node, ask, priority) of every live allocation with an
        id, the fillers entered on the first call."""
        if not self._fillers_entered:
            self._fillers_entered = True
            prio = JOB_DEFAULTS["priority"]
            for f in self.c.fillers:
                self._live[f["id"]] = (
                    f["node"], (f["cpu"], f["memory"], f["disk"], 0), prio)
        return self._live

    def forget(self, job_id: str) -> None:
        """Drop a finished job's own-allocation map (its allocations stay
        in `used`): nothing reads it once the job's eval is through."""
        self.own.pop(job_id, None)
        self._static.pop(job_id, None)

    # ---- one Select ----

    def static_mask(self, job: dict) -> np.ndarray:
        m = self._static.get(job["id"])
        if m is None:
            cols = self.c.columns
            m = np.isin(cols["${node.datacenter}"], job["datacenters"])
            for lt, op, rv in job["constraints"]:
                m &= _compare(op, cols[lt], rv)
            self._static[job["id"]] = m
        return m

    def select(self, job: dict, rnd: Callable = exact
               ) -> Tuple[np.ndarray, np.ndarray]:
        """(feasible bool[N], final score f64[N]) for the job's next
        allocation against the state as it stands."""
        c = self.c
        n = len(c.nodes)
        own = np.zeros(n)
        for i, k in self.own.get(job["id"], {}).items():
            own[i] = k
        feas = self.static_mask(job).copy()
        if job["distinct_hosts"]:
            feas &= own == 0
        if job["distinct_property"]:
            target, allowed = job["distinct_property"]
            col = c.columns[target]
            seen: Dict[str, int] = {}
            for i, k in self.own.get(job["id"], {}).items():
                seen[col[i]] = seen.get(col[i], 0) + int(k)
            full = [v for v, k in seen.items() if k >= allowed]
            if full:
                feas &= ~np.isin(col, full)
        ask = np.array([job["cpu"], job["memory"], job["disk"]],
                       dtype=np.float64)
        util = rnd(rnd(self.used) + rnd(ask))
        cap = rnd(c.cap)
        feas &= (util <= cap).all(axis=1)
        if job["gpus"]:
            feas &= self.gpu_free >= job["gpus"]

        # rank.go BinPackIterator + structs.ScoreFit (funcs.go:175)
        free_cpu = rnd(1.0 - rnd(util[:, 0] / cap[:, 0]))
        free_mem = rnd(1.0 - rnd(util[:, 1] / cap[:, 1]))
        total = rnd(rnd(np.power(10.0, free_cpu))
                    + rnd(np.power(10.0, free_mem)))
        fitness = np.clip(rnd(20.0 - total), 0.0, BINPACK_MAX_FIT_SCORE)
        score = rnd(fitness / BINPACK_MAX_FIT_SCORE)
        parts = np.ones(n)

        # JobAntiAffinityIterator (rank.go:505)
        hit = own > 0
        if hit.any():
            pen = rnd(-1.0 * (own + 1.0) / max(job["count"], 1))
            score = np.where(hit, rnd(score + pen), score)
            parts = parts + hit

        # NodeAffinityIterator (rank.go:640)
        if job["affinities"]:
            sum_w = sum(abs(float(w)) for _l, _o, _r, w in job["affinities"])
            tot = np.zeros(n)
            for lt, op, rv, w in job["affinities"]:
                tot = tot + np.where(_compare(op, c.columns[lt], rv),
                                     float(w), 0.0)
            hit = tot != 0.0
            score = np.where(hit, rnd(score + rnd(tot / sum_w)), score)
            parts = parts + hit

        # SpreadIterator (spread.go:110), targets in percent
        sp = job["spread"]
        if sp:
            col = c.columns[sp["attribute"]]
            use: Dict[str, int] = {}
            for i, k in self.own.get(job["id"], {}).items():
                use[col[i]] = use.get(col[i], 0) + int(k)
            desired = {v: (p / 100.0) * job["count"]
                       for v, p in sp["targets"]}
            s = sum(desired.values())
            implicit = job["count"] - s if 0 < s < job["count"] else None
            boost = np.full(n, -1.0)
            for v in np.unique(col):
                d = desired.get(v, implicit)
                if d is None or d <= 0:
                    continue
                boost[col == v] = rnd(
                    (d - (use.get(v, 0) + 1)) / d)   # weight / sum = 1
            hit = boost != 0.0
            score = np.where(hit, rnd(score + boost), score)
            parts = parts + hit

        return feas, rnd(score / parts)

    # ---- a system job ----

    def _fits(self, job: dict, freed: np.ndarray, rows=slice(None),
              rnd: Callable = exact) -> np.ndarray:
        """Where, among `rows`, the job's ask fits once `freed` (f64[rows,
        4]: cpu, memory, disk, GPUs) is given back; used, freed, ask and
        capacity rounded by `rnd` as `select` rounds them (GPUs are
        counts)."""
        ask = np.array([job["cpu"], job["memory"], job["disk"]],
                       dtype=np.float64)
        util = rnd(rnd(rnd(self.used[rows]) - rnd(freed[:, :3])) + rnd(ask))
        return ((util <= rnd(self.c.cap[rows])).all(axis=1)
                & (self.gpu_free[rows] + freed[:, 3] >= job["gpus"]))

    def system_nodes(self, job: dict, rnd: Callable = exact
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """(must bool[N], may_preempt bool[N]) for a system job against the
        state as it stands: the nodes that pass its datacenters and
        constraints where the ask fits, and those where it fits only once
        every live allocation it may evict there is gone; computed with
        `rnd` (`bf16`: the control)."""
        n = len(self.c.nodes)
        static = self.static_mask(job)
        top = job_priority(job) - PRIORITY_DELTA
        evictable = [(node, ask) for node, ask, prio in self.live().values()
                     if prio <= top]
        freed = np.zeros((n, 4))
        if evictable:
            np.add.at(freed, [node for node, _a in evictable],
                      np.array([ask for _n, ask in evictable],
                               dtype=np.float64))
        must = static & self._fits(job, np.zeros((n, 4)), rnd=rnd)
        return must, static & ~must & self._fits(job, freed, rnd=rnd)

    def judge_preemption(self, job: dict, node: int, victims
                         ) -> List[str]:
        """The reasons why evicting `victims` (allocation ids) on `node`
        for one allocation of `job` is wrong; none where it is right."""
        why = []
        zero = np.zeros(4)

        def fits(freed: np.ndarray) -> bool:
            return bool(self._fits(job, freed[None, :], [node])[0])

        if fits(zero):
            why.append("fit without evicting")
        asks = []
        live = self.live()
        for v in victims:
            rec = live.get(v)
            if rec is None or rec[0] != node:
                why.append(f"{v} is not live on the node")
                continue
            if rec[2] > job_priority(job) - PRIORITY_DELTA:
                why.append(f"{v} has priority {rec[2]}")
            asks.append(np.array(rec[1], dtype=np.float64))
        total = sum(asks, zero)
        if not fits(total):
            why.append("does not fit after the eviction")
        elif asks:
            far = [basic_distance(job, a) for a in asks]
            near = [a for a, d in zip(asks, far) if d == min(far)]
            if all(fits(total - a) for a in near):
                why.append("the victim nearest the ask could be given back")
        return why


def basic_distance(job: dict, used: np.ndarray) -> float:
    """Upstream's `basicResourceDistance` (`preemption.go`): how far an
    allocation's cpu, memory and disk lie from the job's ask, each as a
    share of the ask."""
    coords = [(job[d] - u) / job[d] for d, u in
              zip(("cpu", "memory", "disk"), used[:3]) if job[d] > 0]
    return float(np.sqrt(sum(c * c for c in coords)))


def gap_of(feas: np.ndarray, final: np.ndarray, node: int
           ) -> Optional[float]:
    """How far the node's score lies below the best feasible score; None
    where the reference finds the node infeasible."""
    if not feas[node]:
        return None
    return float(final[feas].max() - final[node])
