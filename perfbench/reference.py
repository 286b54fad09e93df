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
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import numpy as np

from cluster import Cluster

BINPACK_MAX_FIT_SCORE = 18.0


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
    """Cluster state as the serial schedule leaves it, and one Select."""

    def __init__(self, cluster: Cluster) -> None:
        self.c = cluster
        self.used = cluster.used.copy()
        self.gpu_free = cluster.gpus.copy()
        #: job id -> {node index: allocations of that job there}
        self.own: Dict[str, Dict[int, int]] = {}
        self._static: Dict[str, np.ndarray] = {}

    # ---- state ----

    def place(self, job: dict, node: int) -> None:
        self.used[node] += (job["cpu"], job["memory"], job["disk"])
        self.gpu_free[node] -= job["gpus"]
        own = self.own.setdefault(job["id"], {})
        own[node] = own.get(node, 0) + 1

    def forget(self, job_id: str) -> None:
        """Drop a finished job's own-allocation map (its allocations stay
        in `used`): nothing reads it once the job's eval is through."""
        self.own.pop(job_id, None)
        self._static.pop(job_id, None)

    # ---- one Select ----

    def _static_mask(self, job: dict) -> np.ndarray:
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
        feas = self._static_mask(job).copy()
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


def gap_of(feas: np.ndarray, final: np.ndarray, node: int
           ) -> Optional[float]:
    """How far the node's score lies below the best feasible score; None
    where the reference finds the node infeasible."""
    if not feas[node]:
        return None
    return float(final[feas].max() - final[node])
