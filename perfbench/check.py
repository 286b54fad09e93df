"""The comparison that decides `correct`.

What is compared is what the timed path itself produced: the allocations of
the jobs the window drove through `PUT /v1/jobs`, read back over HTTP once
the window has closed. The plain reference (`reference.py`) replays every
job of the run in the order in which its eval was enqueued (the index of
the eval's first event) — the serial order the configuration guarantees —
on the benchmark's own copy of the cluster, applying the SERVED node of
each allocation, and for a sample of the window's evals drawn from the seed
asks, before each allocation is applied, what it would have chosen there:

  score_gap_max        widest gap by which the served node's score lies
                       below the reference's best feasible score
  score_dev_max_pct    widest deviation of the normalized score the program
                       reports for the served node from the reference's, in
                       per cent of the reference's (BASELINE.json: 1 %)
  infeasible           sampled allocations on a node the reference finds
                       infeasible (datacenter, constraint, distinct_hosts,
                       distinct_property, no room, no free GPU)
  short_with_room      sampled evals whose job read back with fewer
                       allocations than it asked for although the reference
                       still finds a feasible node for the next one
  overcommitted_nodes  nodes past their usable cpu, memory or disk once
                       every allocation of the run is applied
  gpu_double_assigned  GPU instances that two allocations hold
  unanswered           requests of the window that no terminal eval ever
                       answered (late is late, not wrong; never is wrong)

Every number has its limit in `limits/<workload>.json`; `correct` is all of
them within it.
"""
from __future__ import annotations

import random
from typing import Callable, Dict, List, Optional

import numpy as np

from cluster import Cluster
from reference import Reference, exact, gap_of


def replay(cluster: Cluster, jobs: List[dict], sample_ids: set,
           rnd_control: Optional[Callable] = None) -> dict:
    """`jobs`: every job of the run in the order of its eval, each
    {"spec": plain spec, "allocs": [{"index", "node", "norm_score",
    "device_ids"}] as read back}. Returns the numbers and the state."""
    ref = Reference(cluster)
    gap_max = 0.0
    dev_max = 0.0
    infeasible = 0
    compared = 0
    control = {"score_gap_max": 0.0, "score_dev_max_pct": 0.0,
               "infeasible": 0}
    short_with_room = 0
    worst: Dict[str, object] = {}
    gpu_seen: Dict[str, int] = {}
    for job in jobs:
        spec = job["spec"]
        sampled = spec["id"] in sample_ids
        for a in sorted(job["allocs"], key=lambda a: a["index"]):
            node = cluster.index_of.get(a["node"])
            if node is None:
                infeasible += sampled
                continue
            for g in a.get("device_ids") or ():
                gpu_seen[g] = gpu_seen.get(g, 0) + 1
            if sampled:
                feas, final = ref.select(spec, exact)
                gap = gap_of(feas, final, node)
                compared += 1
                if gap is None:
                    infeasible += 1
                    worst.setdefault("infeasible", {
                        "job": spec["id"], "kind": spec["kind"],
                        "alloc": a["index"], "node": node})
                else:
                    if gap > gap_max:
                        gap_max = gap
                        worst["gap"] = {"job": spec["id"],
                                        "kind": spec["kind"],
                                        "alloc": a["index"], "node": node,
                                        "best": int(np.argmax(
                                            np.where(feas, final, -np.inf)))}
                    if a.get("norm_score") is not None:
                        dev = score_dev_pct(a["norm_score"],
                                            float(final[node]))
                        if dev > dev_max:
                            dev_max = dev
                            worst["dev"] = {"job": spec["id"],
                                            "kind": spec["kind"],
                                            "alloc": a["index"],
                                            "served": a["norm_score"],
                                            "reference": float(final[node])}
                if rnd_control is not None:
                    # the control in the program's place: what the lower
                    # precision would have put first, judged by the
                    # reference
                    cfeas, cfinal = ref.select(spec, rnd_control)
                    if cfeas.any():
                        pick = int(np.argmax(np.where(cfeas, cfinal,
                                                      -np.inf)))
                        # the score it would report, wherever it lands
                        control["score_dev_max_pct"] = max(
                            control["score_dev_max_pct"],
                            score_dev_pct(float(cfinal[pick]),
                                          float(final[pick])))
                        cgap = gap_of(feas, final, pick)
                        if cgap is None:
                            control["infeasible"] += 1
                        else:
                            control["score_gap_max"] = max(
                                control["score_gap_max"], cgap)
            ref.place(spec, node)
        if sampled and len(job["allocs"]) < spec["count"] \
                and ref.select(spec, exact)[0].any():
            short_with_room += 1
            worst.setdefault("short", {"job": spec["id"],
                                       "kind": spec["kind"],
                                       "placed": len(job["allocs"])})
        ref.forget(spec["id"])
    over = int(((ref.used - cluster.cap) > 1e-6).any(axis=1).sum())
    out = {"score_gap_max": gap_max, "score_dev_max_pct": dev_max,
           "infeasible": infeasible, "short_with_room": short_with_room,
           "overcommitted_nodes": over,
           "gpu_double_assigned": sum(1 for v in gpu_seen.values() if v > 1),
           "compared": compared, "worst": worst,
           "fill": cluster.fill(ref.used),
           "gpus_in_use": int(sum(gpu_seen.values()))}
    if rnd_control is not None:
        out["control"] = control
    return out


def score_dev_pct(reported: float, reference: float) -> float:
    """A reported normalized score's distance from the reference's, in per
    cent of the reference's."""
    return 100.0 * abs(reported - reference) / max(abs(reference), 1e-9)


def draw_sample(job_ids: List[str], longest: Optional[str], n: int,
                seed: int) -> set:
    """`n` of the window's answered evals, drawn from the seed, the one
    with most allocations among them."""
    ids = sorted(job_ids)
    random.Random(f"{int(seed)}/sample").shuffle(ids)
    out = set(ids[:n])
    if longest is not None:
        out.add(longest)
    return out


def verdict(numbers: dict, limits: dict) -> Dict[str, dict]:
    """{name: {"value", "limit", "ok"}} for every number that has a limit;
    a number is within its limit when value <= limit."""
    return {name: {"value": numbers[name], "limit": limit,
                   "ok": bool(numbers[name] <= limit)}
            for name, limit in limits.items()}
