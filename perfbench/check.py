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
                       every allocation of the run is applied and every
                       victim of a preemption freed
  gpu_double_assigned  GPU instances that two allocations hold at once (an
                       evicted allocation's are free from its eviction on)
  unanswered           requests of the window that no terminal eval ever
                       answered (late is late, not wrong; never is wrong)

A system job (`reference.system_nodes`) is judged whole, in every run and
whether sampled or not, against the state at its turn; none of the numbers
above but `overcommitted_nodes` reads it:

  system_missing       nodes where the reference places it — the ask fits,
                       or fits once what it may evict is gone (system
                       preemption is on) — that got no allocation
  system_extra         allocations on a node that is neither, and second
                       allocations of the job on one node
  preempt_wrong        allocations whose victims `reference.judge_preemption`
                       refuses, a node that needed an eviction served with
                       none among them; the victims a service allocation
                       names are judged alike

An allocation's victims are freed at its turn, before it is placed. Every
number has its limit in `limits/<workload>.json`; `correct` is all of them
within it. A limits file that names none of the system numbers judges a run
of service jobs exactly as before they existed.

The control (`rnd_control`: the reference in bfloat16 in the program's
place) reads `score_gap_max`, `score_dev_max_pct` and `infeasible` on the
sampled service allocations and, where the run has a system job,
`system_missing`, `system_extra` and `preempt_wrong` on every system job
(`_control_system`).

A system job is judged whole at its place in `serial_order`. That is exact
where its allocations were committed at one index, or at several with no
other job's commit between them; `system_interleaved` counts the system
jobs of which that is not true (a plan refused in part and planned again
while other plans committed), whose verdict may then be off either way.
"""
from __future__ import annotations

import random
import re
from typing import Callable, Dict, List, Optional

import numpy as np

from cluster import Cluster, job_priority, job_type
from reference import Reference, exact, gap_of

ALLOC_INDEX = re.compile(r"\[(\d+)\]$")


def alloc_record(a: dict) -> Optional[dict]:
    """What the replay reads of one allocation, from its wire form (the
    HTTP API's JSON, or `to_wire` of the store's `Allocation`); None where
    it is neither running nor evicted by a later allocation."""
    if a.get("desired_status") not in ("run", "evict"):
        return None
    m = ALLOC_INDEX.search(a.get("name", ""))
    score = None
    for sm in (a.get("metrics") or {}).get("score_meta") or ():
        if sm.get("node_id") == a.get("node_id"):
            score = sm.get("norm_score")
    gpus = []
    tasks = (a.get("allocated_resources") or {}).get("tasks") or {}
    for t in tasks.values():
        for d in t.get("devices") or ():
            gpus.extend(d.get("device_ids") or ())
    return {"index": int(m.group(1)) if m else 0,
            "node": a.get("node_id"), "norm_score": score,
            "device_ids": gpus, "id": a.get("id"),
            "preempted": list(a.get("preempted_allocations") or ()),
            "commit": a.get("create_index"),
            "evicted": a.get("desired_status") == "evict"}


def replay(cluster: Cluster, jobs: List[dict], sample_ids: set,
           rnd_control: Optional[Callable] = None) -> dict:
    """`jobs`: every job of the run in the order of its eval
    (`serial_order`), each {"spec": plain spec, "allocs": [{"index",
    "node", "norm_score", "device_ids"}, and where read: "id",
    "preempted" (victim ids), "evicted"] as read back}. Returns the numbers
    and the state."""
    ref = Reference(cluster)
    gap_max = 0.0
    dev_max = 0.0
    infeasible = 0
    compared = 0
    control = {"score_gap_max": 0.0, "score_dev_max_pct": 0.0,
               "infeasible": 0}
    short_with_room = 0
    worst: Dict[str, object] = {}
    gpus = Instances()
    system = {"system_missing": 0, "system_extra": 0, "preempt_wrong": 0,
              "system_placed": 0, "system_refused": 0, "evictions": 0}
    for job in jobs:
        spec = job["spec"]
        if job_type(spec) == "system":
            if rnd_control is not None:
                _control_system(ref, spec, rnd_control, control)
            _replay_system(cluster, ref, spec, job["allocs"], system, worst,
                           gpus)
            ref.forget(spec["id"])
            continue
        sampled = spec["id"] in sample_ids
        for a in sorted(job["allocs"], key=lambda a: a["index"]):
            node = cluster.index_of.get(a["node"])
            if node is None:
                infeasible += sampled
                continue
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
            _evict(ref, spec, node, a, system, worst, gpus)
            gpus.take(a)
            ref.place(spec, node, a.get("id"))
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
           "gpu_double_assigned": len(gpus.double),
           **system, "system_interleaved": interleaved(jobs),
           "compared": compared, "worst": worst,
           "fill": cluster.fill(ref.used),
           "gpus_in_use": gpus.taken}
    if rnd_control is not None:
        out["control"] = control
    return out


class Instances:
    """GPU instances and the live allocation that holds each."""

    def __init__(self) -> None:
        self.holder: Dict[str, Optional[str]] = {}
        self.held_by: Dict[str, tuple] = {}
        self.double: set = set()
        self.taken = 0

    def take(self, alloc: dict) -> None:
        ids = tuple(alloc.get("device_ids") or ())
        for g in ids:
            if g in self.holder:
                self.double.add(g)
            self.holder[g] = alloc.get("id")
        self.taken += len(ids)
        if ids and alloc.get("id") is not None:
            self.held_by[alloc["id"]] = ids

    def release(self, alloc_id: str) -> None:
        for g in self.held_by.pop(alloc_id, ()):
            if self.holder.get(g) == alloc_id:
                del self.holder[g]


def _evict(ref: Reference, spec: dict, node: int, alloc: dict,
           system: dict, worst: dict, gpus: Instances,
           needed: bool = False) -> None:
    """Judge and free the victims that `alloc` names, at its turn, their
    GPU instances with them; judge their absence too where the node
    `needed` an eviction."""
    victims = alloc.get("preempted") or ()
    if not victims and not needed:
        return
    why = ref.judge_preemption(spec, node, victims)
    if why:
        system["preempt_wrong"] += 1
        worst.setdefault("preempt", {"job": spec["id"], "kind": spec["kind"],
                                     "node": node, "why": why})
    for v in victims:
        system["evictions"] += ref.evict(v)
        gpus.release(v)


def _control_system(ref: Reference, spec: dict, rnd: Callable,
                    control: dict) -> None:
    """The control in the program's place for one system job, against the
    reference at the same turn: `system_nodes` computed with `rnd`, and
    system preemption off, the guarantee a faster system eval would be
    tempted to drop (evictions are most of its wall). It serves the nodes
    it finds room on, evicting nothing: a node the reference serves that it
    does not is `system_missing`, one it serves that the reference does not
    `system_extra`, and one it serves where the reference has to evict
    `preempt_wrong`. Rounding alone misjudges only the nodes whose used +
    ask lies within a bfloat16 step of the capacity, none on some seeds.
    Its numbers enter the control with the first system job, so a run of
    service jobs reads the three it did."""
    must, may = ref.system_nodes(spec)
    served = ref.system_nodes(spec, rnd)[0]
    want = must | may
    for name, count in (("system_missing", want & ~served),
                        ("system_extra", served & ~want),
                        ("preempt_wrong", may & served)):
        control[name] = control.get(name, 0) + int(count.sum())


def _replay_system(cluster: Cluster, ref: Reference, spec: dict,
                   allocs: List[dict], system: dict, worst: dict,
                   gpus: Instances) -> None:
    """One system job: the nodes it served against `system_nodes` at its
    turn; each allocation's victims judged and freed, then it is placed."""
    must, may = ref.system_nodes(spec)
    want = must | may
    served = np.zeros(len(cluster.nodes), dtype=bool)
    system["system_refused"] += int((ref.static_mask(spec) & ~want).sum())
    for a in allocs:
        node = cluster.index_of.get(a["node"])
        if node is None or served[node] or not want[node]:
            system["system_extra"] += 1
            worst.setdefault("system_extra", {
                "job": spec["id"], "kind": spec["kind"], "node": node})
            if node is None:
                continue
        served[node] = True
        _evict(ref, spec, node, a, system, worst, gpus,
               needed=bool(may[node]))
        gpus.take(a)
        ref.place(spec, node, a.get("id"))
        system["system_placed"] += 1
    missing = want & ~served
    if missing.any():
        system["system_missing"] += int(missing.sum())
        node = int(np.argmax(missing))
        worst.setdefault("system_missing", {
            "job": spec["id"], "kind": spec["kind"], "node": node,
            "may_preempt": bool(may[node])})


def serial_order(jobs: List[dict]) -> List[dict]:
    """The jobs (each {"spec", "allocs", "enqueued", "finished"}) in the
    order they were scheduled. Evals of one priority are scheduled in the
    order they were enqueued (`enqueued`: the index of the eval's first
    event), so a run of one priority is in that order, exactly. The broker
    hands out a higher priority first, whenever it was enqueued: across
    priorities, the per-priority sequences are merged by the index at which
    each job's first allocation was committed (`"commit"` of its
    allocations), or, where it placed none, by that of its terminal event.

    The merge assumes that within a priority the first commits come in the
    order enqueued, as they do where one priority's evals are scheduled one
    at a time or in batches that commit in order, and it places a job
    whole at its first commit (`interleaved` counts where that is not
    so). Traffic that sends several priorities is judged exactly where both
    hold; a cell that mixes them reads `system_interleaved` at 0 first."""
    by_prio: Dict[int, List[dict]] = {}
    for j in sorted(jobs, key=lambda j: j["enqueued"]):
        by_prio.setdefault(job_priority(j["spec"]), []).append(j)
    if len(by_prio) <= 1:
        return [j for seq in by_prio.values() for j in seq]

    def when(j: dict) -> float:
        commits = [a["commit"] for a in j["allocs"] if a.get("commit")]
        at = min(commits) if commits else j.get("finished")
        return float("inf") if at is None else at

    heads = {p: 0 for p in by_prio}
    out = []
    while heads:
        p = min(heads, key=lambda q: (when(by_prio[q][heads[q]]), -q))
        out.append(by_prio[p][heads[p]])
        heads[p] += 1
        if heads[p] == len(by_prio[p]):
            del heads[p]
    return out


def interleaved(jobs: List[dict]) -> int:
    """System jobs with another job's allocation committed between their
    own first and last commit (the allocations' `"commit"` indexes)."""
    owner, at = [], []
    for i, j in enumerate(jobs):
        for a in j["allocs"]:
            if a.get("commit"):
                owner.append(i)
                at.append(a["commit"])
    owner_a, at_a = np.array(owner), np.array(at)
    count = 0
    for i, j in enumerate(jobs):
        if job_type(j["spec"]) != "system":
            continue
        own = at_a[owner_a == i] if len(at_a) else at_a
        if len(own) < 2 or own.min() == own.max():
            continue
        count += bool(((at_a > own.min()) & (at_a < own.max())
                       & (owner_a != i)).any())
    return count


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
