"""A job's `type` and `priority`, and the plain reference of a system job:
`make_job`'s defaults and refusals, `system_nodes` on a hand-built cluster of
four nodes, each of `judge_preemption`'s refusals, a replay in which a later
system job evicts an earlier service allocation, the bfloat16 control on the
system numbers (the reference in the program's place passes, the control
fails at `baseline-10k`'s size), the serial order across priorities and what
it counts as not exact, and an allocation's record from its wire form."""
import hashlib
import json
import os

import numpy as np
import pytest

import check
import cluster as cl
from reference import PRIORITY_DELTA, Reference, basic_distance, bf16

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILES_ONLY = os.path.join(BENCH, "testdata", "files-only", "config.json")
SYSTEM_600 = os.path.join(BENCH, "testdata", "system-600", "config.json")
HIGH = {"type": "system", "priority": 100}
LOW = {"type": "system", "priority": 55}


def cfg_with(**kinds):
    cfg = json.load(open(FILES_ONLY))
    return dict(cfg, kinds=kinds, mix={"binpack": 1, **{k: 1 for k in kinds}})


# ---- the vocabulary ----

def test_the_default_job_holds_neither_key_and_reads_the_defaults():
    cfg = cfg_with()
    spec = cl.make_job(cfg, 3, 0, "binpack", 8)
    assert "type" not in spec and "priority" not in spec
    assert cl.job_type(spec) == "service" and cl.job_priority(spec) == 50
    # the default job's bytes are those it had before the keys existed
    assert hashlib.sha256(json.dumps(spec).encode()).hexdigest() == \
        hashlib.sha256(json.dumps(cl.make_job(
            cfg_with(high=HIGH), 3, 0, "binpack", 8)).encode()).hexdigest()


def test_a_system_record_has_a_count_of_one_whatever_the_traffic_asks():
    cfg = cfg_with(high=HIGH, prio={"priority": 70})
    spec = cl.make_job(cfg, 3, 1, "high", 8)
    assert spec["count"] == 1
    assert cl.job_type(spec) == "system" and cl.job_priority(spec) == 100
    svc = cl.make_job(cfg, 3, 2, "prio", 8)
    assert svc["count"] == 8 and cl.job_type(svc) == "service"
    assert cl.job_priority(svc) == 70


@pytest.mark.parametrize("key,value", [
    ("spread", {"attribute": "${node.datacenter}", "weight": 100,
                "targets": [["dc1", 100]]}),
    ("affinities", [["${node.class}", "=", "linux-large", 50]]),
    ("distinct_hosts", True),
    ("distinct_property", ["${meta.cell}", 1]),
])
def test_a_system_record_refuses_what_the_system_scheduler_cannot_rank(
        key, value):
    cfg = cfg_with(bad=dict(HIGH, **{key: value}))
    with pytest.raises(ValueError, match="a system job takes none of"):
        cl.make_job(cfg, 3, 0, "bad", 8)


@pytest.mark.parametrize("record,match", [
    ({"priority": 0}, "priority"), ({"priority": 101}, "priority"),
    ({"priority": 50.5}, "priority"), ({"priority": True}, "priority"),
    ({"priority": "50"}, "priority"), ({"type": "batch"}, "type"),
    ({"type": "sysbatch"}, "type"),
])
def test_a_record_refuses_a_type_or_priority_the_reference_cannot_judge(
        record, match):
    with pytest.raises(ValueError, match=match):
        cl.make_job(cfg_with(bad=record), 3, 0, "bad", 8)


def test_the_adapter_sends_type_and_priority():
    import adapter

    cfg = cfg_with(high=HIGH)
    job = adapter.to_job(cl.make_job(cfg, 3, 0, "high", 8))
    assert (job.type, job.priority, job.task_groups[0].count) == \
        ("system", 100, 1)
    job = adapter.to_job(cl.make_job(cfg, 3, 0, "binpack", 8))
    assert {"type": job.type, "priority": job.priority} == cl.JOB_DEFAULTS


# ---- the reference on four nodes ----

def four_nodes():
    """Four nodes of 4,000 MHz / 8,192 MiB in one datacenter, 3,900 MHz
    usable. Fillers (priority 50): node 1 full but 80 MHz; node 2 empty of
    fillers (filled below at priority 95); nothing on node 0 or node 3."""
    cfg = dict(json.load(open(FILES_ONLY)), nodes=4, allocs=0,
               classes={"a": 1}, datacenters=1, gpu_every=1000)
    c = cl.Cluster(cfg, 1)
    c.fillers = [
        {"id": "f1", "node": 1, "job": 0, "cpu": 110, "memory": 300,
         "disk": 100},
        {"id": "f2", "node": 1, "job": 0, "cpu": 290, "memory": 70,
         "disk": 150},
        {"id": "f3", "node": 1, "job": 0, "cpu": 170, "memory": 150,
         "disk": 150},
        {"id": "f4", "node": 1, "job": 0, "cpu": 3250, "memory": 70,
         "disk": 100},
    ]
    c.used = np.zeros((4, 3))
    for f in c.fillers:
        c.used[f["node"]] += (f["cpu"], f["memory"], f["disk"])
    return cfg, c


def spec_of(cfg, kind, record, cpu=290, memory=150, k=0):
    return dict(cl.make_job(dict(cfg, kinds={kind: record}), 1, k, kind, 8),
                cpu=cpu, memory=memory)


@pytest.fixture
def ref():
    cfg, c = four_nodes()
    r = Reference(c)
    # node 2 filled by a priority-95 service job: 3,890 MHz of 3,900
    svc = spec_of(cfg, "p95", {"priority": 95}, cpu=3890, memory=100, k=9)
    r.place(svc, 2, "s95")
    return cfg, c, r


def test_system_nodes(ref):
    cfg, c, r = ref
    no3 = [["${attr.kernel.name}", "=", "linux"],
           ["${node.unique.name}", "!=", "node-3"]]
    high = spec_of(cfg, "high", dict(HIGH, constraints=no3))
    must, may = r.system_nodes(high)
    # node 0 fits; node 1 fits once its fillers are gone; node 2's
    # allocation is of priority 95 > 100 - 10; node 3 fails a constraint
    assert must.tolist() == [True, False, False, False]
    assert may.tolist() == [False, True, False, False]
    low = spec_of(cfg, "low", dict(LOW, constraints=no3))
    must, may = r.system_nodes(low)     # 50 > 55 - 10: evicts nothing
    assert must.tolist() == [True, False, False, False]
    assert not may.any()
    top = spec_of(cfg, "top", {"type": "system", "priority": 100},
                  cpu=5000)             # fits nowhere, even empty
    assert not any(m.any() for m in r.system_nodes(top))


# ---- the judge: each refusal, and upstream's own non-minimal set ----

def test_judge_preemption_accepts_upstreams_set(ref):
    cfg, c, r = ref
    high = spec_of(cfg, "high", HIGH)
    # free on node 1: 80 MHz; ask 290. f3 (170) is nearest the ask, f2
    # (290) next, f1 (110) farthest. {f2} alone and {f3, f1} both do.
    d = {f["id"]: basic_distance(high, np.array(
        [f["cpu"], f["memory"], f["disk"]], dtype=float)) for f in c.fillers}
    assert d["f3"] < d["f2"] < d["f1"]
    assert r.judge_preemption(high, 1, ["f2"]) == []
    assert r.judge_preemption(high, 1, ["f3", "f1"]) == []
    # upstream's filterSuperset stops at the first fit, far to near: from
    # {f1, f2} it keeps both, though f2 alone would do; f2, the nearer,
    # is needed, and that is all it guarantees
    assert r.judge_preemption(high, 1, ["f1", "f2"]) == []


@pytest.mark.parametrize("node,victims,why", [
    (1, ["nope"], "nope is not live on the node"),
    (1, ["f4", "s95"], "s95 is not live on the node"),
    (2, ["s95"], "s95 has priority 95"),
    (0, ["f2"], "fit without evicting"),
    (1, ["f1"], "does not fit after the eviction"),
    (1, [], "does not fit after the eviction"),
    (1, ["f2", "f3"], "the victim nearest the ask could be given back"),
])
def test_judge_preemption_refuses(ref, node, victims, why):
    cfg, c, r = ref
    high = spec_of(cfg, "high", HIGH)
    assert why in r.judge_preemption(high, node, victims)


# ---- the replay: a later system job evicts an earlier service allocation

def replay_of(cfg, c, victims_on_1):
    svc = spec_of(cfg, "binpack", {}, cpu=80, memory=10, k=1)
    svc = dict(svc, count=1)
    high = spec_of(cfg, "high", HIGH, cpu=290, k=2)
    ids = {i: c.nodes[i]["id"] for i in range(4)}
    jobs = [
        {"spec": svc, "allocs": [{"index": 0, "node": ids[1], "id": "s1",
                                  "evicted": True}]},
        {"spec": high, "allocs": [
            {"index": 0, "node": ids[i], "id": f"h{i}",
             "preempted": victims_on_1 if i == 1 else []}
            for i in (0, 1, 2, 3)]},
    ]
    return check.replay(c, jobs, set())


def test_a_later_system_job_evicts_an_earlier_service_allocation():
    cfg, c = four_nodes()
    n = replay_of(cfg, c, ["s1", "f2"])   # node 1 full: 80 MHz of ours
    assert n["evictions"] == 2 and n["system_placed"] == 4
    assert (n["system_missing"], n["system_extra"], n["preempt_wrong"],
            n["overcommitted_nodes"]) == (0, 0, 0, 0), n["worst"]
    n = replay_of(cfg, c, [])             # placed, nothing evicted
    assert n["preempt_wrong"] == 1 and n["overcommitted_nodes"] == 1
    n = replay_of(cfg, c, ["s1"])         # 80 MHz: not enough
    assert n["preempt_wrong"] == 1 and n["overcommitted_nodes"] == 1


def test_a_system_job_on_too_many_or_too_few_nodes():
    cfg, c = four_nodes()
    high = spec_of(cfg, "high", HIGH, k=2)
    ids = [n["id"] for n in c.nodes]
    twice = [{"index": 0, "node": ids[i], "id": f"h{i}",
              "preempted": ["f2"] if i == 1 else []} for i in (0, 1, 2, 3)]
    n = check.replay(c, [{"spec": high, "allocs": twice + [
        {"index": 0, "node": ids[0], "id": "again"}]}], set())
    assert (n["system_extra"], n["system_missing"]) == (1, 0)
    n = check.replay(c, [{"spec": high, "allocs": twice[1:]}], set())
    assert (n["system_extra"], n["system_missing"]) == (0, 1)


def test_an_evicted_allocations_gpu_is_free_to_the_next():
    cfg, c = four_nodes()
    dev = dict(spec_of(cfg, "binpack", {}, cpu=10, memory=10, k=1),
               count=1, gpus=1)
    high = spec_of(cfg, "high", HIGH, k=2)
    ids = [n["id"] for n in c.nodes]

    def run(victims):
        taker = dict(dev, id="svc-taker")
        return check.replay(c, [
            {"spec": dev, "allocs": [{"index": 0, "node": ids[1], "id": "d1",
                                      "device_ids": ["gpu-1-0"],
                                      "evicted": bool(victims)}]},
            {"spec": high, "allocs": [
                {"index": 0, "node": ids[i], "id": f"h{i}",
                 "preempted": victims if i == 1 else []}
                for i in (0, 2, 3)] + [
                {"index": 0, "node": ids[1], "id": "h1",
                 "preempted": victims}]},
            {"spec": taker, "allocs": [{"index": 0, "node": ids[0],
                                        "id": "d2",
                                        "device_ids": ["gpu-1-0"]}]},
        ], set())

    c.gpus[:] = 1
    n = run(["d1", "f2"])
    assert (n["gpu_double_assigned"], n["gpus_in_use"]) == (0, 2), n
    assert n["preempt_wrong"] == 0, n["worst"]
    n = run(["f2"])                     # d1 still holds it
    assert n["gpu_double_assigned"] == 1


# ---- the control: the reference in bfloat16 in the program's place ----

def victims_for(ref, spec, node, live_on):
    """Upstream's choice on one node, as a stand-in: the candidates (a
    priority at least 10 below) nearest the ask first until the ask fits,
    then `filterSuperset`: added back far to near until it fits."""
    top = cl.job_priority(spec) - PRIORITY_DELTA
    cands = sorted((basic_distance(spec, np.array(ask, float)), i,
                    np.array(ask, float))
                   for i, ask, prio in live_on.get(node, ()) if prio <= top)

    def first_fit(seq):
        freed, out = np.zeros(4), []
        for _d, i, ask in seq:
            out.append((_d, i, ask))
            freed = freed + ask
            if ref._fits(spec, freed[None, :], [node])[0]:
                break
        return out

    return [i for _d, i, _a in first_fit(sorted(first_fit(cands),
                                                key=lambda c: -c[0]))]


def serve_by_reference(cfg, seed, kinds):
    """Stand-in for the program: each system job placed, one after the
    other, on the reference's node set, evicting on the nodes that need
    it; judged by the same replay."""
    c = cl.Cluster(cfg, seed)
    ref = Reference(c)
    jobs = []
    for k, kind in enumerate(kinds):
        spec = cl.make_job(cfg, seed, k, kind, 8)
        must, may = ref.system_nodes(spec)
        live_on = {}
        for i, (node, ask, prio) in ref.live().items():
            if may[node]:
                live_on.setdefault(node, []).append((i, ask, prio))
        allocs = []
        for node in np.flatnonzero(must | may).tolist():
            victims = victims_for(ref, spec, node, live_on) \
                if may[node] else []
            for v in victims:
                ref.evict(v)
            aid = f"{spec['id']}/{node}"
            ref.place(spec, node, aid)
            allocs.append({"index": 0, "node": c.nodes[node]["id"],
                           "id": aid, "preempted": victims})
        jobs.append({"spec": spec, "allocs": allocs})
    return jobs


def system_cfg(name):
    """`name`'s cluster with `system-600`'s two system kinds."""
    kinds = json.load(open(SYSTEM_600))["kinds"]
    if name == "system-600":
        return json.load(open(SYSTEM_600)), ["agent-high", "agent-low"] * 12
    cfg = json.load(open(os.path.join(BENCH, "configs", f"{name}.json")))
    return dict(cfg, kinds=kinds, mix={"agent-high": 1}), ["agent-high"] * 20


def control_of(name, seed):
    cfg, kinds = system_cfg(name)
    limits = json.load(open(os.path.join(os.path.dirname(SYSTEM_600),
                                          "limits.json")))["limits"]
    n = check.replay(cl.Cluster(cfg, seed),
                     serve_by_reference(cfg, seed, kinds), set(),
                     rnd_control=bf16)
    n["unanswered"] = 0
    assert all(c["ok"] for c in check.verdict(n, limits).values()), \
        n["worst"]
    return check.verdict(n["control"], {k: limits[k] for k in n["control"]})


@pytest.mark.parametrize("name,seed", [
    ("baseline-10k", 5), ("baseline-10k", 2**31 + 17),
    ("baseline-10k", 3000000019),
    ("system-600", 1), ("system-600", 2**31 + 17), ("system-600", 3000000019),
])
def test_the_reference_passes_and_the_control_fails(name, seed):
    held = control_of(name, seed)
    assert set(held) >= {"system_missing", "system_extra", "preempt_wrong"}
    # with preemption off it leaves out every node that needed an eviction
    assert not held["system_missing"]["ok"], held


# ---- the order ----

def test_one_priority_keeps_the_enqueued_order_two_merge_by_commit():
    cfg, _c = four_nodes()
    svc = [spec_of(cfg, "binpack", {}, k=k) for k in range(3)]
    high = spec_of(cfg, "high", HIGH, k=7)
    jobs = [{"spec": svc[k], "enqueued": 10 + k, "finished": None,
             "allocs": [{"commit": 100 - k}]} for k in range(3)]
    # one priority: the order enqueued, whatever the commits say
    assert [j["enqueued"] for j in check.serial_order(jobs[::-1])] == \
        [10, 11, 12]
    for k, at in enumerate((100, 101, 103)):
        jobs[k]["allocs"] = [{"commit": at}]
    # enqueued last, scheduled between the second and the third service
    jobs.append({"spec": high, "enqueued": 13, "finished": None,
                 "allocs": [{"commit": 102}, {"commit": 102.5}]})
    assert [j["enqueued"] for j in check.serial_order(jobs)] == \
        [10, 11, 13, 12]
    # a job that placed nothing goes by its terminal event
    jobs[-1] = dict(jobs[-1], allocs=[], finished=50)
    assert [j["enqueued"] for j in check.serial_order(jobs)] == \
        [13, 10, 11, 12]


@pytest.mark.parametrize("system_commits,count", [
    ((102, 102), 0),            # one plan, one index
    ((102, 102.5), 0),          # planned again, nothing between
    ((100.5, 102), 1),          # planned again around a service commit
])
def test_a_system_job_committed_around_another_is_counted(system_commits,
                                                          count):
    cfg, _c = four_nodes()
    svc = spec_of(cfg, "binpack", {}, k=0)
    high = spec_of(cfg, "high", HIGH, k=7)
    jobs = [{"spec": svc, "allocs": [{"commit": 101}]},
            {"spec": high, "allocs": [{"commit": at}
                                      for at in system_commits]}]
    assert check.interleaved(jobs) == count


def test_the_record_of_an_allocation_read_from_its_wire_form():
    wire = {"id": "a1", "name": "svc-1.web[3]", "node_id": "n7",
            "desired_status": "evict", "create_index": 41,
            "preempted_allocations": ["f1"],
            "metrics": {"score_meta": [{"node_id": "n2", "norm_score": .2},
                                       {"node_id": "n7", "norm_score": .7}]},
            "allocated_resources": {"tasks": {"web": {"devices": [
                {"device_ids": ["gpu-7-0", "gpu-7-1"]}]}}}}
    assert check.alloc_record(wire) == {
        "index": 3, "node": "n7", "norm_score": .7,
        "device_ids": ["gpu-7-0", "gpu-7-1"], "id": "a1",
        "preempted": ["f1"], "commit": 41, "evicted": True}
    assert check.alloc_record(dict(wire, desired_status="stop")) is None
