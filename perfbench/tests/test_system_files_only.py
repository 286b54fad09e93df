"""A system job with priorities and preemption is files:
`testdata/system-600/` holds a deployment whose two system kinds —
`agent-high` at priority 100, which may evict the priority-50 fillers and
services, and `agent-low` at priority 55, which may evict nothing (a delta
under 10) — no line of `perfbench/*.py` names. Its jobs go `make_job` ->
`adapter.to_job` -> a `Server`'s worker on the CPU, in two shapes of load:
eight at a time, the first eight before the worker starts, so that the
broker hands out the system jobs among them first; and 96 at once into a
running worker, so that system evals queue while batches of service evals
drain. `check.serial_order` + `check.replay` + `check.verdict` judge what
was placed and evicted against `reference.py` with the file's own limits:
`correct`, and the bfloat16 control read beside it on the system numbers.
With either fault of the system path planted underneath, the number named
for it comes out over its limit."""
import json
import os

import pytest

import adapter
import check
import cluster as cl
import faults
import launcher
from reference import bf16

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "system-600")
TERMINAL = ("complete", "failed", "blocked", "cancelled")
SEED, COUNT = 2**31 + 40, 8
#: (jobs, registered at a time, the first group before the worker starts)
STEPPED, FLOOD = (48, 8, True), (96, 96, False)


def load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def serve(cfg, seed, load=STEPPED):
    """The jobs of the mix through a `Server`, `group` registered at a
    time, each group waited for; the first before the worker starts where
    `early`, so that the broker holds all of it and hands out the system
    jobs first. -> what `check.serial_order` takes:
    every job with its allocations as the store holds them (those a later
    allocation evicted too), the index its eval was written at and the one
    it ended at."""
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.structs.codec import to_wire

    n_jobs, group, early = load
    s = Server(ServerConfig(num_schedulers=1, heartbeat_ttl=3600.0,
                            eval_batch=32))
    launcher.load_cluster(s, cfg, seed)
    kinds = cl.kinds_sequence(cfg, seed, n_jobs)
    specs = [cl.make_job(cfg, seed, k, kinds[k], COUNT)
             for k in range(n_jobs)]
    try:
        if not early:
            s.start()
        evals = []
        for g in range(0, n_jobs, group):
            evs = [s.job_register(adapter.to_job(spec))
                   for spec in specs[g:g + group]]
            if g == 0 and early:
                s.start()
            for ev in evs:
                got = s.wait_for_eval(ev.id, statuses=TERMINAL,
                                      timeout=120.0)
                assert got is not None, ev.id
                evals.append((ev, got))
        jobs = []
        for spec, (ev, got) in zip(specs, evals):
            allocs = [rec for rec in (
                check.alloc_record(to_wire(a))
                for a in s.state.allocs_by_job("default", spec["id"]))
                if rec is not None]
            jobs.append({"spec": spec, "allocs": allocs,
                         "enqueued": ev.modify_index,
                         "finished": got.modify_index})
    finally:
        s.shutdown()
    return jobs


def judge(cfg, jobs):
    limits = load("limits.json")
    sample = check.draw_sample([j["spec"]["id"] for j in jobs], None,
                               limits["sample_evals"], SEED)
    numbers = check.replay(cl.Cluster(cfg, SEED), check.serial_order(jobs),
                           sample, rnd_control=bf16)
    numbers["unanswered"] = 0
    return numbers, check.verdict(numbers, limits["limits"])


def test_the_harness_names_neither_kind():
    cfg = load("config.json")
    bench = os.path.dirname(os.path.dirname(DATA))
    for fn in os.listdir(bench):
        if fn.endswith(".py"):
            with open(os.path.join(bench, fn)) as f:
                text = f.read()
            for kind in cfg["kinds"]:
                assert kind not in text, (fn, kind)


def test_its_system_jobs_are_placed_and_evict_as_the_reference_says():
    cfg = load("config.json")
    c = cl.Cluster(cfg, SEED)
    assert not ((c.used - c.cap) > 0).any()      # no node starts over
    jobs = serve(cfg, SEED)
    by_kind = {}
    for j in jobs:
        by_kind.setdefault(j["spec"]["kind"], []).append(j)
    assert {k: len(v) for k, v in by_kind.items()} == \
        {"binpack": 24, "agent-high": 12, "agent-low": 12}
    numbers, held = judge(cfg, jobs)
    assert all(c["ok"] for c in held.values()), (held, numbers["worst"])
    assert numbers["compared"] == 24 * COUNT
    # every node of the 600 is the system jobs' own: placed, or refused
    assert numbers["system_placed"] + numbers["system_refused"] == 24 * 600
    # agent-high evicted, the fillers and services of priority 50 among
    # its victims; agent-low, 5 over them, was refused on full nodes
    victims = {v for j in by_kind["agent-high"] for a in j["allocs"]
               for v in a["preempted"]}
    assert numbers["evictions"] == len(victims) > 0
    assert victims & {f["id"] for f in c.fillers}
    assert all(not a["preempted"] for j in by_kind["agent-low"]
               for a in j["allocs"])
    assert min(len(j["allocs"]) for j in by_kind["agent-low"]) < 600
    assert any(a["evicted"] for j in jobs for a in j["allocs"])
    # the broker handed the system jobs out first: not the enqueued order
    assert [j["enqueued"] for j in check.serial_order(jobs)] != \
        sorted(j["enqueued"] for j in jobs)
    # each system job committed whole: the verdict above is exact
    assert numbers["system_interleaved"] == 0
    # the control, in the program's place on the same turns, is not correct
    limits = load("limits.json")["limits"]
    control = check.verdict(numbers["control"],
                            {k: limits[k] for k in numbers["control"]})
    assert not control["system_missing"]["ok"], numbers["control"]


def test_system_jobs_among_a_running_flood_of_service_jobs():
    cfg = load("config.json")
    jobs = serve(cfg, SEED, FLOOD)
    kinds = [j["spec"]["kind"] for j in jobs]
    assert {k: kinds.count(k) for k in set(kinds)} == \
        {"binpack": 48, "agent-high": 24, "agent-low": 24}
    numbers, held = judge(cfg, jobs)
    assert all(c["ok"] for c in held.values()), (held, numbers["worst"])
    assert numbers["system_interleaved"] == 0
    assert numbers["evictions"] > 0 and numbers["compared"] > 0


@pytest.mark.parametrize("fault,number", [
    ("system-node-left-out", "system_missing"),
    ("preempt-too-high", "preempt_wrong"),
])
def test_a_fault_of_the_system_path_is_not_correct(monkeypatch, fault,
                                                   number):
    from nomad_tpu.scheduler import preemption
    from nomad_tpu.scheduler.system import SystemScheduler

    # undone after the test: `plant` replaces them in place
    monkeypatch.setattr(SystemScheduler, "_compute_placements",
                        SystemScheduler._compute_placements)
    monkeypatch.setattr(preemption, "preempt_on_node",
                        preemption.preempt_on_node)
    faults.plant(fault)
    cfg = load("config.json")
    numbers, held = judge(cfg, serve(cfg, SEED))
    assert not held[number]["ok"], numbers
