"""A configuration is files: `testdata/files-only/` holds a small deployment
whose job kinds — one constrained to a single `${meta.cell}` partition, one
to a single `${node.unique.name}` — no line of `perfbench/*.py` names. Its
jobs go `make_job` -> `adapter.to_job` -> the program's batched drain on the
CPU, and `check.replay` + `check.verdict` judge what was placed against
`reference.py` with the file's own limits: `correct`; with the fault
`half-left-out` planted under the program's select: not `correct`."""
import json
import os
import time

import pytest

import adapter
import check
import cluster as cl
import faults
import launcher

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "files-only")
TERMINAL = ("complete", "failed", "blocked", "cancelled")
SEED, N_JOBS, COUNT = 2**31 + 34, 48, 8


def load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def serve(cfg, seed, n_jobs, count):
    """`n_jobs` jobs of the configuration's mix through a `Server` with
    fused batches of 32, all registered before the worker starts (as
    `tests/test_pinned_deployment.py` feeds it). -> what `check.replay`
    takes: every job in the order its eval was enqueued, with its
    allocations as the store holds them."""
    from nomad_tpu.server import Server, ServerConfig

    s = Server(ServerConfig(num_schedulers=1, heartbeat_ttl=3600.0,
                            eval_batch=32))
    launcher.load_cluster(s, cfg, seed)
    kinds = cl.kinds_sequence(cfg, seed, n_jobs)
    specs = [cl.make_job(cfg, seed, k, kinds[k], count)
             for k in range(n_jobs)]
    evs = [s.job_register(adapter.to_job(spec)) for spec in specs]
    s.start()
    try:
        for ev in evs:
            got = s.wait_for_eval(ev.id, statuses=TERMINAL, timeout=120.0)
            assert got is not None, ev.id
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and s.metrics.counters().get(
                "worker.0.batch.batched", 0) < n_jobs:
            time.sleep(0.05)
        counters = s.metrics.counters()
        jobs = []
        for spec in specs:
            allocs = []
            for a in s.state.allocs_by_job("default", spec["id"]):
                if a.desired_status != "run":
                    continue
                score = next((m.norm_score for m in a.metrics.score_meta
                              if m.node_id == a.node_id), None)
                allocs.append({
                    "index": int(a.name.rsplit("[", 1)[1][:-1]),
                    "node": a.node_id, "norm_score": score,
                    "device_ids": []})
            jobs.append({"spec": spec, "allocs": allocs})
    finally:
        s.shutdown()
    return jobs, counters


def judge(cfg, jobs):
    limits = load("limits.json")
    sample = check.draw_sample([j["spec"]["id"] for j in jobs], None,
                               limits["sample_evals"], SEED)
    numbers = check.replay(cl.Cluster(cfg, SEED), jobs, sample)
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


def test_its_jobs_are_placed_as_the_reference_places_them():
    cfg = load("config.json")
    jobs, counters = serve(cfg, SEED, N_JOBS, COUNT)
    by_kind = {}
    for j in jobs:
        by_kind.setdefault(j["spec"]["kind"], []).append(j)
    assert {k: len(v) for k, v in by_kind.items()} == \
        {"binpack": 24, "one-partition": 18, "named-node": 6}
    numbers, held = judge(cfg, jobs)
    assert numbers["compared"] == N_JOBS * COUNT
    assert all(c["ok"] for c in held.values()), (held, numbers["worst"])
    # the shapes did what they say: one node, one partition
    c = cl.Cluster(cfg, SEED)
    for j in by_kind["named-node"]:
        assert {c.nodes[c.index_of[a["node"]]]["name"]
                for a in j["allocs"]} == {"node-197"}
    for j in by_kind["one-partition"]:
        assert {c.nodes[c.index_of[a["node"]]]["cell"]
                for a in j["allocs"]} == {"c7"}
    assert counters.get("worker.0.batch.batched", 0) == N_JOBS


def test_half_of_every_group_left_out_is_not_correct(monkeypatch):
    from nomad_tpu.scheduler import stack

    # undone after the test: `plant` replaces the method in place
    monkeypatch.setattr(stack.TPUStack, "select", stack.TPUStack.select)
    faults.plant("half-left-out")
    cfg = load("config.json")
    jobs, _ = serve(cfg, SEED, N_JOBS, COUNT)
    numbers, held = judge(cfg, jobs)
    assert not held["short_with_room"]["ok"], numbers
    assert numbers["short_with_room"] == N_JOBS
