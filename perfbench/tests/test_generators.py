"""The generators: same seed, same cluster and schedule; exact shares per
kind; no node over capacity at the start; every datacenter holds every
class; the sizing rule's totals."""
import json
import os
from collections import Counter

import numpy as np
import pytest

import cluster as cl
import traffic as tf

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg_of(name):
    return json.load(open(os.path.join(BENCH, "configs", f"{name}.json")))


@pytest.fixture(scope="module")
def baseline():
    return cl.Cluster(cfg_of("baseline-10k"), 2**31 + 11)


def test_same_seed_same_cluster_other_seed_other(baseline):
    cfg = cfg_of("baseline-10k")
    again = cl.Cluster(cfg, 2**31 + 11)
    assert [n["id"] for n in again.nodes] == [n["id"]
                                              for n in baseline.nodes]
    assert again.fillers == baseline.fillers
    other = cl.make_nodes(cfg, 12)
    assert other[0]["id"] != baseline.nodes[0]["id"]


def test_every_datacenter_holds_every_class(baseline):
    seen = Counter((n["datacenter"], n["class"]) for n in baseline.nodes)
    assert len(seen) == 9
    assert max(seen.values()) - min(seen.values()) <= 1


def test_node_shapes_are_the_configurations_own():
    """A later configuration brings other shapes as a file, editing no
    code: classes, sizes, reserve, datacenters, racks and GPUs are read."""
    cfg = dict(cfg_of("c1m-5k"), nodes=40, allocs=80, datacenters=2,
               classes={"a": 1, "b": 3}, node_cpu_mhz=1000,
               node_memory_mib=2048, node_disk_mib=5000,
               reserved={"cpu": 50, "memory": 48, "disk": 1000},
               racks=7, gpu_every=5, gpus_per_node=2)
    c = cl.Cluster(cfg, 3)
    assert {n["datacenter"] for n in c.nodes} == {"dc1", "dc2"}
    assert len({(n["datacenter"], n["class"]) for n in c.nodes}) == 4
    assert c.raw[1].tolist() == [3000.0, 6144.0, 5000.0]
    assert c.cap[1].tolist() == [2950.0, 6096.0, 4000.0]
    assert {n["rack"] for n in c.nodes} == {f"r{i}" for i in range(7)}
    assert c.gpus.tolist() == [2 if i % 5 == 0 else 0 for i in range(40)]
    spec = cl.make_job(cfg, 3, 0, "affinity", 8)
    assert spec["datacenters"] == ["dc1", "dc2"]
    assert spec["affinities"][0][2] == "b"


def test_no_node_starts_over_capacity_and_shares_are_even(baseline):
    share = baseline.used / baseline.cap
    assert share.max() < 0.70
    fill = baseline.fill(baseline.used)
    assert fill["cpu"] == pytest.approx(0.32, abs=0.02)
    assert fill["memory"] == pytest.approx(0.09, abs=0.01)
    # dealt in proportion to the class multiplier: fillers per unit agree
    per_unit = Counter()
    for f in baseline.fillers:
        per_unit[baseline.nodes[f["node"]]["mult"]] += 1
    units = Counter(n["mult"] for n in baseline.nodes)
    r = [per_unit[m] / (units[m] * m) for m in (1, 2, 4)]
    assert max(r) - min(r) < 0.05 * max(r)


def test_kinds_have_exact_shares_for_every_seed():
    cfg = cfg_of("baseline-10k")
    for seed in (1, 2**31 + 5, 4000000007):
        ks = cl.kinds_sequence(cfg, seed, 32 * 40)
        for b in range(40):
            assert Counter(ks[32 * b:32 * b + 32]) == Counter(cfg["mix"])
    assert cl.kinds_sequence(cfg, 1, 64) != cl.kinds_sequence(cfg, 2, 64)


def test_asks_have_the_same_totals_for_every_seed():
    cfg = cfg_of("baseline-10k")
    tot = []
    for seed in (3, 2**31 + 3):
        jobs = [cl.make_job(cfg, seed, k, "binpack", 8) for k in range(900)]
        tot.append((sum(j["cpu"] for j in jobs),
                    sum(j["memory"] for j in jobs)))
        assert jobs == [cl.make_job(cfg, seed, k, "binpack", 8)
                        for k in range(900)]
    assert tot[0] == tot[1] == (100 * 3 * (110 + 170 + 290),
                                100 * 3 * (70 + 150 + 300))


def test_sizing_rule_totals(baseline):
    """60 s at four times PR 22's flood rate must leave the tightest
    dimension under 85 %."""
    cfg = cfg_of("baseline-10k")
    placements = 4 * 124 * 8 * 60
    raw = baseline.raw.sum(axis=0)
    used = baseline.used.sum(axis=0)
    mean_cpu = np.mean(cfg["job"]["cpu"])
    mean_mem = np.mean(cfg["job"]["memory"])
    assert (used[0] + placements * mean_cpu) / raw[0] < 0.85
    assert (used[1] + placements * mean_mem) / raw[1] < 0.85
    # one job in 32 asks for GPUs: by the GPUs there are
    assert cfg["mix"]["devices"] * 32 == sum(cfg["mix"].values())
    assert placements / 32 < baseline.gpus.sum()
    c1m = cl.Cluster(cfg_of("c1m-5k"), 5)
    end = (c1m.used.sum(axis=0)[0] + 4 * 1530 * 60 * 37) / \
        c1m.raw.sum(axis=0)[0]
    assert end < 0.40


def test_open_loop_gaps_are_the_same_set_for_every_seed():
    a = tf.gap_block(62, 1, 0)
    b = tf.gap_block(62, 2**31 + 9, 0)
    assert a != b and sorted(a) == sorted(b)
    assert sum(a) == pytest.approx(tf.BLOCK / 62)
    arr = tf.arrivals(62, 7)
    first = [next(arr) for _ in range(2 * tf.BLOCK)]
    assert first == sorted(first)
    assert first[-1] == pytest.approx(2 * tf.BLOCK / 62)


def test_payloads_equal_the_codecs_own_also_with_awkward_ids():
    """Seed 1000003's job 4272 is `svc-9876523a1b7f`: its id holds the
    digits of the memory placeholder (PR 25: one request of that seed was
    never answered, because the body named another job)."""
    import adapter
    import run

    cfg = cfg_of("baseline-10k")
    bodies = run.Payloads(cfg, 8)
    kinds = cl.kinds_sequence(cfg, 1000003, 4300)
    for k in (0, 1, 4271, 4272, 4273):
        spec = cl.make_job(cfg, 1000003, k, kinds[k], 8)
        assert bodies.of(spec) == adapter.job_payload(spec)
    assert "987652" in cl.make_job(cfg, 1000003, 4272, "binpack", 8)["id"]
