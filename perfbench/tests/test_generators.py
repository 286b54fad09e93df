"""The generators: same seed, same cluster and schedule; exact shares per
kind; no node over capacity at the start; every datacenter holds every
class; the sizing rule's totals; a job kind is a record in the
configuration's file, and every cell's jobs are byte for byte what they were
when `make_job` was an `elif` a kind."""
import hashlib
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
    cfg["kinds"] = {"to-b": {"affinities": [["${node.class}", "=", "b", 9]]}}
    spec = cl.make_job(cfg, 3, 0, "to-b", 8)
    assert spec["datacenters"] == ["dc1", "dc2"]
    assert spec["affinities"] == [["${node.class}", "=", "b", 9]]


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


#: SHA-256 of `json.dumps` of the first 1,024 jobs of a run, taken on the
#: parent commit of PR 34 (33c0edb), whose `make_job` built each kind in code
GOLDEN = {
    ("c1m-5k", 1, 0):
        "a9375771483734935ad9d33d76f423c42c805daa47a6d06eff4e8002975c2102",
    ("c1m-5k", 1, 3):
        "afc94c0d629150904cbeaff16b5b3eb0ad0924540955e9dc6e4735fe9cf6b775",
    ("c1m-5k", 1, 1000003):
        "1a21b4b640ec9185a53b7238ab19ee6920301d2d598e0ab56376231dd019cae9",
    ("c1m-5k", 1, 2147485621):
        "681df3235f4111a3c10f5c641c41335a3e04ee2d97bc99138bded6843551b0db",
    ("c1m-5k", 1000, 0):
        "f67567ce8e47d8c9ba32b6adfbe5c4f0c3e0fe682aed7df17c5b01cabf211930",
    ("c1m-5k", 1000, 3):
        "a1514bdf4671261a6410cc5bf5f707ba7664059741d4e74ccbcfa379262d91f9",
    ("c1m-5k", 1000, 1000003):
        "9a97c4f4cddc614aab8ddae1570d74536e678ad8bbd87ac2ada8b9fb769053b3",
    ("c1m-5k", 1000, 2147485621):
        "83abe42c3aa892596f849fbcd4fb52e6e4f7d449d61bcbfef18a0ef4f8a0ff45",
    ("baseline-10k", 8, 0):
        "927a683e2e9880a1e6c660e6f57faf49e23f08596358bf448bc8679fc88bcd66",
    ("baseline-10k", 8, 3):
        "cbb2cd2ffc7b72aaab438035ec84f95e5ee43e37adc1d4595bbc66ca66a4be5a",
    ("baseline-10k", 8, 1000003):
        "969cb30805604e1aed356255231a9ebebdb5ecf468355a1b2e3fb64802c7d22e",
    ("baseline-10k", 8, 2147485621):
        "a2c4d3fc02a6820ee7af5702124b495325faa7a199f3ee4691f27cad7ab70d3e",
    ("pinned-10k", 8, 0):
        "59f81dde14bbe8fadc3f5e6bd99be2234dcba695e7bc0e765903ef34e589e5ec",
    ("pinned-10k", 8, 3):
        "e8a5a6886213b568625a2eb1308fd976d57feb5e71c69a211d3b77d0ecfcbdc4",
    ("pinned-10k", 8, 1000003):
        "1eb3472a7b4575115c6fe2cfcb1a073d27f4c1edcc9adb488c2fbfe1c069c259",
    ("pinned-10k", 8, 2147485621):
        "e5cb0c1801180f4f7f447c3d6dc77d00a7961ff46af65f86e5c95fcef2b0dd32",
}


@pytest.mark.parametrize("name,count,seed", sorted(GOLDEN))
def test_every_cell_sends_the_jobs_it_sent_before_kinds_were_records(
        name, count, seed):
    cfg = cfg_of(name)
    kinds = cl.kinds_sequence(cfg, seed, 1024)
    jobs = [cl.make_job(cfg, seed, k, kinds[k], count) for k in range(1024)]
    assert hashlib.sha256(json.dumps(jobs).encode()).hexdigest() == \
        GOLDEN[name, count, seed]


@pytest.mark.parametrize("name", ["c1m-5k", "baseline-10k", "pinned-10k"])
def test_every_kind_of_a_mix_has_its_record(name):
    cfg = cfg_of(name)
    records = cfg.get("kinds", {})     # none where the mix is `binpack`
    assert set(cfg["mix"]) - {"binpack"} == set(records)
    for kind in cfg["mix"]:
        spec = cl.make_job(cfg, 1, 0, kind, 8)
        assert set(records.get(kind, {})) < set(spec)
        # a record's value stands in the default's place, whole
        for key, value in records.get(kind, {}).items():
            assert spec[key] == value


def test_a_kind_is_its_record_and_nothing_else():
    cfg = dict(cfg_of("c1m-5k"), kinds={
        "one-node": {"constraints": [["${node.unique.name}", "=", "node-5"]],
                     "gpus": 2},
        "typo": {"constraint": []},
        "binpack": {}})
    plain = cl.make_job(cfg, 7, 3, "binpack", 8)   # needs no record
    spec = cl.make_job(cfg, 7, 3, "one-node", 8)
    assert {k for k in spec if spec[k] != plain[k]} == \
        {"kind", "constraints", "gpus"}
    assert list(spec) == list(plain)
    spec["constraints"].append("x")                # a job owns its lists
    assert len(cfg["kinds"]["one-node"]["constraints"]) == 1
    with pytest.raises(ValueError, match="no keys of a job's shape"):
        cl.make_job(cfg, 7, 3, "typo", 8)          # an unknown key
    with pytest.raises(ValueError, match="has no record"):
        cl.make_job(cfg, 7, 3, "spread", 8)        # a name with no record
    with pytest.raises(ValueError, match="has no record"):
        cl.make_job(dict(cfg, kinds=None), 7, 3, "one-node", 8)
    with pytest.raises(ValueError, match="takes no record"):
        cl.make_job(dict(cfg, kinds={"binpack": {"gpus": 1}}), 7, 3,
                    "binpack", 8)
