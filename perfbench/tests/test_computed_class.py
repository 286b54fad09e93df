"""`configs/computed-class-5k.json` (PR 36): upstream's stack benchmark as a
deployment. Its 64 job kinds are 64 records that differ in the partition's
value alone; its node table is upstream's (node i in partition `c{i % 64}`)
with every class in every partition; its cluster is `c1m-5k`'s key for key
but `cells`; and from a seed it sends, byte for byte, the jobs it sent when
the cell was first measured."""
import hashlib
import json
import os
from collections import Counter

import pytest

import cluster as cl
from test_generators import BENCH, cfg_of

CELLS = 64
CFG = cfg_of("computed-class-5k")

#: SHA-256 of `json.dumps` of the first 1,024 jobs of a run (the seeds of
#: `test_generators.py`), taken on PR 36's commit
GOLDEN = {
    0: "6d8b2facc1d562a7b0fa478fcd7bc1c466023de14510ec62f245df81041abddb",
    3: "24af5bea1c42686cddb1efe8f76c7b61245f75aae396cc764754c4755deb611f",
    1000003:
        "702fa37a50d9c8e4ca232d89f7d205a0ce20198433c64e897424594d3c19d600",
    2147485621:
        "1192c3fa6cb3d2565b67ac2620dc6d34d0375cdddbd21868bc2ffe777a34357d",
}


@pytest.mark.parametrize("seed", sorted(GOLDEN))
def test_the_cell_sends_the_jobs_it_was_measured_with(seed):
    kinds = cl.kinds_sequence(CFG, seed, 1024)
    jobs = [cl.make_job(CFG, seed, k, kinds[k], 8) for k in range(1024)]
    assert hashlib.sha256(json.dumps(jobs).encode()).hexdigest() == \
        GOLDEN[seed]
    # whole copies of the mix: each partition 16 times in 1,024 jobs, and
    # once in every block of 64
    assert set(Counter(kinds).values()) == {1024 // CELLS}
    for b in range(0, 1024, CELLS):
        assert len(set(kinds[b:b + CELLS])) == CELLS


def test_the_records_differ_in_the_partitions_value_alone():
    assert CFG["mix"] == {f"cell-c{k}": 1 for k in range(CELLS)}
    assert set(CFG["kinds"]) == set(CFG["mix"])
    for k in range(CELLS):
        assert CFG["kinds"][f"cell-c{k}"] == {"constraints": [
            ["${attr.kernel.name}", "=", "linux"],
            ["${meta.cell}", "=", f"c{k}"]]}
    plain = cl.make_job(CFG, 7, 5, "cell-c0", 8)
    for k in range(1, CELLS):
        spec = cl.make_job(CFG, 7, 5, f"cell-c{k}", 8)
        assert {key for key in spec if spec[key] != plain[key]} == \
            {"kind", "constraints"}
        assert spec["constraints"][1][2] == f"c{k}"
        assert spec["datacenters"] == ["dc1", "dc2", "dc3"]


def test_every_partition_holds_78_or_79_nodes_of_every_class():
    nodes = cl.make_nodes(CFG, 2**31 + 36)
    assert len(nodes) == 5000
    assert all(n["cell"] == f"c{n['i'] % CELLS}" for n in nodes)  # upstream
    per = Counter(n["cell"] for n in nodes)
    assert set(per) == {f"c{k}" for k in range(CELLS)}
    assert set(per.values()) == {78, 79}
    by_class = Counter((n["cell"], n["class"]) for n in nodes)
    assert len(by_class) == CELLS * len(CFG["classes"])
    assert min(by_class.values()) >= 25
    # and of every datacenter: the constraint is the only gate
    assert len({(n["cell"], n["datacenter"]) for n in nodes}) == CELLS * 3


def test_the_cluster_is_c1m_5ks_but_for_the_partitions():
    base = cfg_of("c1m-5k")
    for key in ("nodes", "allocs", "row_bucket", "classes", "node_cpu_mhz",
                "node_memory_mib", "node_disk_mib", "reserved",
                "datacenters", "racks", "gpu_every", "gpus_per_node",
                "filler", "job"):
        assert CFG[key] == base[key], key
    assert (CFG["cells"], base["cells"]) == (CELLS, 500)
    assert CFG["reduced"] == [] and CFG["chips"] == 1
    assert CFG["rehearsal"] == cfg_of("pinned-10k")["rehearsal"]
    pinned = cfg_of("pinned-10k")["guarantees"]
    mine = CFG["guarantees"]
    assert {k: v for k, v in mine.items() if k != "partition"} == \
        {k: v for k, v in pinned.items() if k != "zone"}
    assert "meta.cell" in mine["partition"]
    limits = json.load(open(os.path.join(
        BENCH, "limits", "computed-class-5k.flood.json")))
    assert limits == json.load(open(os.path.join(
        BENCH, "limits", "pinned-10k.flood.json")))


def test_a_partition_has_three_times_the_room_its_share_of_a_run_takes():
    """The sizing rule, per PARTITION: 60 s (window + warm-up) at the
    rate the file states, a 64th of it each, against what a partition has
    free under 85 %."""
    import re

    c = cl.Cluster(CFG, 2**31 + 36)
    rate = float(re.search(r"at the ([\d,]+) allocs/s", CFG["sizing"])
                 .group(1).replace(",", ""))
    mean_cpu = sum(CFG["job"]["cpu"]) / 3.0
    mean_mem = sum(CFG["job"]["memory"]) / 3.0
    need = rate * 60.0 / CELLS
    for k in range(CELLS):
        rows = [n["i"] for n in c.nodes if n["cell"] == f"c{k}"]
        free = 0.85 * c.raw[rows].sum(axis=0) - c.used[rows].sum(axis=0)
        assert free[0] >= 3.0 * need * mean_cpu, k
        assert free[1] >= 3.0 * need * mean_mem, k
