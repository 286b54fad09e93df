"""`gpu_double_assigned`, the number that kept `baseline-10k` out until the
program verified instance ids at the commit point (PERF.md, section 6,
PR 28): a run whose device jobs each hold their own instances passes the
cell's limits; the same run with ONE instance handed to a second
allocation — the fault the fused batches had — fails them, by that number
and no other."""
import json
import os

import numpy as np

import check
import cluster as cl
from reference import Reference, exact

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def serve(cluster, cfg, seed, n_jobs, count):
    """The reference in the program's place, handing out the instance ids
    of the chosen node in order."""
    ref = Reference(cluster)
    kinds = cl.kinds_sequence(cfg, seed, n_jobs)
    taken = {}
    jobs = []
    for k in range(n_jobs):
        spec = cl.make_job(cfg, seed, k, kinds[k], count)
        allocs = []
        for i in range(count):
            feas, final = ref.select(spec, exact)
            node = int(np.argmax(np.where(feas, final, -np.inf)))
            ref.place(spec, node)
            rec = cluster.nodes[node]
            ids = []
            for _ in range(spec["gpus"]):
                n = taken[node] = taken.get(node, 0) + 1
                assert n <= rec["gpus"]
                ids.append(f"gpu-{rec['i']}-{n - 1}")
            allocs.append({"index": i, "node": rec["id"],
                           "norm_score": None, "device_ids": ids})
        ref.forget(spec["id"])
        jobs.append({"spec": spec, "allocs": allocs})
    return jobs


def test_one_instance_handed_out_twice_is_not_correct():
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "baseline-10k.json")))
    cfg.update(cfg["rehearsal"])
    limits = json.load(open(os.path.join(
        BENCH, "limits", "baseline-10k.flood.json")))["limits"]
    assert limits["gpu_double_assigned"] == 0
    seed = 2**31 + 28
    cluster = cl.Cluster(cfg, seed)
    jobs = serve(cluster, cfg, seed, 128, 8)
    ids = {j["spec"]["id"] for j in jobs}
    n = check.replay(cluster, jobs, ids)
    n["unanswered"] = 0
    assert n["gpus_in_use"] >= 16 and n["gpu_double_assigned"] == 0
    assert all(c["ok"] for c in check.verdict(n, limits).values()), n

    holders = [a for j in jobs for a in j["allocs"] if a["device_ids"]]
    holders[-1]["device_ids"] = list(holders[0]["device_ids"])
    n = check.replay(cluster, jobs, ids)
    n["unanswered"] = 0
    failed = {k for k, c in check.verdict(n, limits).items() if not c["ok"]}
    assert failed == {"gpu_double_assigned"}, n
    assert n["gpu_double_assigned"] == 1
