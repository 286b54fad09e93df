"""The control of `correct`, at a size a test run can hold: the reference
put in the program's place passes its own check exactly; computed in
bfloat16 — the nearest precision below the kernels' float32 at HIGHEST — it
fails it, both as the server of a whole run and, as the harness reads it
(`run.py --control`), position by position beside a sound run. (On the
chip, at the cells' own sizes: PERF.md, section 2.)"""
import json
import os

import numpy as np
import pytest

import check
import cluster as cl
from reference import Reference, bf16, exact

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def serve(cluster, cfg, seed, n_jobs, count, rnd):
    """Stand-in for the program: serial placement of `n_jobs` jobs by the
    reference computed with `rnd`."""
    ref = Reference(cluster)
    kinds = cl.kinds_sequence(cfg, seed, n_jobs)
    jobs = []
    for k in range(n_jobs):
        spec = cl.make_job(cfg, seed, k, kinds[k], count)
        allocs = []
        for i in range(count):
            feas, final = ref.select(spec, rnd)
            if not feas.any():
                break
            node = int(np.argmax(np.where(feas, final, -np.inf)))
            ref.place(spec, node)
            allocs.append({"index": i, "node": cluster.nodes[node]["id"],
                           "norm_score": None, "device_ids": []})
        ref.forget(spec["id"])
        jobs.append({"spec": spec, "allocs": allocs})
    return jobs


@pytest.mark.parametrize("name,count", [("baseline-10k", 8),
                                        ("c1m-5k", 40)])
def test_exact_passes_and_bfloat16_fails(name, count):
    cfg = json.load(open(os.path.join(BENCH, "configs", f"{name}.json")))
    cfg.update(cfg["rehearsal"])
    limits = json.load(open(os.path.join(
        BENCH, "limits", f"{name}.flood.json")))["limits"]
    for seed in (5, 2**31 + 17, 3000000019):
        cluster = cl.Cluster(cfg, seed)
        good = serve(cluster, cfg, seed, 64, count, exact)
        ids = {j["spec"]["id"] for j in good}
        n = check.replay(cluster, good, ids, rnd_control=bf16)
        n["unanswered"] = 0
        assert all(c["ok"] for c in check.verdict(n, limits).values()), n
        assert n["score_gap_max"] == 0.0 and n["compared"] == 64 * count
        held = check.verdict(n["control"], {k: limits[k]
                                            for k in n["control"]})
        assert set(held) == {"score_gap_max", "score_dev_max_pct",
                             "infeasible"}
        assert not held["score_dev_max_pct"]["ok"], n["control"]
        assert (not held["score_gap_max"]["ok"]) \
            or (not held["infeasible"]["ok"]), n["control"]

        bad = serve(cluster, cfg, seed, 64, count, bf16)
        n = check.replay(cluster, bad, ids)
        n["unanswered"] = 0
        v = check.verdict(n, limits)
        assert not all(c["ok"] for c in v.values()), n
        assert (not v["score_gap_max"]["ok"]) or (not v["infeasible"]["ok"])
