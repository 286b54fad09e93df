"""The partitioned cluster (ISSUE 36): every job is hard-constrained to one
of 64 `meta.cell` partitions.

Upstream's stack benchmark (`benchmarkServiceStack_MetaKeyConstraint`,
`scheduler/stack_test.go`) as a served deployment: node `i` carries
`meta.cell = c{i % 64}` and a job's constraint `${meta.cell} = cK` selects
one partition. A drain of 32 such evals partitions into about as many
conflict groups as it holds evals (`broker._group_picks` over
`Server._eval_footprint`), more than `SelectCoordinator._MAX_WAVE_LANES`,
so `_wave_lanes` packs several groups into a lane and the dispatch is a
wave of 8 lanes, each a sequence of groups that have nothing to do with
one another. Here a seeded feed goes through the SERVED path — `Server`,
broker drain, worker, `SelectCoordinator`, plan apply — and what it
committed is compared, allocation by allocation, with the plain
scheduler's serial replay (`scheduler/oracle.py`): every node and every
normalized score. `tests/test_pinned_deployment.py` holds the wave of
one group a lane to the same reference; this file the shared lanes, and
shows that the comparison catches two groups of ONE lane swapped.
"""
import random

import pytest

from nomad_tpu.structs.job import Constraint
from tests.test_pinned_deployment import (
    COUNT, N_FILLERS, N_NODES, _against_the_plain_scheduler as replay,
    _serve as serve)

CELLS = 64


def _spread(n, over, seed):
    """`n` jobs' partitions: `over` distinct ones, each at least once, in
    a seeded order."""
    rng = random.Random(seed)
    cells = rng.sample(range(CELLS), over)
    out = cells + [rng.choice(cells) for _ in range(n - over)]
    rng.shuffle(out)
    return out


#: name -> (partition of each job in the order enqueued, lanes x lane
#: length the one drain is laid out as)
FEEDS = {
    # 32 partitions, one eval each: four groups a lane
    "thirty-two-partitions": (list(range(0, 64, 2)), (8, 4)),
    # as the cell sends it: 32 evals drawn over 26 partitions
    "drawn-over-26": (_spread(32, 26, seed=36), (8, 4)),
    # 24 partitions, 32 evals: some groups hold two or three evals
    "drawn-over-24": (_spread(32, 24, seed=37), (8, 4)),
    # one partition takes nine of 32: LPT gives it a lane of its own
    # and the longest lane sets the bucket
    "one-partition-heavy": ([5] * 9 + list(range(10, 33)), (8, 16)),
    # nine groups for eight lanes: exactly one lane is shared
    "nine-partitions": (list(range(40, 49)), (8, 2)),
    # a short drain of many groups: 12 evals, 12 partitions
    "twelve-partitions": (list(range(3, 63, 5)), (8, 2)),
}


def _cluster(rng):
    """600 nodes, node i in partition c{i % 64} (every partition holds
    every class, as 3 and 64 share no factor), and seeded standing
    allocations so that no two nodes of a partition tie on the score."""
    from nomad_tpu.synth import synth_alloc, synth_node, synth_service_job

    nodes = []
    for i in range(N_NODES):
        n = synth_node(rng, i)
        n.meta["cell"] = f"c{i % CELLS}"
        n.compute_class()
        nodes.append(n)
    filler_job = synth_service_job(rng)
    fillers = [synth_alloc(rng, nodes[rng.randrange(N_NODES)], filler_job)
               for _ in range(N_FILLERS)]
    return nodes, fillers


def _jobs(rng, cells):
    """The deployment's stanza, built here and not by `perfbench`: every
    datacenter, `${attr.kernel.name} = linux`, `${meta.cell} = cK`."""
    from nomad_tpu.synth import synth_service_job

    jobs = []
    for i, k in enumerate(cells):
        j = synth_service_job(rng, count=COUNT)
        j.constraints.append(Constraint("${meta.cell}", f"c{k}", "="))
        # distinct asks: a job's nodes do not tie with a batch-mate's
        j.task_groups[0].tasks[0].resources.cpu = 110 + 17 * i
        j.task_groups[0].tasks[0].resources.memory_mb = 70 + 23 * i
        jobs.append(j)
    return jobs


def _serve(cells, monkeypatch, tamper=None):
    """`test_pinned_deployment._serve` over this file's cluster and jobs:
    ONE drain holds the whole feed."""
    return serve(cells, monkeypatch, tamper=tamper, seed=36,
                 cluster=_cluster, jobs=_jobs)


def _cell_of(job):
    return next(c.rtarget for c in job.constraints
                if c.ltarget == "${meta.cell}")


def _against_the_plain_scheduler(run):
    """The serial replay of `test_pinned_deployment`, the gate being the
    partition."""
    return replay(run, at_home=lambda job, node:
                  node.meta["cell"] == _cell_of(job),
                  outside="outside its partition")


@pytest.mark.parametrize("feed", sorted(FEEDS))
def test_a_partitioned_drain_places_what_the_plain_scheduler_places(
        feed, monkeypatch):
    cells, shape = FEEDS[feed]
    groups = len(set(cells))
    run = _serve(cells, monkeypatch)
    jobs, counters, hists = run.jobs, run.counters, run.hists
    assert counters.get("worker.0.batch.batched", 0) >= len(jobs)
    assert run.shapes == [shape], run.shapes  # one drain, laid out as stated
    assert hists["drain.groups"]["max"] == groups
    # more groups than lanes: a wave of eight, at least one lane shared
    assert counters.get("wave.dispatches", 0) == 1
    assert counters["wave.programs"] == len(jobs)
    assert counters["wave.slots"] == shape[0] * shape[1]
    assert counters.get("wave.collisions", 0) == 0
    assert counters.get("spec.rolled_back", 0) == 0
    assert hists["wave.lanes"]["max"] == 8
    # LPT: the longest lane is the largest group or the groups' fair share
    sizes = sorted((cells.count(k) for k in set(cells)), reverse=True)
    assert hists["wave.lane_len"]["max"] >= max(sizes[0],
                                                -(-len(jobs) // 8))
    assert hists["wave.lane_len"]["max"] <= shape[1]
    per_lane = [len({p.lut.tobytes() for p in lane})
                for lane in run.laid[0]]
    assert max(per_lane) > 1, per_lane  # several partitions' LUTs in a lane
    diffs = _against_the_plain_scheduler(run)
    assert not diffs, f"{len(diffs)} differ: {diffs[:4]}"
    # every allocation in its job's partition, and explained from it:
    # 9 or 10 of 600 nodes pass the constraint
    cell_of = {n.id: n.meta["cell"] for n in run.nodes}
    for j in jobs:
        want = _cell_of(j)
        n_cell = sum(1 for c in cell_of.values() if c == want)
        for a in run.served[j.id]:
            assert cell_of[a.node_id] == want, (j.id, a.name)
            m = a.metrics
            assert m.nodes_evaluated == N_NODES
            assert m.nodes_evaluated - m.nodes_filtered == n_cell
            assert {cell_of[sm.node_id] for sm in m.score_meta} == {want}


def test_the_comparison_catches_two_groups_of_one_lane_swapped(monkeypatch):
    """The negative: inside ONE lane the first programs of two groups get
    each other's slot, so each reads what the kernel placed for the other
    partition. Plan apply finds room on those nodes and commits; the
    replay must not agree."""
    hit = []

    def swap(idxs, lanes_idx, lanes):
        for li, lane in enumerate(lanes):
            luts = [r.params.lut.tobytes() for r in lane]
            other = next((p for p in range(1, len(lane))
                          if luts[p] != luts[0]), None)
            if other is not None:
                a, b = lanes_idx[li][0], lanes_idx[li][other]
                idxs[a], idxs[b] = idxs[b], idxs[a]
                hit.append((a, b))
                return

    cells, _shape = FEEDS["drawn-over-26"]
    run = _serve(cells, monkeypatch, tamper=swap)
    assert run.counters.get("wave.dispatches", 0) >= 1 and hit
    diffs = _against_the_plain_scheduler(run)
    assert any(d[2] == "outside its partition" for d in diffs), diffs[:4]
    a, b = hit[0]
    # `reqs` of the dispatch are in the lanes' order; the jobs that own
    # the two swapped slots are both wrong
    order = [p for lane in run.laid[0] for p in lane]
    wrong = {d[0] for d in diffs}
    by_ask = {(float(j.task_groups[0].tasks[0].resources.cpu)): j.id
              for j in run.jobs}
    owners = {by_ask[float(order[i].ask[0])] for i in (a, b)}
    assert owners <= wrong, (owners, diffs[:4])
