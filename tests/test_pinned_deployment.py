"""The zonal deployment (ISSUE 32): every job names one datacenter.

A drain of such jobs partitions into one conflict group a datacenter
(`broker._group_picks` over `Server._eval_footprint`), and the fused
dispatch runs the groups as lanes of `place_table_wave`. Here a seeded
feed goes through the SERVED path — `Server`, broker drain, worker,
`SelectCoordinator`, plan apply — and what it committed is compared,
allocation by allocation, with the plain scheduler's serial replay
(`scheduler/oracle.py`: one eval after the other in the order they were
enqueued, each against what the earlier ones left): every node and every
normalized score. The kernel-level parity of chain and wave is
`tests/test_drain.py`'s; this file holds the layers above it to the
reference, and shows that the comparison catches a wave that mixes up its
lanes.
"""
import random
import time
import types

import pytest

from nomad_tpu.scheduler.oracle import OracleContext, select_option

N_NODES, N_FILLERS, COUNT = 600, 1500, 4
DCS = ("dc1", "dc2", "dc3")
TERMINAL = ("complete", "failed", "blocked", "cancelled")

#: name -> (datacenter of each job in the order enqueued, lanes x lane
#: length the one drain is laid out as; None: the sequential chain)
FEEDS = {
    "equal-thirds": ([DCS[i % 3] for i in range(12)], (4, 4)),
    "one-datacenter-absent": ([DCS[2 * (i % 2)] for i in range(8)], (2, 4)),
    "one-datacenter": (["dc2"] * 6, None),
    "uneven-lanes": (["dc1"] * 9 + ["dc2"] * 2 + ["dc3"], (4, 16)),
}


def _cluster(rng):
    """600 nodes, every datacenter holding every class (as the
    benchmark's cluster), and seeded standing allocations so that no two
    nodes of a datacenter tie on the score."""
    from nomad_tpu.synth import synth_alloc, synth_node, synth_service_job

    nodes = []
    for i in range(N_NODES):
        n = synth_node(rng, i)
        n.datacenter = DCS[(i // 3) % 3]
        n.compute_class()
        nodes.append(n)
    filler_job = synth_service_job(rng)
    fillers = [synth_alloc(rng, nodes[rng.randrange(N_NODES)], filler_job)
               for _ in range(N_FILLERS)]
    return nodes, fillers


def _jobs(rng, dcs):
    from nomad_tpu.synth import synth_service_job

    jobs = []
    for i, dc in enumerate(dcs):
        j = synth_service_job(rng, count=COUNT, datacenter=dc)
        # distinct asks: a job's nodes do not tie with a batch-mate's
        j.task_groups[0].tasks[0].resources.cpu = 210 + 37 * i
        j.task_groups[0].tasks[0].resources.memory_mb = 130 + 53 * i
        jobs.append(j)
    return jobs


def _serve(feed, monkeypatch, tamper=None, seed=32, cluster=None, jobs=None):
    """The feed through a `Server` with fused batches of 32, every job
    registered before the worker starts: ONE drain holds them all.
    `tamper(idxs, lanes_idx, lanes)` may spoil a wave's slots in place.
    `cluster(rng)` and `jobs(rng, feed)` build another deployment than
    this file's (`tests/test_computed_class_deployment.py`).
    -> nodes, fillers, jobs, served ({job id: allocations by index}),
    counters, hists, shapes (what the table dispatches were laid out as),
    laid (the programs of each lane of each dispatch)."""
    from nomad_tpu.server import Server, ServerConfig, select_batch

    monkeypatch.delenv("NOMAD_TPU_EVAL_BATCH", raising=False)
    shapes, laid = [], []
    layout = select_batch._table_layout

    def spy(lanes):
        out = layout(lanes)
        shapes.append(out[3])
        laid.append([[r.params for r in lane] for lane in lanes])
        if tamper is not None and out[2] is not None:
            tamper(out[2], out[4], lanes)
        return out

    monkeypatch.setattr(select_batch, "_table_layout", spy)
    rng = random.Random(seed)
    s = Server(ServerConfig(num_schedulers=1, heartbeat_ttl=3600.0,
                            eval_batch=32))
    nodes, fillers = (cluster or _cluster)(rng)
    for n in nodes:
        s.state.upsert_node(n)
    for a in fillers:
        s.state.upsert_alloc(a)
    jobs = (jobs or _jobs)(rng, feed)
    evs = [s.job_register(j) for j in jobs]
    s.start()
    try:
        for ev in evs:
            got = s.wait_for_eval(ev.id, statuses=TERMINAL, timeout=120.0)
            assert got is not None and got.status == "complete", got
        # a batch's counts are folded in once the batch has ended, which
        # is after its last eval was answered
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and s.metrics.counters().get(
                "worker.0.batch.batched", 0) < len(jobs):
            time.sleep(0.05)
        counters = s.metrics.counters()
        hists = s.metrics.snapshot()["histograms"]
        served = {j.id: sorted(
            s.state.allocs_by_job("default", j.id),
            key=lambda a: int(a.name.rsplit("[", 1)[1][:-1])) for j in jobs}
    finally:
        s.shutdown()
    return types.SimpleNamespace(
        nodes=nodes, fillers=fillers, jobs=jobs, served=served,
        counters=counters, hists=hists, shapes=shapes, laid=laid)


def _in_its_datacenter(job, node):
    return node.datacenter in job.datacenters


def _against_the_plain_scheduler(run, at_home=_in_its_datacenter,
                                 outside="outside its datacenter"):
    """The serial replay: eval by eval in the order enqueued, allocation
    by allocation, the served node applied after each question. -> the
    list of what differs (empty: every node and score equal).
    `at_home(job, node)`: the deployment's gate, named `outside` where a
    served node fails it."""
    nodes = run.nodes
    by_node = {}
    for a in run.fillers:
        by_node.setdefault(a.node_id, []).append(a)
    node_of = {n.id: n for n in nodes}
    diffs = []
    for j in run.jobs:
        allocs = run.served[j.id]
        if len(allocs) != COUNT:
            diffs.append((j.id, "count", len(allocs)))
        placed = {}
        for a in allocs:
            ctx = OracleContext(nodes=nodes, allocs_by_node=by_node,
                                plan_node_alloc=placed)
            opt = select_option(ctx, j, j.task_groups[0])
            score = next((sm.norm_score for sm in a.metrics.score_meta
                          if sm.node_id == a.node_id), None)
            if not at_home(j, node_of[a.node_id]):
                diffs.append((j.id, a.name, outside, a.node_id))
            elif opt is None or a.node_id != opt.node.id:
                diffs.append((j.id, a.name, "node", a.node_id,
                              opt and opt.node.id))
            elif score is None or abs(score - opt.final_score) >= 1e-4:
                diffs.append((j.id, a.name, "score", score,
                              opt.final_score))
            placed.setdefault(a.node_id, []).append(a)
        for nid, on in placed.items():
            by_node.setdefault(nid, []).extend(on)
    return diffs


@pytest.mark.parametrize("feed", sorted(FEEDS))
def test_a_pinned_drain_places_what_the_plain_scheduler_places(
        feed, monkeypatch):
    dcs, shape = FEEDS[feed]
    run = _serve(dcs, monkeypatch)
    nodes, jobs, counters, hists = run.nodes, run.jobs, run.counters, run.hists
    assert counters.get("worker.0.batch.batched", 0) >= len(jobs)
    assert run.shapes == [shape], run.shapes  # one drain, laid out as stated
    if shape is None:  # one conflict group: the sequential chain
        assert counters.get("wave.dispatches", 0) == 0
        assert hists["drain.groups"]["max"] == 1
    else:  # it cannot pass on the chain
        assert counters.get("wave.dispatches", 0) >= 1
        assert counters["wave.programs"] == len(jobs)
        assert counters["wave.slots"] == shape[0] * shape[1]
        assert counters.get("wave.collisions", 0) == 0
        assert hists["wave.lanes"]["max"] == len(set(dcs))
        assert hists["drain.groups"]["max"] == len(set(dcs))
    diffs = _against_the_plain_scheduler(run)
    assert not diffs, f"{len(diffs)} differ: {diffs[:4]}"
    if feed == "uneven-lanes":
        # the explanation rides the same fetch and is cut from the same
        # [lane, position] slot: each allocation counts its own
        # datacenter's nodes and ranks none from another
        per_dc = {dc: sum(1 for n in nodes if n.datacenter == dc)
                  for dc in DCS}
        dc_of = {n.id: n.datacenter for n in nodes}
        for j in jobs:
            for a in run.served[j.id]:
                m = a.metrics
                assert m.nodes_evaluated == N_NODES
                assert m.nodes_evaluated - m.nodes_filtered \
                    == per_dc[j.datacenters[0]], (j.id, a.name)
                assert m.score_meta and {dc_of[sm.node_id]
                                         for sm in m.score_meta} \
                    == set(j.datacenters), (j.id, a.name)


def test_the_comparison_catches_two_lanes_swapped(monkeypatch):
    """The negative: the layout hands the first programs of two lanes
    each other's slot, so each reads the other lane's result. Plan apply
    finds room on those nodes and commits; the replay must not agree."""
    def swap(idxs, lanes_idx, _lanes):
        a, b = lanes_idx[0][0], lanes_idx[1][0]
        idxs[a], idxs[b] = idxs[b], idxs[a]

    dcs, _shape = FEEDS["equal-thirds"]
    run = _serve(dcs, monkeypatch, tamper=swap)
    assert run.counters.get("wave.dispatches", 0) >= 1
    diffs = _against_the_plain_scheduler(run)
    assert any(d[2] == "outside its datacenter" for d in diffs), diffs[:4]
    wrong = {d[0] for d in diffs}
    assert {run.jobs[0].id, run.jobs[1].id} <= wrong, diffs[:4]
