"""An eval's static footprint mask is computed once per (datacenters,
constraints, node table) — ISSUE 33.

`Server._eval_footprint` keeps the part of a footprint that only the node
table decides in `ClusterTensors.static_masks`. What this file holds it
to: a cached estimate is, element for element, what a fresh one returns
(`_fresh` below is the estimator as it stood before the cache, every
call computed anew), for the job shapes the benchmark's four cells send
(`perfbench/cluster.py make_job`, through `perfbench/adapter.py`) and
the shapes they do not; every node write and every growth of the
attribute table retires the cached masks; what comes back cannot be
written through; the cache keeps its bound.
"""
import copy
import importlib.util
import json
import os
import random

import numpy as np
import pytest

from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.server import _constraint_mask
from nomad_tpu.structs import Evaluation
from nomad_tpu.structs.job import Constraint
from nomad_tpu.synth import synth_alloc
from nomad_tpu.tensor.cluster import ClusterTensors

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_NODES = 150          # a 256-row bucket with room: no growth by accident
CELL_KINDS = ("binpack", "affinity", "spread", "pinned-dc1", "pinned-dc2",
              "pinned-dc3", "distinct-cell", "devices")


def _bench(name):
    """A module of the benchmark, by path (`perfbench/` is no package)."""
    spec = importlib.util.spec_from_file_location(
        f"_fp_{name}", os.path.join(ROOT, "perfbench", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bench_cluster, adapter = _bench("cluster"), _bench("adapter")
with open(os.path.join(ROOT, "perfbench", "configs",
                       "baseline-10k.json")) as _f:
    CFG = dict(json.load(_f), nodes=N_NODES, allocs=0)


def _fresh(server, ev):
    """`Server._eval_footprint` as it was before ISSUE 33: the same steps
    on the same tables, nothing kept from one call to the next."""
    if not ev.job_id:
        return None
    cl = server.state.cluster
    attrs = cl.attrs
    n = attrs.shape[0]
    job = server.state.job_by_id(ev.namespace, ev.job_id)
    if job is not None:
        if job.datacenters:
            k_dc = cl.vocab.lookup_key("node.datacenter")
            if k_dc < 0 or k_dc >= attrs.shape[1]:
                return None
            kv = cl.vocab.key_vocabs[k_dc]
            toks = [t for t in (kv.lookup(dc) for dc in job.datacenters)
                    if t >= 0]
            mask = (np.isin(attrs[:, k_dc], toks) if toks
                    else np.zeros(n, dtype=bool))
        else:
            mask = np.ones(n, dtype=bool)
        mask &= _constraint_mask(cl, attrs, job.constraints, n)
        tg_union = None
        for tg in job.task_groups:
            cons = list(tg.constraints)
            for t in tg.tasks:
                cons.extend(t.constraints)
            m = _constraint_mask(cl, attrs, cons, n)
            tg_union = m if tg_union is None else (tg_union | m)
        if tg_union is not None:
            mask &= tg_union
        if not job.datacenters and bool(mask.all()):
            return None
    else:
        mask = np.zeros(n, dtype=bool)
    for row, _tg in cl.job_allocs.get(ev.job_id, {}).values():
        if 0 <= row < n:
            mask[row] = True
    if ev.node_id:
        row = cl.row_of.get(ev.node_id)
        if row is not None and row < n:
            mask[row] = True
    return mask


def _same(a, b):
    if a is None or b is None:
        return a is None and b is None
    return a.dtype == b.dtype and a.shape == b.shape and bool((a == b).all())


def _server(n_nodes=N_NODES, seed=33):
    """A server that is never started, holding the benchmark's cluster cut
    to `n_nodes` (three classes, three datacenters each holding every
    class, GPUs on every fourth node, `meta.cell`)."""
    s = Server(ServerConfig(num_schedulers=1, heartbeat_ttl=3600.0))
    nodes = [adapter.to_node(r) for r in bench_cluster.make_nodes(
        dict(CFG, nodes=n_nodes), seed)]
    for n in nodes:
        s.state.upsert_node(n)
    return s, nodes


def _job(kind="binpack", k=0, seed=33):
    return adapter.to_job(bench_cluster.make_job(CFG, seed, k, kind, 8))


def _eval_of(s, job, node_id=""):
    s.state.upsert_job(job)
    return Evaluation(namespace=job.namespace, job_id=job.id,
                      type=job.type, priority=job.priority,
                      node_id=node_id)


def _empty(s):
    s.state.cluster.static_masks()[1].clear()


def _other_shapes():
    """name -> job: what no cell sends."""
    no_dc = _job(k=101)
    no_dc.datacenters = []
    no_dc.constraints = [Constraint("${node.class}", "linux-large", "=")]
    unbounded = _job(k=102)
    unbounded.datacenters = []
    unbounded.constraints = []  # empty rows fail even `kernel.name = linux`
    two_groups = _job("pinned-dc2", k=103)
    tg = copy.deepcopy(two_groups.task_groups[0])
    tg.name = "db"
    tg.constraints = [Constraint("${node.class}", "linux-small", "!=")]
    tg.tasks[0].constraints = [Constraint("${meta.cell}", "c7", "=")]
    two_groups.task_groups.append(tg)
    absent_dc = _job(k=104)
    absent_dc.datacenters = ["dc9"]
    return {"no-datacenters-node-class": no_dc,
            "no-datacenters-nothing-narrows": unbounded,
            "two-groups-task-constraint": two_groups,
            "datacenter-absent": absent_dc}


@pytest.fixture(scope="module")
def standing():
    return _server()


@pytest.mark.parametrize("shape", CELL_KINDS + tuple(_other_shapes()))
def test_cached_is_fresh_for_a_new_job(standing, shape):
    """Miss, then hit, each equal to a fresh estimate; the hit is the
    cached array itself, read-only, or the cached verdict None."""
    s, _nodes = standing
    job = (_job(shape, k=CELL_KINDS.index(shape)) if shape in CELL_KINDS
           else _other_shapes()[shape])
    ev = _eval_of(s, job)
    _empty(s)
    fresh = _fresh(s, ev)
    est0, hits0 = s._fp_estimates, s._fp_hits
    miss = s._eval_footprint(ev)
    hit = s._eval_footprint(ev)
    assert _same(miss, fresh) and _same(hit, fresh)
    assert (s._fp_estimates - est0, s._fp_hits - hits0) == (2, 1)
    if shape == "no-datacenters-nothing-narrows":
        assert fresh is None
        return
    assert hit is miss and not hit.flags.writeable
    if shape.startswith("pinned-") or shape == "two-groups-task-constraint":
        assert 0 < int(fresh.sum()) < N_NODES  # it narrows
    # a second job of the same shape asks nothing of NumPy
    twin = (_job(shape, k=40 + CELL_KINDS.index(shape))
            if shape in CELL_KINDS else None)
    if twin is not None:
        assert s._eval_footprint(_eval_of(s, twin)) is hit


def test_an_eval_without_a_job_has_no_footprint_and_no_lookup(standing):
    s, _nodes = standing
    est0 = s._fp_estimates
    assert s._eval_footprint(Evaluation(type="_core", job_id="")) is None
    assert s._fp_estimates == est0


@pytest.mark.parametrize("dynamic", ["current-allocations", "node-id",
                                     "both", "job-gone"])
def test_the_rows_that_move_are_set_in_a_copy(standing, dynamic):
    """The job's current allocation rows and the eval's own node row are
    read at every call and never reach the cached mask."""
    s, nodes = standing
    rng = random.Random(dynamic)
    job = _job("pinned-dc1", k=200 + len(dynamic))
    cl = s.state.cluster
    # rows outside the job's datacenter: the static mask lacks them
    outside = [n for n in nodes if n.datacenter != "dc1"]
    ev = _eval_of(s, job, node_id=(outside[0].id if dynamic in
                                   ("node-id", "both", "job-gone") else ""))
    if dynamic in ("current-allocations", "both", "job-gone"):
        for n in outside[1:4]:
            s.state.upsert_alloc(synth_alloc(rng, n, job))
    if dynamic == "job-gone":
        s.state.delete_job(job.namespace, job.id)
        assert s.state.job_by_id(job.namespace, job.id) is None
    _empty(s)
    fresh = _fresh(s, ev)
    first, second = s._eval_footprint(ev), s._eval_footprint(ev)
    assert _same(first, fresh) and _same(second, fresh)
    assert first is not second and first.flags.writeable
    want = {cl.row_of[n.id] for n in
            {"current-allocations": outside[1:4], "node-id": outside[:1],
             "both": outside[:4], "job-gone": outside[:4]}[dynamic]}
    assert all(fresh[r] for r in want)
    if dynamic != "job-gone":
        cached = cl.static_masks()[1]
        (static,) = cached.values()
        assert not any(static[r] for r in want)
        # the next new job of the shape gets the static mask, unspoiled
        assert s._eval_footprint(_eval_of(s, _job("pinned-dc1", k=77))) \
            is static


def _grown_keys(node):
    for i in range(80):  # past the 64-column bucket
        node.meta[f"extra{i}"] = "x"


WRITES = {
    # name -> what is done to the cluster; each changes dc1's mask
    "new-node": lambda s, nodes, extra: s.state.upsert_node(extra),
    "changed-attribute": lambda s, nodes, extra: (
        nodes[0].attributes.__setitem__("kernel.name", "plan9"),
        s.state.upsert_node(nodes[0])),
    "changed-datacenter": lambda s, nodes, extra: (
        setattr(nodes[0], "datacenter", "dc2"),
        s.state.upsert_node(nodes[0])),
    "removed-node": lambda s, nodes, extra: s.state.delete_node(nodes[0].id),
    "row-bucket-growth": lambda s, nodes, extra: s.state.upsert_node(extra),
    "key-bucket-growth": lambda s, nodes, extra: (
        _grown_keys(extra), s.state.upsert_node(extra)),
}


@pytest.mark.parametrize("write", sorted(WRITES))
def test_a_node_write_retires_the_cached_masks(write):
    """After every kind of node write a cached estimate is again what a
    fresh one returns — and not what it was."""
    # 64 nodes fill the smallest row bucket: the 65th doubles it
    s, nodes = _server(n_nodes=64 if write == "row-bucket-growth" else 40)
    cl = s.state.cluster
    extra = adapter.to_node(bench_cluster.make_nodes(
        dict(CFG, nodes=1), 7)[0])
    assert nodes[0].datacenter == extra.datacenter == "dc1"
    evs = [_eval_of(s, _job(kind, k=i)) for i, kind in enumerate(
        ("pinned-dc1", "pinned-dc2", "binpack"))]
    before = [s._eval_footprint(ev) for ev in evs]
    assert all(s._eval_footprint(ev) is b for ev, b in zip(evs, before))
    attrs, version = cl.attrs, cl.node_version
    WRITES[write](s, nodes, extra)
    assert cl.node_version > version
    assert (cl.attrs is not attrs) == (write in ("row-bucket-growth",
                                                 "key-bucket-growth"))
    after = [s._eval_footprint(ev) for ev in evs]
    for ev, b, a in zip(evs, before, after):
        assert _same(a, _fresh(s, ev))
        assert a is not b
    # dc1's mask is another mask now; what stood is as it was
    assert not _same(after[0], before[0])
    assert int(before[0].sum()) == sum(
        1 for r in bench_cluster.make_nodes(dict(CFG, nodes=len(nodes)), 33)
        if r["datacenter"] == "dc1")
    assert len(cl.static_masks()[1]) == 3


def test_what_comes_back_cannot_be_written_through(standing):
    """The cached array is read-only; the broker's partition merges into
    copies, so a drain leaves every cached mask as it was."""
    s, _nodes = standing
    _empty(s)
    evs = [_eval_of(s, _job(kind, k=300 + i)) for i, kind in enumerate(
        ("pinned-dc1", "pinned-dc2", "binpack", "pinned-dc1", "spread"))]
    fps = [s._eval_footprint(ev) for ev in evs]
    with pytest.raises(ValueError):
        fps[0][0] = True
    with pytest.raises(ValueError):
        fps[0] |= fps[1]
    mine = fps[0].astype(bool)
    mine[:] = True                      # a caller's copy is the caller's
    kept = {k: v.copy() for k, v in s.state.cluster.static_masks()[1].items()}
    groups = s.broker._group_picks([(ev, "") for ev in evs])
    assert [[ev.job_id for ev, _t in g] for g in groups] == [
        [ev.job_id for ev in evs]]      # binpack spans every datacenter
    groups = s.broker._group_picks([(ev, "") for ev in evs[:2] + evs[3:4]])
    assert [len(g) for g in groups] == [2, 1]
    now = s.state.cluster.static_masks()[1]
    assert now.keys() == kept.keys()
    assert all(_same(now[k], kept[k]) and not now[k].flags.writeable
               for k in kept)
    assert _same(s._eval_footprint(evs[0]), _fresh(s, evs[0]))


def _partition_evals(s, n, k0=400):
    """`n` evals, each of a job hard-constrained to a `meta.cell` value of
    its own (ISSUE 36's stanza): `n` distinct static shapes."""
    evs = []
    for i in range(n):
        job = _job("binpack", k=k0 + i)
        job.constraints.append(Constraint("${meta.cell}", f"c{i}", "="))
        evs.append(_eval_of(s, job))
    return evs


def _bound_of(monkeypatch, s, masks):
    """Hold the cache to `masks` masks at this table's rows."""
    monkeypatch.setattr(ClusterTensors, "STATIC_MASKS_BYTES",
                        masks * s.state.cluster.n_cap)


#: 65 shapes through a cache of 64, each eval looked up twice as a drain
#: does (the partition's estimate, then `start_batch`'s) -> the least hit
#: share. In rotation every first lookup misses whatever goes by age, and
#: the second finds what the first stored; in shuffled blocks (how a cell
#: deals its kinds) a first lookup misses only on the one shape that is
#: out.
ORDERS = {"rotation": 50.0, "shuffled-blocks": 97.0}


@pytest.mark.parametrize("order", sorted(ORDERS))
def test_one_shape_past_the_bound_costs_one_mask_a_miss(
        standing, monkeypatch, order):
    s, _nodes = standing
    _empty(s)
    cl = s.state.cluster
    bound = 64
    _bound_of(monkeypatch, s, bound)
    evs = _partition_evals(s, bound + 1)
    rng = random.Random(36)
    feed = []
    for _ in range(12):
        block = list(evs)
        if order == "shuffled-blocks":
            rng.shuffle(block)
        feed.extend(block)
    gone0 = cl.static_mask_evictions
    s.count_footprints()
    c0 = dict(s.metrics.counters())
    sizes, lookups = [], 0
    for ev in feed[:bound + 1]:         # the first pass: all misses
        assert _same(s._eval_footprint(ev), _fresh(s, ev))
        sizes.append(len(cl.static_masks()[1]))
    # the bound is met and kept: the 65th store took ONE mask out
    assert sizes[bound - 1] == sizes[bound] == bound
    assert cl.static_mask_evictions - gone0 == 1
    s.count_footprints()
    c1 = dict(s.metrics.counters())
    for ev in feed[bound + 1:]:
        for _ in range(2):
            got = s._eval_footprint(ev)
            assert _same(got, _fresh(s, ev))
            assert not got.flags.writeable  # after eviction and re-insert too
            lookups += 1
        assert len(cl.static_masks()[1]) == bound
    s.count_footprints()
    c2 = dict(s.metrics.counters())
    est = c2["drain.footprint_estimates"] - c1["drain.footprint_estimates"]
    hits = c2["drain.footprint_hits"] - c1["drain.footprint_hits"]
    gone = c2["drain.footprint_evictions"] - c1["drain.footprint_evictions"]
    assert est == lookups
    assert 100.0 * hits / est >= ORDERS[order], (hits, est)
    assert gone == est - hits           # a miss at the bound: one victim
    assert c1["drain.footprint_evictions"] \
        - c0["drain.footprint_evictions"] == 1
    assert cl.static_mask_evictions - gone0 == 1 + gone


def test_the_bound_is_reckoned_from_the_tables_rows(standing, monkeypatch):
    """A mask is one byte a row: the cache holds `STATIC_MASKS_BYTES` of
    them, so ISSUE 36's 64 partitions fit at both row buckets the
    benchmark runs (8,192 and 16,384) with room, and a table of a
    million rows still keeps four."""
    budget = ClusterTensors.STATIC_MASKS_BYTES
    assert budget // 8192 >= 4 * 64 and budget // 16384 >= 4 * 64
    assert budget // (1 << 20) == 4
    s, _nodes = standing
    _empty(s)
    cl = s.state.cluster
    _bound_of(monkeypatch, s, 3)
    evs = _partition_evals(s, 5, k0=480)
    keys = []
    for ev in evs:
        s._eval_footprint(ev)
        keys.append(list(cl.static_masks()[1]))
    assert [len(k) for k in keys] == [1, 2, 3, 3, 3]
    assert keys[3] == keys[2][1:] + keys[3][-1:]    # the oldest went
    assert keys[4] == keys[3][1:] + keys[4][-1:]
    # a key that is held is stored over, not evicted for
    gone = cl.static_mask_evictions
    masks = cl.static_masks()[1]
    cl.masks_put(masks, keys[4][0], masks[keys[4][0]].copy())
    assert cl.static_mask_evictions == gone and list(masks) == keys[4]


def test_lookups_reach_the_registry_once_a_drain(standing):
    s, _nodes = standing
    names = ("drain.footprint_estimates", "drain.footprint_hits")

    def counted():
        c = s.metrics.counters()
        return [c[n] for n in names]    # there from the start, at 0

    s.count_footprints()
    c0 = counted()
    _empty(s)
    ev = _eval_of(s, _job("pinned-dc3", k=500))
    for _ in range(4):
        s._eval_footprint(ev)
    assert counted() == c0              # plain integers until counted
    s.count_footprints()
    s.count_footprints()                # nothing new: nothing added
    assert [b - a for a, b in zip(c0, counted())] == [4, 3]


def test_a_mask_made_before_a_node_write_is_not_served_after_it(
        monkeypatch):
    """The order that matters with no lock: the version is read before
    the table. A node write that lands while a mask is being computed
    retires it with everything else filed under the old version."""
    from nomad_tpu.server import server as server_mod

    s, nodes = _server(n_nodes=40)
    ev = _eval_of(s, _job("pinned-dc1", k=600))
    moved = next(n for n in nodes if n.datacenter == "dc1")
    compute = server_mod._static_footprint

    def compute_then_the_node_moves(cl, attrs, job, n):
        mask = compute(cl, attrs, job, n)
        moved.datacenter = "dc3"
        s.state.upsert_node(moved)      # as another thread would
        return mask

    monkeypatch.setattr(server_mod, "_static_footprint",
                        compute_then_the_node_moves)
    stale = s._eval_footprint(ev)
    monkeypatch.setattr(server_mod, "_static_footprint", compute)
    row = s.state.cluster.row_of[moved.id]
    assert stale[row]                   # what a racing reader may see
    now = s._eval_footprint(ev)
    assert not now[row] and _same(now, _fresh(s, ev))


def test_readers_and_a_writer_without_a_lock():
    """More estimating threads than cores against a thread that moves
    nodes between datacenters, the interpreter switching threads every
    few bytecodes: whatever was served meanwhile, every mask handed out
    is whole, and once the writes have stopped a cached estimate is a
    fresh one."""
    import sys
    import threading
    import time

    s, nodes = _server(n_nodes=40)
    evs = [_eval_of(s, _job(kind, k=700 + i)) for i, kind in enumerate(
        ("pinned-dc1", "pinned-dc2", "pinned-dc3", "binpack"))]
    stop = threading.Event()
    errors, served = [], [0]

    def reader():
        try:
            while not stop.is_set():
                for ev in evs:
                    fp = s._eval_footprint(ev)
                    if fp.dtype != bool or fp.shape[0] < len(nodes):
                        errors.append(("shape", fp.dtype, fp.shape))
                    served[0] += 1
        except Exception as e:  # noqa: BLE001 — the assertion is below
            errors.append(e)

    def writer():
        rng = random.Random(9)
        for _ in range(150):
            n = rng.choice(nodes)
            n.datacenter = rng.choice(("dc1", "dc2", "dc3"))
            s.state.upsert_node(n)
        extra = adapter.to_node(bench_cluster.make_nodes(
            dict(CFG, nodes=1), 11)[0])
        _grown_keys(extra)              # the table itself is swapped
        s.state.upsert_node(extra)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        readers = [threading.Thread(target=reader, daemon=True)
                   for _ in range(2 * (os.cpu_count() or 4))]
        w = threading.Thread(target=writer, daemon=True)
        for t in readers + [w]:
            t.start()
        w.join(60.0)
        time.sleep(0.05)
        stop.set()
        for t in readers:
            t.join(30.0)
    finally:
        sys.setswitchinterval(interval)
    assert not w.is_alive() and not any(t.is_alive() for t in readers)
    assert not errors, errors[:3]
    assert served[0] > len(evs)
    for ev in evs:
        assert _same(s._eval_footprint(ev), _fresh(s, ev))
    assert len(s.state.cluster.static_masks()[1]) <= len(evs)
