"""`traffic/pinned96-drained.json`'s warm-up against the program's own
layout rule: every program shape a drain of pinned evals can take in the
window has been met, and so compiled, before the window opens.

A drain of evals that each name one datacenter partitions into one conflict
group a datacenter; `SelectCoordinator._wave_lanes` makes the groups lanes
and `select_batch._table_layout` buckets lanes and lane length to powers of
two. A dispatch whose shape is new compiles inside the window, and has
placed a program against a wrong view (PERF.md §7 third; ROADMAP B7).

How a burst leaves the broker was read on the chip (PERF.md §6, PR 32): a
burst of a few jobs is ONE drain, or its first job is scheduled alone (an
idle queue hands its one eval over at once) and the rest are one drain; a
burst of 65 leaves as 1 + 32 + 32 or as three drains of which one is 32
wide. So a short burst gives its shape either way, and a shape with a lane
longer than 8 comes from 65 jobs of a repeating pattern, of which any 32 in
a row (and any 27) hold the pattern's shares.
"""
import collections
import json
import os

import numpy as np
import pytest

from nomad_tpu.server.select_batch import SelectCoordinator, _table_layout

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAFFIC = json.load(open(os.path.join(BENCH, "traffic",
                                      "pinned96-drained.json")))
EVAL_BATCH = 32  # ServerConfig.eval_batch: the widest drain

_P = collections.namedtuple("_P", "n_place delta_idx delta_res pclr_idx "
                            "pclr_port pset_idx pset_port")
_Req = collections.namedtuple("_Req", "order params")
_PARAMS = _P(np.int32(8), *(np.zeros(2, np.int32) for _ in range(6)))


def kinds_of(burst):
    """The kinds of a burst's jobs in the order sent (`Generator.burst`)."""
    return [burst["kinds"][i % len(burst["kinds"])]
            for i in range(burst["n"])]


def shape_of(kinds):
    """(lanes, lane length) one drain of jobs of these kinds is laid out
    as; one lane is the chain."""
    groups = {k: g for g, k in enumerate(dict.fromkeys(kinds))}
    coord = SelectCoordinator()
    coord.group_ids = {i: groups[k] for i, k in enumerate(kinds)}
    lanes = coord._wave_lanes([_Req(i, _PARAMS) for i in range(len(kinds))])
    _reqs, params_list, _idxs, shape, _lanes_idx = _table_layout(lanes)
    return shape or (1, len(params_list))


def every_shape_a_drain_can_take():
    dcs = ("pinned-dc1", "pinned-dc2", "pinned-dc3")
    out = set()
    for a in range(1, EVAL_BATCH + 1):
        for b in range(0, min(a, EVAL_BATCH - a) + 1):
            for c in range(0, min(b, EVAL_BATCH - a - b) + 1):
                out.add(shape_of([dcs[0]] * a + [dcs[1]] * b + [dcs[2]] * c))
    return out


def test_a_drain_of_pinned_evals_takes_one_of_fifteen_shapes():
    lengths = (2, 4, 8, 16, 32)
    assert every_shape_a_drain_can_take() == {
        (lanes, n) for lanes in (1, 2, 4) for n in lengths}


@pytest.mark.parametrize("ahead", [0, 1])
def test_the_bursts_reach_every_shape_twice(ahead):
    """`ahead` jobs of a burst are scheduled before the rest drain, 32 at
    a time."""
    met = collections.Counter()
    for b in TRAFFIC["warmup"]["bursts"]:
        rest = kinds_of(b)[ahead:]
        for at in range(0, len(rest), EVAL_BATCH):
            if len(rest) - at > 1:  # one eval alone rides no table
                met[shape_of(rest[at:at + EVAL_BATCH])] += 1
    want = every_shape_a_drain_can_take()
    assert set(met) == want, sorted(want - set(met))
    # the second pass is the speculative launch's
    assert all(n >= 2 for n in met.values()), met


def test_a_loaded_burst_keeps_its_shape_wherever_the_drains_fall():
    """A burst too long for one drain (lane length over 8) repeats a
    pattern, so that ANY 32 jobs in a row, and any 27, give its shape."""
    long_ones = [b for b in TRAFFIC["warmup"]["bursts"] if b["n"] > 33]
    assert len(long_ones) == 10
    for b in long_ones:
        kinds = kinds_of(b)
        want = shape_of(kinds[1:1 + EVAL_BATCH])
        assert want[1] >= 16, b
        for width in (27, EVAL_BATCH):
            assert {shape_of(kinds[at:at + width])
                    for at in range(1, len(kinds) - width)} == {want}, b


def test_the_cell_is_the_issues():
    t = TRAFFIC
    assert (t["loop"], t["window"], t["outstanding"], t["count"],
            t["senders"], t["timeout_s"]) == ("closed", "drained", 96, 8,
                                              8, 60)
    cfg = json.load(open(os.path.join(BENCH, "configs", "pinned-10k.json")))
    assert cfg["mix"] == {"pinned-dc1": 1, "pinned-dc2": 1, "pinned-dc3": 1}
    base = json.load(open(os.path.join(BENCH, "configs",
                                       "baseline-10k.json")))
    for k in ("nodes", "allocs", "row_bucket", "classes", "node_cpu_mhz",
              "node_memory_mib", "node_disk_mib", "reserved", "datacenters",
              "racks", "cells", "gpu_every", "gpus_per_node", "filler",
              "job", "rehearsal"):
        assert cfg[k] == base[k], k  # the same cluster from the same seed
    assert "status" not in cfg
