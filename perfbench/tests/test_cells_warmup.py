"""`traffic/cells96-drained.json`'s warm-up against the program's own
layout rule: every program shape a drain of evals that each name one of 64
partitions can take has been met, and so compiled, before the window opens.

A drain of such evals partitions into one conflict group a partition
named; `SelectCoordinator._wave_lanes` packs the groups, longest first,
into at most eight lanes and `select_batch._table_layout` buckets lanes and
lane length to powers of two: (1, 2, 4, 8) lanes x (2 .. 32) programs, 20
shapes where `pinned-10k.flood` meets 15. A dispatch whose shape is new
compiles inside the window (PERF.md section 7 third; ROADMAP B7).

How a burst leaves the broker is `test_pinned_warmup.py`'s (read on the
chip in PR 32): a short burst is one drain, or its first job alone and the
rest in one drain; a burst of 65 holds at least one drain 32 wide, so a
shape with a lane longer than 8 comes from 65 jobs of a repeating pattern
of which any 32 in a row (and any 27) hold the pattern's shares.
"""
import collections
import json
import os

import pytest

from test_pinned_warmup import EVAL_BATCH, kinds_of, shape_of

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAFFIC = json.load(open(os.path.join(BENCH, "traffic",
                                      "cells96-drained.json")))
CFG = json.load(open(os.path.join(BENCH, "configs",
                                  "computed-class-5k.json")))
LENGTHS = (2, 4, 8, 16, 32)
SHAPES = {(lanes, n) for lanes in (1, 2, 4, 8) for n in LENGTHS}


def partitions(n, largest=None):
    """Every way to cut `n` evals into groups: the group sizes, falling."""
    largest = n if largest is None else largest
    if n == 0:
        yield ()
        return
    for first in range(min(n, largest), 0, -1):
        for rest in partitions(n - first, first):
            yield (first,) + rest


def test_a_drain_of_partitioned_evals_takes_one_of_twenty_shapes():
    met = set()
    for n in range(2, EVAL_BATCH + 1):  # one eval alone rides no table
        for sizes in partitions(n):
            if len(sizes) <= 64:
                met.add(shape_of([g for g, k in enumerate(sizes)
                                  for _ in range(k)]))
    assert met == SHAPES


@pytest.mark.parametrize("ahead", [0, 1])
def test_the_bursts_reach_every_shape_twice(ahead):
    """`ahead` jobs of a burst are scheduled before the rest drain, 32 at
    a time."""
    met = collections.Counter()
    for b in TRAFFIC["warmup"]["bursts"]:
        rest = kinds_of(b)[ahead:]
        for at in range(0, len(rest), EVAL_BATCH):
            if len(rest) - at > 1:
                met[shape_of(rest[at:at + EVAL_BATCH])] += 1
    assert set(met) == SHAPES, sorted(SHAPES - set(met))
    # the second pass is the speculative launch's
    assert all(n >= 2 for n in met.values()), met


def test_a_loaded_burst_keeps_its_shape_wherever_the_drains_fall():
    """A burst too long for one drain (lane length over 8) repeats a
    pattern, so that ANY 32 jobs in a row, and any 27, give its shape."""
    long_ones = [b for b in TRAFFIC["warmup"]["bursts"] if b["n"] > 33]
    assert len(long_ones) == 14
    shapes = collections.Counter()
    for b in long_ones:
        kinds = kinds_of(b)
        want = shape_of(kinds[1:1 + EVAL_BATCH])
        assert want[1] >= 16, b
        shapes[want] += 1
        for width in (27, EVAL_BATCH):
            assert {shape_of(kinds[at:at + width])
                    for at in range(1, len(kinds) - width)} == {want}, b
    assert shapes == {(lanes, n): 2 for lanes in (2, 4, 8) for n in (16, 32)
                      } | {(1, 32): 2}


def test_a_short_burst_is_one_shape_with_or_without_its_first_job():
    for b in TRAFFIC["warmup"]["bursts"]:
        if 4 <= b["n"] <= 33:
            kinds = kinds_of(b)
            assert shape_of(kinds) == shape_of(kinds[1:]), b


def test_the_bursts_name_kinds_of_the_mix_and_crowd_no_partition():
    per = collections.Counter()
    for b in TRAFFIC["warmup"]["bursts"]:
        assert set(b["kinds"]) <= set(CFG["mix"]), b
        per.update(kinds_of(b))
    assert sum(per.values()) == 1226
    # the bursts are spread over the partitions: none takes more than a
    # tenth of them (a partition holds 1/64 of the cluster)
    assert max(per.values()) <= 130, per.most_common(3)


def test_the_cell_is_the_issues():
    t = TRAFFIC
    assert (t["loop"], t["window"], t["outstanding"], t["count"],
            t["senders"], t["timeout_s"], t["trace_span_s"]) == (
                "closed", "drained", 96, 8, 8, 60, 6)
    pinned = json.load(open(os.path.join(BENCH, "traffic",
                                         "pinned96-drained.json")))
    for k in ("loop", "outstanding", "window", "count", "senders",
              "timeout_s", "trace_span_s", "rehearsal"):
        assert t[k] == pinned[k], k
    assert (t["warmup"]["quiet_s"], t["warmup"]["max_s"]) == (5, 900)
