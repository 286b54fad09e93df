"""The `counter_at_start` reader on a hand-made context: what had accrued
when the window opened, scaled; nothing where the program has no such
counter (the parent commit of the PR that brought the counters)."""
import json
import os

import pytest

import run

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def ctx(start: dict, end: dict) -> dict:
    return {"start": {"counters": start}, "end": {"counters": end}}


def test_reads_the_sum_at_the_window_s_opening_scaled():
    read = run.plugin("readers", "counter_at_start").read
    spec = {"keys": ["runtime.trace_lower_ms", "runtime.compile_ms"],
            "scale": 0.001}
    c = ctx({"runtime.trace_lower_ms": 18250.0, "runtime.compile_ms": 4750.0,
             "spec.launches": 3},
            {"runtime.trace_lower_ms": 99999.0, "runtime.compile_ms": 99999.0})
    # set-up's share only: what the window itself added is not read
    assert read(spec, c) == pytest.approx(23.0)
    assert read({"keys": ["runtime.compile_ms"]}, c) == pytest.approx(4750.0)


def test_a_program_without_the_counter_reads_nothing():
    read = run.plugin("readers", "counter_at_start").read
    assert read({"keys": ["runtime.compile_ms"], "scale": 0.001},
                ctx({"spec.launches": 3}, {})) is None
    # one of two keys there: the other counts as nothing accrued
    assert read({"keys": ["runtime.compile_ms", "runtime.trace_lower_ms"]},
                ctx({"runtime.compile_ms": 2.0}, {})) == pytest.approx(2.0)


@pytest.mark.parametrize("name", ["setup_trace_lower_s.setup",
                                  "setup_compile_load_s.setup"])
def test_the_setup_family_reads_seconds_through_it(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "counter_at_start" and spec["unit"] == "s"
    read = run.plugin("readers", spec["reader"]).read
    c = ctx({k: 20500.0 for k in spec["keys"]}, {})
    assert read(spec, c) == pytest.approx(20.5 * len(spec["keys"]))
