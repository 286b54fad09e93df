"""The benchmark's own arithmetic, on the CPU: percentiles and rates on a
hand-made sample that holds a stall, the trace reduction on hand-made rows
and on the recorded trace, the shape-based work count at a worked example."""
import json
import os

import pytest

import stats
import work
import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def stalled_sample():
    """100 requests/s for 10 s, each answered after 20 ms — but the system
    stalls from t=4 s to t=5 s: what is due then is answered at t=5 s."""
    due = [i / 100.0 for i in range(1000)]
    done = [max(d + 0.020, 5.0 + 0.020) if 4.0 <= d < 5.0 else d + 0.020
            for d in due]
    return due, done


def test_a_stall_moves_the_p95_and_the_rate_not_the_median():
    due, done = stalled_sample()
    lat = [1e3 * (b - a) for a, b in zip(due, done)]
    assert stats.percentile(lat, 50) == pytest.approx(20.0)
    # 10 % of the requests waited up to a second: the tail shows it
    assert stats.percentile(lat, 95) > 500.0
    # a median of one-second chunks of the rate would read 100/s; the rate
    # over all the work and all the time of a window that ends inside the
    # stall does not
    assert stats.rate(done, 0.0, 5.0) == pytest.approx(
        sum(1 for t in done if t < 5.0) / 5.0)
    assert stats.rate(done, 0.0, 5.0) < 81.0
    chunks = sorted(stats.rate(done, s, s + 1.0) for s in range(5))
    assert chunks[len(chunks) // 2] == pytest.approx(100.0, abs=3.0)


def test_percentile_is_nearest_rank_over_all_values():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50
    assert stats.percentile(v, 95) == 95
    assert stats.percentile(v, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([], 95) is None


def test_rate_weights_and_window_edges():
    assert stats.rate([0.0, 0.5, 1.0], 0.0, 1.0) == 2.0  # [t0, t1)
    assert stats.rate([0.1, 0.2], 0.0, 2.0, weights=[1000, 500]) == 750.0


ROWS = [
    # one device, three launches; ops overlap inside the second
    ["/device:TPU:0", "XLA Modules", "jit_place_table_chain(11)", 1000, 400],
    ["/device:TPU:0", "XLA Ops", "fusion.1", 1000, 300],
    ["/device:TPU:0", "XLA Ops", "sort.2", 1300, 100],
    ["/device:TPU:0", "XLA Modules", "jit__hot_delta_impl(12)", 2000, 100],
    ["/device:TPU:0", "XLA Ops", "fusion.1", 2000, 60],
    ["/device:TPU:0", "XLA Ops", "copy.3", 2040, 60],      # overlaps 20
    ["/device:TPU:0", "XLA Modules", "jit_place_table_chain(13)", 3000, 500],
    ["/device:TPU:0", "XLA Ops", "fusion.1", 3000, 500],
]


def test_trace_reduction_on_hand_made_rows():
    r = xplane.reduce(ROWS)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(2500e-9)
    assert r["busy_s"] == pytest.approx((400 + 100 + 500) * 1e-9)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.6)
    chain = r["programs"]["jit_place_table_chain"]
    assert chain["count"] == 2
    assert chain["device_s"] == pytest.approx(900e-9)
    assert xplane.placement_device_s(r) == (pytest.approx(900e-9), 2)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(860e-9)]
    gaps = dict(r["idle_gaps"])
    assert gaps["before_jit__hot_delta_impl"] == pytest.approx(600e-9)
    assert gaps["before_jit_place_table_chain"] == pytest.approx(900e-9)


def test_trace_reduction_without_a_device_plane_reads_nothing():
    assert xplane.reduce([])["window_s"] == 0.0


def test_one_launch_in_a_long_traced_span_is_mostly_idle():
    """The window is the traced span on the launcher's clock: a 4 s span
    that caught one 260 ms launch is 93.5 % idle, where the span of the
    device's own events would read it as all but busy."""
    rows = [["/device:TPU:0", "XLA Modules", "jit_place_table_chain(1)",
             5_000_000_000, 260_000_000],
            ["/device:TPU:0", "XLA Ops", "while.53", 5_000_000_000,
             260_000_000]]
    assert xplane.reduce(rows)["window_s"] == pytest.approx(0.26)
    r = xplane.reduce(rows, traced_s=4.0)
    assert r["window_s"] == 4.0 and r["busy_s"] == pytest.approx(0.26)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.935)
    # never a window shorter than what the device's events span
    assert xplane.reduce(rows, traced_s=0.1)["window_s"] == \
        pytest.approx(0.26)


def test_trace_reduction_on_the_recorded_trace():
    """`testdata/v5e-flood-rows.json`: rows of a trace taken on the chip.
    Busy time is recomputed here another way (a sweep over the sorted
    starts and ends), launches and kernel time are read off the rows."""
    path = os.path.join(os.path.dirname(HERE), "testdata",
                        "v5e-flood-rows.json")
    rows = json.load(open(path))["rows"]
    assert {r[0] for r in rows} == {"/device:TPU:0"}
    r = xplane.reduce(rows)
    ops = [x for x in rows if x[1] == xplane.OPS_LINE]
    mods = [x for x in rows if x[1] == xplane.MODULES_LINE]
    edges = sorted([(x[3], 1) for x in ops] + [(x[3] + x[4], -1)
                                               for x in ops])
    depth = busy = 0
    for (t, d), (t_next, _d) in zip(edges, edges[1:]):
        depth += d
        if depth > 0:
            busy += t_next - t
    assert r["busy_s"] == pytest.approx(busy / 1e9)
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert r["window_s"] == pytest.approx(
        (max(x[3] + x[4] for x in rows) - min(x[3] for x in rows)) / 1e9)
    # the scan's `while` nests its body's operations: a sum of durations
    # counts them twice, the union does not
    assert r["busy_s"] < sum(x[4] for x in ops) / 1e9
    dev_s, launches = xplane.placement_device_s(r)
    assert launches == len(mods) == 2
    assert {xplane.program_name(m[2]) for m in mods} == \
        {"jit_place_table_chain"}
    assert dev_s == pytest.approx(sum(m[4] for m in mods) / 1e9)
    assert 0.020 < dev_s / launches < 0.022    # 20.8 ms a launch
    assert r["idle_gaps"][0][0] == "before_jit_place_table_chain"
    assert r["device_ops"][0][0].startswith("while.")


def test_operation_names_are_cut_at_the_equals_sign():
    assert xplane.op_name("%while.53 = (s32[]{:T(128)}, f32[16384,8]) "
                          "while(%tuple.1)") == "while.53"
    assert xplane.op_name("fusion.1") == "fusion.1"
    assert xplane.program_name("jit_place_table_chain(155644)") == \
        "jit_place_table_chain"


def test_work_count_worked_example():
    # 32 programs of 8 allocations over the 16,384-row view, 3 columns
    w = work.placement_work(16384, 32, 8, 3)
    per_node = 2 * 4 * 4 + 1 + 4 + 3 * 4          # 49 bytes
    assert w["bytes"] == 32 * 16384 * per_node     # 25,690,112
    assert sum(work.OPS_PER_CANDIDATE.values()) == 33
    assert w["ops"] == 32 * 8 * 16384 * 33         # 138,412,032
    least = work.least_seconds(w, "TPU v5 lite")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(25690112 / 819e9)
    with pytest.raises(KeyError):
        work.peaks("TPU v9")
