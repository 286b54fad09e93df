"""ISSUE 26 — what the host was doing inside `schedule` and between a
dispatch's launch and its first read.

One pipelined feed (16 single-alloc jobs, fused batches of 8: the first
dispatch is a normal one, the second a certified speculative launch) runs
through a `Server` under a `jax.profiler` trace on the CPU; every test
below reads that one run. Gates:

- per eval, prepare + park + result_wait + plan_build + plan_apply +
  finish tile `schedule` (within 2 %), for both kinds of dispatch, and
  `finish` stays out of the scheduler threads' CPU and wall counters;
- per plan, the wait for the commit-point mutex and `apply_ms` make up
  the eval's `plan_apply`, and `apply_ms` holds the verify and the commit;
- every offer of a speculative launch is counted under one outcome;
- per dispatch, launch + release + spec_hold + wake + fetch_block sum to
  launch → first read (`kernel_ms`);
- a record whose predecessor was read without blocking is `bounds_only`,
  stays out of the overlap/bubble histograms and is counted;
- `runtime.compiles` grows on a first launch and not on the second;
- the profile's host plane holds every `nomad/*` name, and `host_span`
  is a null context without JAX.
"""
import contextlib
import gc
import glob
import random
import sys
import threading
import time

import pytest

from nomad_tpu.lib import trace as trace_mod
from nomad_tpu.lib.metrics import MetricsRegistry, default_registry
from nomad_tpu.lib.transfer import DispatchTimeline

EVAL_PARTS = ("prepare", "park", "result_wait", "plan_build", "plan_apply",
              "finish")
READ_PARTS = ("launch_ms", "release_ms", "spec_hold_ms", "wake_ms",
              "fetch_block_ms")
HOST_SPANS = ("drain_hold", "partition", "snapshot", "footprint", "prepare",
              "park", "pack", "view", "launch", "release", "certify",
              "result_wait", "plan_build", "plan_apply", "gc", "finish",
              "spec_offer", "plan_verify", "plan_commit")
N_JOBS, BATCH = 16, 8


@pytest.fixture(scope="module")
def feed(tmp_path_factory):
    import jax

    from nomad_tpu.lib.backend import GcWatch
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.synth import synth_node, synth_service_job

    mp = pytest.MonkeyPatch()
    mp.delenv("NOMAD_TPU_EVAL_BATCH", raising=False)
    mp.setenv("NOMAD_TPU_DRAIN_WINDOW_MS", "50")
    mp.setenv("NOMAD_TPU_SPEC_PARK_MS", "2000")
    mp.setenv("NOMAD_TPU_SPEC_ROLLBACK_MAX", "1.0")
    mp.setenv("NOMAD_TPU_SPECULATE", "1")
    rng = random.Random(29)
    s = Server(ServerConfig(num_schedulers=1, heartbeat_ttl=3600.0,
                            eval_batch=BATCH))
    for i in range(48):
        s.state.upsert_node(synth_node(rng, i))
    s.broker.set_enabled(False)
    evs = []
    for i in range(N_JOBS):
        j = synth_service_job(rng, count=1, datacenter=f"dc{1 + i % 3}")
        j.task_groups[0].tasks[0].resources.cpu = 50
        j.task_groups[0].tasks[0].resources.memory_mb = 64
        evs.append(s.job_register(j))
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1   # what perfbench/launcher.py traces at
    watch = GcWatch(MetricsRegistry())
    watch.install()
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    s.start()  # enables the broker and enqueues what was registered
    try:
        for ev in evs:
            got = s.wait_for_eval(
                ev.id, statuses=("complete", "failed", "blocked",
                                 "cancelled"), timeout=120.0)
            assert got is not None and got.status == "complete", got
        # an eval is answered before its thread records what it gathered
        # and before the worker adds its batch's counts: wait for both
        deadline = time.monotonic() + 30.0
        while s.metrics.counters().get("worker.0.batch.evals", 0) < N_JOBS:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        gc.collect()
        out = {"traces": [s.tracer.get(ev.id) for ev in evs],
               "records": s.timeline.records_after(0)[1],
               "snap": s.metrics.snapshot()}
    finally:
        jax.profiler.stop_trace()
        watch.remove()
        s.shutdown()
        mp.undo()
    (pb,) = glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True)
    names = {}
    for plane in jax.profiler.ProfileData.from_file(pb).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("nomad/"):
                    names.setdefault(ev.name, set()).add(plane.name)
    out["profile"] = names
    return out


def _by_kind(feed):
    """seq of the dispatch each eval rode → its kind."""
    recs = feed["records"]
    assert [r["speculative"] for r in recs[:2]] == [False, True], recs
    assert recs[1]["spec_outcome"] == "certified"
    return {"normal": recs[0], "speculative": recs[1]}


def _spans(trace):
    out = {}
    for sp in trace["spans"]:
        out.setdefault(sp["phase"], []).append(
            (sp["start_s"], sp["start_s"] + sp["duration_ms"] / 1e3))
    return out


@pytest.mark.parametrize("kind", ["normal", "speculative"])
def test_the_six_parts_tile_schedule(feed, kind):
    # jobs are drained in order: the first 8 ride the normal dispatch
    traces = feed["traces"][:BATCH] if kind == "normal" \
        else feed["traces"][BATCH:]
    assert len(traces) == BATCH
    for tr in traces:
        by = _spans(tr)
        (s0, s1), = by["schedule"]
        parts = sorted(iv for p in EVAL_PARTS for iv in by.get(p, []))
        assert {p for p in EVAL_PARTS if p in by} == set(EVAL_PARTS), by
        # inside the schedule span, in order, never overlapping
        assert parts[0][0] >= s0 - 2e-6 and parts[-1][1] <= s1 + 2e-6
        for (_a0, a1), (b0, _b1) in zip(parts, parts[1:]):
            assert b0 >= a1 - 2e-6, (tr["trace_id"], parts)
        covered = sum(b - a for a, b in parts)
        # each part starts where the one before it ended: nothing is
        # left over but the rounding of the exported offsets
        assert abs((s1 - s0) - covered) <= 0.02 * (s1 - s0), \
            (covered, s1 - s0)
        # the first part starts where process() does, the last ends there
        assert parts[0][0] - s0 < 1e-5 and s1 - parts[-1][1] < 1e-5
        assert by["prepare"][0] == parts[0] and by["finish"][-1] == parts[-1]


@pytest.mark.parametrize("kind", ["normal", "speculative"])
def test_the_five_intervals_sum_to_launch_to_first_read(feed, kind):
    rec = _by_kind(feed)[kind]
    parts = [rec[k] for k in READ_PARTS]
    if kind == "normal":
        assert rec["spec_hold_ms"] is None
        parts = [p for p in parts if p is not None]
        assert len(parts) == 4
    else:
        # held from its stash until the predecessor's plans committed
        assert rec["spec_hold_ms"] > 0.0
    assert all(p >= 0.0 for p in parts)
    assert sum(parts) == pytest.approx(rec["kernel_ms"], abs=0.01)
    assert rec["was_ready"] in (True, False)


@pytest.mark.parametrize("phase", ["prepare", "park", "result_wait",
                                   "plan_build", "finish"])
def test_every_eval_of_the_feed_has_the_phase(feed, phase):
    h = feed["snap"]["histograms"]
    assert h[f"eval.phase.{phase}_ms"]["count"] == N_JOBS
    assert h["eval.phase.schedule_ms"]["count"] == N_JOBS
    # a part never outlasts the whole
    assert h[f"eval.phase.{phase}_ms"]["sum"] \
        <= h["eval.phase.schedule_ms"]["sum"]


@pytest.mark.parametrize("part", READ_PARTS)
def test_every_dispatch_samples_the_interval(feed, part):
    h = feed["snap"]["histograms"]
    recs = feed["records"]
    want = sum(1 for r in recs if r[part] is not None)
    assert h[f"pipeline.{part}"]["count"] == want
    assert want == (sum(1 for r in recs if r["speculative"])
                    if part == "spec_hold_ms" else len(recs))
    assert h[f"pipeline.{part}"]["sum"] == pytest.approx(
        sum(r[part] for r in recs if r[part] is not None), abs=0.01)


def test_cpu_and_wall_are_counted_over_the_unblocked_phases(feed):
    c = feed["snap"]["counters"]
    h = feed["snap"]["histograms"]
    wall = h["eval.phase.prepare_ms"]["sum"] \
        + h["eval.phase.plan_build_ms"]["sum"]
    assert c["sched.phase_wall_ms"] == pytest.approx(wall, rel=1e-6)
    assert 0.0 < c["sched.phase_cpu_ms"]
    # thread CPU time cannot exceed wall time by more than clock grain
    assert c["sched.phase_cpu_ms"] <= c["sched.phase_wall_ms"] + 5.0


def test_a_plan_waits_for_the_mutex_then_verifies_and_commits(feed):
    h = feed["snap"]["histograms"]
    n = h["eval.phase.plan_apply_ms"]["count"]
    assert n == N_JOBS
    for k in ("wait_ms", "apply_ms", "verify_ms", "commit_ms"):
        assert h[f"plan_apply.{k}"]["count"] == n, k
    # submit → mutex, then the apply: the eval's plan_apply, less the
    # hand-back to the submitting thread
    assert h["plan_apply.wait_ms"]["sum"] + h["plan_apply.apply_ms"]["sum"] \
        == pytest.approx(h["eval.phase.plan_apply_ms"]["sum"], rel=0.05)
    assert h["plan_apply.apply_ms"]["sum"] >= \
        h["plan_apply.verify_ms"]["sum"] + h["plan_apply.commit_ms"]["sum"]


def test_every_offer_is_counted_under_one_outcome(feed):
    from nomad_tpu.server.select_batch import SPEC_OFFER_OUTCOMES

    c = feed["snap"]["counters"]
    h = feed["snap"]["histograms"]
    got = {o: c[f"spec.offer.{o}"] for o in SPEC_OFFER_OUTCOMES}
    assert sum(got.values()) == c["spec.offers"] == h["spec.offer_ms"]["count"]
    # the first dispatch offered the second batch a launch it took; the
    # certified one offered again, to no successor
    assert got["launched"] == 1 and got["no_successor"] >= 1, got
    assert c["spec.launches"] == 1


@pytest.mark.parametrize("name", HOST_SPANS)
def test_the_profile_holds_the_span_in_a_host_plane(feed, name):
    planes = feed["profile"].get("nomad/" + name)
    assert planes, sorted(feed["profile"])
    assert all(p.startswith("/host:") for p in planes), planes


def test_host_span_annotates_only_while_a_profile_is_taken(tmp_path):
    import jax

    assert isinstance(trace_mod.host_span("park"), contextlib.nullcontext)
    jax.profiler.start_trace(str(tmp_path))
    try:
        span = trace_mod.host_span("park")
        assert isinstance(span, jax.profiler.TraceAnnotation)
        with span:
            pass
    finally:
        jax.profiler.stop_trace()
    assert isinstance(trace_mod.host_span("park"), contextlib.nullcontext)


def test_host_span_is_a_null_context_without_jax(monkeypatch):
    monkeypatch.setattr(trace_mod, "_profiler", None)
    monkeypatch.setitem(sys.modules, "jax._src", None)
    span = trace_mod.host_span("prepare")
    assert isinstance(span, contextlib.nullcontext)
    with span:
        pass
    assert trace_mod._profiler is False
    # and the tracer's host phases still record their spans
    reg = MetricsRegistry()
    tr = trace_mod.EvalTracer(reg)
    tr.begin("e1")
    tr.host_begin("prepare")   # a thread that is not armed: a no-op
    tr.host_flush("e1")
    assert tr.get("e1")["spans"] == []
    tr.host_arm()
    tr.host_begin("prepare")
    tr.host_add("park", 1.0, 1.5)
    tr.host_begin("plan_build")  # closes prepare
    tr.host_end()
    tr.host_end()  # nothing open: a no-op
    tr.host_flush("e1")
    tr.host_flush("e1")  # disarmed: a no-op
    assert sorted(s["phase"] for s in tr.get("e1")["spans"]) == \
        ["park", "plan_build", "prepare"]
    assert reg.histogram("eval.phase.prepare_ms").count == 1
    assert reg.histogram("eval.phase.park_ms").sum == pytest.approx(500.0)
    assert reg.counters()["sched.phase_wall_ms"] >= 0.0
    tr.host_arm()
    tr.host_begin("prepare")
    tr.host_flush("never-enqueued")  # unknown id: histograms only
    assert reg.histogram("eval.phase.prepare_ms").count == 2


# ---- the timeline's own arithmetic, on synthetic instants -----------------

def _commit(tl, t, **kw):
    return tl.commit(programs=2, batched=True, pack=(t, t + 0.001),
                     view=(t + 0.001, t + 0.002), kernel_start=t + 0.002,
                     transfer_bytes=0, transfer_count=0, **kw)


@pytest.mark.parametrize("was_ready,bounds", [(True, True), (False, False),
                                              (None, False)])
def test_a_read_that_did_not_block_makes_the_successor_bounds_only(
        was_ready, bounds):
    reg = MetricsRegistry()
    tl = DispatchTimeline(reg)
    s1 = _commit(tl, 0.0)
    tl.kernel_end(s1, 0.150, entered=0.1495, was_ready=was_ready)
    s2 = _commit(tl, 0.100)
    tl.kernel_end(s2, 0.160, entered=0.1595, was_ready=False)
    _i, (r1, r2) = tl.records_after(0)
    assert r1["was_ready"] is was_ready and r1["bounds_only"] is False
    assert r2["bounds_only"] is bounds
    # still computed (as bounds), as before
    assert r2["overlap_ms"] == pytest.approx(2.0)
    assert r2["bubble_ms"] == 0.0
    hists = reg.snapshot().get("histograms", {})
    counted = reg.counters().get("pipeline.kernel_end_unknown", 0)
    summ = tl.summary()
    if bounds:
        assert "pipeline.overlap_ms" not in hists
        assert "pipeline.bubble_ms" not in hists
        assert counted == 1 and summ["kernel_end_unknown"] == 1
        assert summ["overlap_ms_total"] == 0.0 and summ["overlap_pct"] == 0.0
    else:
        assert hists["pipeline.overlap_ms"]["count"] == 1
        assert hists["pipeline.bubble_ms"]["count"] == 1
        assert counted == 0 and summ["kernel_end_unknown"] == 0
        assert summ["overlap_ms_total"] == pytest.approx(2.0)


def test_a_rolled_back_speculation_read_by_its_certifier_still_sums():
    tl = DispatchTimeline(MetricsRegistry())
    s1 = _commit(tl, 0.0, speculative=True, launch_end=0.003)
    tl.released(s1, 0.004, held=True)
    # nobody is released: the certifier resolves the holder itself
    tl.kernel_end(s1, 0.060, entered=0.050, was_ready=True)
    _i, (r,) = tl.records_after(0)
    assert r["launch_ms"] == pytest.approx(1.0)
    assert r["release_ms"] == pytest.approx(1.0)
    assert r["spec_hold_ms"] == pytest.approx(46.0)
    assert r["wake_ms"] == 0.0
    assert r["fetch_block_ms"] == pytest.approx(10.0)
    assert sum(r[k] for k in READ_PARTS) == pytest.approx(r["kernel_ms"])


# ---- runtime counters ------------------------------------------------------

def test_runtime_compiles_grows_on_a_first_launch_only():
    import jax
    import jax.numpy as jnp

    from nomad_tpu.lib import backend

    backend.count_compiles()
    backend.count_compiles()  # one listener, however often it is asked
    reg = default_registry()

    @jax.jit
    def f(x):
        return (x * 3.0 + 1.0).sum()

    x = jnp.arange(37.0)  # a shape nothing else in the suite compiles
    c0 = reg.counters(prefix="runtime.")
    f(x).block_until_ready()
    c1 = reg.counters(prefix="runtime.")
    f(x).block_until_ready()
    c2 = reg.counters(prefix="runtime.")
    assert c1["compiles"] == c0.get("compiles", 0) + 1
    assert c1["compile_ms"] > c0.get("compile_ms", 0.0)
    assert c1["trace_lower_ms"] > c0.get("trace_lower_ms", 0.0)
    assert c2 == c1


def test_gc_watch_records_pauses_and_never_waits_for_a_lock():
    from nomad_tpu.lib.backend import GcWatch

    reg = MetricsRegistry()
    watch = GcWatch(reg)
    watch.install()
    watch.install()
    try:
        assert gc.callbacks.count(watch._on_gc) == 1
        gc.collect()
        h = reg.histogram("runtime.gc_pause_ms")
        full = reg.counter("runtime.gc_full")
        n = h.count
        assert n >= 1 and full.value >= 1 and h.sum >= 0.0
        # a collection that starts on a thread holding the instruments'
        # locks (a reader sorting the window allocates) must not wait:
        # the sample is kept and lands with the next collection
        done = []
        with h._lock, full._lock:
            t = threading.Thread(target=lambda: done.append(gc.collect()))
            t.start()
            t.join(10.0)
            assert not t.is_alive() and done
        assert h.count == n
        gc.collect()
        assert h.count == n + 2 and full.value >= 3
    finally:
        watch.remove()
    assert watch._on_gc not in gc.callbacks
