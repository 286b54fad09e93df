"""Cross-process distributed tracing + scheduling SLOs (ISSUE 17).

Four gates on the ninth telemetry layer (`lib/tracectx.py`):

- **propagation**: a job submitted through a FOLLOWER's HTTP edge with
  an inbound `traceparent` yields ONE trace whose spans parent into a
  single tree across the forwarding hop — http.submit on the follower,
  rpc.forward at the transport, eval/phase/plan.apply on the leader —
  with zero orphans;
- **replica determinism**: trace identity rides the raft entry like
  `now=` (leader-minted, NLR01), so two replicas replaying one log
  under skewed clocks/RNGs fingerprint identical, and the fingerprint
  actually COVERS the trace fields (a divergent span id is caught);
- **ring/long-poll contract**: `SpanStore` honors the events.py
  contract verbatim — strictly monotonic seq, wrap drops only the
  oldest, no duplicate past a wrapped cursor, long-poll wakes on
  record — plus its closed span-name vocabulary and the NLS01
  secret-shaped-detail belt;
- **SLO math**: per-band attainment / error-budget / multiwindow burn
  rates are pinned exactly against an injected clock, `slo.burn` is
  edge-triggered with re-arm (fires under an injected regression,
  stays silent at baseline).
"""
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.analysis.vocab import SPAN_NAMES
from nomad_tpu.api import NomadClient
from nomad_tpu.lib.flight import FlightRecorder
from nomad_tpu.lib.tracectx import (SloTracker, SpanStore, TraceContext,
                                    default_spans, format_traceparent,
                                    mint, parse_traceparent, slo_band)


def _wait(cond, timeout=20.0, every=0.05):
    dl = time.time() + timeout
    while time.time() < dl:
        if cond():
            return True
        time.sleep(every)
    return cond()


# ---- context + traceparent -------------------------------------------------


class TestTraceContext:
    def test_traceparent_round_trip(self):
        ctx = mint()
        back = parse_traceparent(format_traceparent(ctx))
        assert back is not None
        assert back.trace_id == ctx.trace_id
        assert back.span_id == ctx.span_id

    @pytest.mark.parametrize("bad", [
        None, 42, "", "garbage", "00-abc-def-01",
        "00-" + "a" * 31 + "-" + "b" * 16 + "-01",   # short trace id
        "00-" + "a" * 32 + "-" + "b" * 15 + "-01",   # short span id
        "00-" + "g" * 32 + "-" + "b" * 16 + "-01",   # non-hex
        "ff-" + "a" * 32 + "-" + "b" * 16 + "-01",   # forbidden version
        "00-" + "0" * 32 + "-" + "b" * 16 + "-01",   # all-zero trace
        "00-" + "a" * 32 + "-" + "0" * 16 + "-01",   # all-zero span
    ])
    def test_malformed_traceparent_is_none_never_raises(self, bad):
        assert parse_traceparent(bad) is None

    def test_mint_with_parent_continues_the_trace(self):
        parent = mint()
        child = mint(parent)
        assert child.trace_id == parent.trace_id
        assert child.parent_span_id == parent.span_id
        assert child.span_id != parent.span_id

    def test_child_chain_keeps_one_trace_id(self):
        root = mint()
        hop = root.child()
        leaf = hop.child()
        assert root.trace_id == hop.trace_id == leaf.trace_id
        assert leaf.parent_span_id == hop.span_id
        assert hop.parent_span_id == root.span_id

    def test_wire_round_trip_and_malformed_tolerance(self):
        ctx = mint().child()
        back = TraceContext.from_wire(ctx.to_wire())
        assert back == ctx
        for bad in (None, [], "x", {}, {"t": "a"}, {"t": "", "s": ""},
                    {"t": 1, "s": 2}):
            assert TraceContext.from_wire(bad) is None


# ---- SpanStore: the events.py ring/long-poll contract ----------------------


def _span(store, i, trace="t" * 32):
    return store.record("http.submit", trace_id=trace,
                        span_id=f"{i:016x}", start_unix=float(i),
                        end_unix=float(i) + 0.001)


class TestSpanStoreRing:
    def test_wrap_keeps_newest_and_stays_monotonic(self):
        st = SpanStore(capacity=8)
        for i in range(20):
            _span(st, i)
        idx, out = st.spans_after(0)
        assert len(out) == 8
        assert [s["span_id"] for s in out] == [f"{i:016x}"
                                               for i in range(12, 20)]
        assert [s["seq"] for s in out] == list(range(13, 21))
        assert idx == 20 and st.last_index() == 20

    def test_cursor_past_wrap_sees_no_duplicates(self):
        st = SpanStore(capacity=8)
        for i in range(10):
            _span(st, i)
        _, first = st.spans_after(0)
        cursor = max(s["seq"] for s in first)
        for i in range(10, 26):
            _span(st, i)
        _, second = st.spans_after(cursor)
        seen = [s["seq"] for s in first] + [s["seq"] for s in second]
        assert len(seen) == len(set(seen)), "duplicate span seq"
        assert seen == sorted(seen), "spans out of seq order"

    def test_trace_filter_across_wrap(self):
        st = SpanStore(capacity=6)
        for i in range(12):
            _span(st, i, trace=("a" if i % 2 else "b") * 32)
        _, only = st.spans_after(0, trace_id="a" * 32)
        assert only and all(s["trace_id"] == "a" * 32 for s in only)
        assert [s["seq"] for s in only] == sorted(s["seq"] for s in only)

    def test_long_poll_wakes_on_record(self):
        st = SpanStore()
        _span(st, 0)
        idx = st.last_index()

        def later():
            time.sleep(0.15)
            _span(st, 1)

        threading.Thread(target=later, daemon=True).start()
        t0 = time.time()
        _, out = st.spans_after(idx, timeout=5.0)
        dt = time.time() - t0
        assert out and out[0]["span_id"] == f"{1:016x}"
        assert dt < 2.0, f"long-poll slept {dt:.2f}s past the record"

    def test_long_poll_times_out_empty(self):
        st = SpanStore()
        t0 = time.time()
        idx, out = st.spans_after(0, timeout=0.2)
        assert out == [] and time.time() - t0 >= 0.15

    def test_unknown_span_name_rejected(self):
        st = SpanStore()
        with pytest.raises(ValueError, match="unknown span name"):
            st.record("made.up", trace_id="t" * 32, span_id="s" * 16)

    def test_secret_shaped_detail_rejected(self):
        """NLS01 runtime belt: traces are operator-readable and cross
        process boundaries — a secret-shaped detail key is a bug."""
        st = SpanStore()
        with pytest.raises(ValueError, match="secret"):
            st.record("http.submit", trace_id="t" * 32, span_id="s" * 16,
                      detail={"node_secret_id": "hunter2"})

    def test_counts_survive_eviction(self):
        st = SpanStore(capacity=4)
        for i in range(10):
            _span(st, i)
        assert st.counts()["http.submit"] == 10
        assert len(st.snapshot()) == 4


# ---- SLO math, pinned against an injected clock ----------------------------


_SLO_ENV = {
    "NOMAD_TPU_SLO_OBJECTIVE": "0.9",
    "NOMAD_TPU_SLO_NORMAL_MS": "100",
    "NOMAD_TPU_SLO_HIGH_MS": "50",
    "NOMAD_TPU_SLO_LOW_MS": "1000",
    "NOMAD_TPU_SLO_FAST_S": "10",
    "NOMAD_TPU_SLO_SLOW_S": "100",
    "NOMAD_TPU_SLO_FAST_BURN": "5.0",
    "NOMAD_TPU_SLO_SLOW_BURN": "2.0",
}


class TestSloMath:
    def test_priority_band_mapping_pinned(self):
        assert slo_band(100) == slo_band(70) == "high"
        assert slo_band(69) == slo_band(50) == slo_band(30) == "normal"
        assert slo_band(29) == slo_band(0) == "low"

    def test_env_knobs_and_band_targets(self):
        t = SloTracker(env=_SLO_ENV)
        assert t.objective == pytest.approx(0.9)
        assert t.target_ms == {"high": 50.0, "normal": 100.0,
                               "low": 1000.0}
        # each band judges against ITS OWN target
        assert t.observe(80, 60.0, now=0.0)["ok"] is False   # high>50
        assert t.observe(50, 60.0, now=0.0)["ok"] is True    # normal<=100

    def test_attainment_and_budget_exact(self):
        t = SloTracker(env=_SLO_ENV)
        out = t.observe(50, 50.0, now=0.0)
        assert out["attainment"] == pytest.approx(1.0)
        assert out["budget_remaining"] == pytest.approx(1.0)
        out = t.observe(50, 200.0, now=1.0)  # miss
        # lifetime attainment 1/2; budget = 1 - (1-0.5)/(1-0.9) = -4:
        # DELIBERATELY unclamped — the gauge shows how overspent
        assert out["attainment"] == pytest.approx(0.5)
        assert out["budget_remaining"] == pytest.approx(-4.0)
        # burn rate = fail_fraction / (1 - objective) = 0.5 / 0.1
        assert out["burn"]["fast"] == pytest.approx(5.0)
        assert out["burn"]["slow"] == pytest.approx(5.0)

    def test_burn_edge_triggered_with_rearm(self):
        fl = FlightRecorder()
        t = SloTracker(flight=fl, source="s1", env=_SLO_ENV)
        idx0 = fl.last_index()
        t.observe(50, 50.0, now=0.0)
        out = t.observe(50, 200.0, now=1.0)
        # rate 5.0 crosses BOTH thresholds (fast 5.0, slow 2.0): one
        # slo.burn per (band, window) on the crossing edge
        assert {b["window"] for b in out["fired"]} == {"fast", "slow"}
        out = t.observe(50, 200.0, now=2.0)
        assert out["fired"] == [], "alert must be edge-triggered"
        # recovery: misses age OUT of the fast window and the rate
        # falls back under threshold → the alert re-arms
        for i in range(3, 14):
            out = t.observe(50, 50.0, now=float(i))
        assert out["burn"]["fast"] < 5.0
        # fresh regression after re-arm fires the fast window again
        # (old observations are outside the 10s fast window by now=40)
        out = t.observe(50, 200.0, now=40.0)
        assert any(b["window"] == "fast" for b in out["fired"])
        # every firing landed as a slo.burn flight event keyed by band
        _, evs = fl.records_after(idx0)
        burns = [e for e in evs if e["type"] == "slo.burn"]
        assert len(burns) >= 3 and all(e["key"] == "normal"
                                       for e in burns)
        assert all(e["source"] == "s1" for e in burns)
        assert {"window", "burn_rate", "threshold",
                "observations", "objective"} <= set(burns[0]["detail"])

    def test_silent_at_baseline(self):
        """All-ok traffic (and the occasional sub-threshold miss under
        the default 0.99 objective’s wide windows) records NOTHING."""
        fl = FlightRecorder()
        t = SloTracker(flight=fl, env=_SLO_ENV)
        idx0 = fl.last_index()
        for i in range(100):
            out = t.observe(50, 50.0, now=float(i))
            assert out["fired"] == []
        assert out["attainment"] == pytest.approx(1.0)
        assert out["budget_remaining"] == pytest.approx(1.0)
        _, evs = fl.records_after(idx0)
        assert [e for e in evs if e["type"] == "slo.burn"] == []

    def test_registry_series_update(self):
        from nomad_tpu.lib.metrics import MetricsRegistry

        reg = MetricsRegistry()
        t = SloTracker(registry=reg, env=_SLO_ENV)
        snap = reg.snapshot()
        # pre-created so exposition pins hold before any placement:
        # attainment/budget start FULL — no data is not a violation
        for b in ("high", "normal", "low"):
            assert snap["gauges"]["slo.attainment." + b] == 1.0
            assert snap["gauges"]["slo.budget_remaining." + b] == 1.0
        t.observe(50, 50.0, now=0.0)
        t.observe(50, 200.0, now=1.0)
        snap = reg.snapshot()
        assert snap["counters"]["slo.observations"] == 2
        assert snap["gauges"]["slo.attainment.normal"] == pytest.approx(0.5)
        assert snap["histograms"]["slo.latency.normal_ms"]["count"] == 2


# ---- replica determinism: trace identity rides the raft entry --------------


class TestTraceReplicaDeterminism:
    """The NLR01 shape for trace fields: minted leader-side, stamped on
    the entry like `now=`, so FSM apply stays a pure function of the
    log. Mirrors test_control_plane.TestReplicaDeterminism."""

    def _log(self, alloc_span="aaaabbbbccccdddd"):
        from nomad_tpu.structs.codec import to_wire

        node = mock.node()
        job = mock.job()
        ev = mock.eval_(job_id=job.id)
        ev.trace_id = "ab" * 16
        ev.trace_span_id = "cd" * 8
        ev.trace_parent_span_id = "ef" * 8
        alloc = mock.alloc(job=job, node_id=node.id)
        alloc.eval_id = ev.id
        alloc.trace_id = ev.trace_id
        alloc.trace_span_id = alloc_span
        entries = [("upsert_node", [node]), ("upsert_job", [job]),
                   ("upsert_eval", [ev]), ("upsert_alloc", [alloc])]
        return [{"op": op, "args": [to_wire(a) for a in args]}
                for op, args in entries]

    def _replay(self, log, clock, seed):
        import random as _random
        from unittest import mock as um

        from nomad_tpu.server.fsm import FSM, state_fingerprint
        from nomad_tpu.server.state import StateStore

        state = StateStore()
        fsm = FSM(state)
        _random.seed(seed)
        with um.patch("time.time", lambda: clock):
            for entry in log:
                fsm.apply(entry)
        return state, state_fingerprint(state)

    def test_two_replicas_fingerprint_identical(self):
        log = self._log()
        st1, fp1 = self._replay(log, 1.0e9, 1)
        st2, fp2 = self._replay(log, 2.0e9, 2)
        assert fp1 == fp2
        # and the trace identity actually LANDED in the state
        evs = st1.evals()
        assert evs and evs[0].trace_id == "ab" * 16
        assert evs[0].trace_span_id == "cd" * 8
        allocs = list(st1._allocs.values())
        assert allocs and allocs[0].trace_id == "ab" * 16

    def test_fingerprint_covers_trace_identity(self):
        """A replica-local span id (the pre-fix shape: minting inside
        apply) MUST diverge the fingerprint — the gate that fails if
        someone moves the mint off the raft entry."""
        _, fp1 = self._replay(self._log(alloc_span="1" * 16), 1.0e9, 1)
        _, fp2 = self._replay(self._log(alloc_span="2" * 16), 1.0e9, 1)
        assert fp1 != fp2, \
            "fingerprint gate is blind to alloc trace identity"


# ---- 3-server propagation: one tree across the forwarding hop --------------


@pytest.fixture()
def cluster3():
    from tests.test_control_plane import _make_cluster

    agents, apis = _make_cluster(3)
    yield agents, apis
    for api in apis:
        api.shutdown()
    for a in agents:
        a.shutdown()


def _leader_of(agents):
    for a in agents:
        if a.is_leader():
            return a
    return None


def _settled_leader(agents, quiet_s=0.6, timeout=30.0):
    """The leader once leadership has SETTLED: every server names the same
    leader in the same term, its Server runs, and nothing moved for
    `quiet_s`. The first server elected after start can lose the next
    vote of a split election; an eval registered in between is processed
    by (and its spans are stamped with) whoever leads by then."""
    def view():
        leader = _leader_of(agents)
        if leader is None or not leader.server._running:
            return None
        seen = {(a.raft.leader_id, a.raft.term) for a in agents}
        return (leader, seen.pop()) if len(seen) == 1 else None

    deadline = time.time() + timeout
    while time.time() < deadline:
        first = view()
        if first is not None:
            time.sleep(quiet_s)
            if view() == first:
                return first[0]
        time.sleep(0.05)
    return None


class TestDistributedPropagation:
    def test_follower_submit_yields_one_parented_tree(self, cluster3):
        agents, apis = cluster3
        leader = _settled_leader(agents)
        assert leader is not None, "leadership never settled"
        fidx = next(i for i, a in enumerate(agents) if a is not leader)
        leader.call("node_register", mock.node())
        api = NomadClient(apis[fidx].addr[0], apis[fidx].addr[1])
        sdk = mint()  # the SDK caller's own context (traceparent header)
        out = api.register_job_traced(
            mock.job(), traceparent=format_traceparent(sdk))
        tid = out["trace_id"]
        assert tid == sdk.trace_id, \
            "ingress must continue the inbound traceparent"
        assert leader.server.wait_for_eval(out["eval_id"],
                                           timeout=30.0) is not None
        want = {"http.submit", "rpc.forward", "eval", "plan.apply"}
        store = default_spans()
        assert _wait(lambda: want <= {
            s["name"] for s in store.for_trace(tid)}), (
            want - {s["name"] for s in store.for_trace(tid)})
        recs = store.for_trace(tid)
        # ONE trace: every span is reachable from the SDK root — the
        # only out-of-process parent allowed is the SDK's own span id
        ids = {s["span_id"] for s in recs}
        orphans = [s for s in recs
                   if s["parent_span_id"] not in ids
                   and s["parent_span_id"] != sdk.span_id]
        assert not orphans, [(s["name"], s["parent_span_id"])
                             for s in orphans]
        by_name = {}
        for s in recs:
            by_name.setdefault(s["name"], []).append(s)
        # the ingress span ran on the FOLLOWER and parents under the SDK
        sub = by_name["http.submit"][0]
        assert sub["parent_span_id"] == sdk.span_id
        assert sub["source"].startswith(agents[fidx].config.node_id + ".")
        # ...the eval span (leader-side) descends from a forwarding hop
        # that itself parents under the ingress span. A retried forward
        # (leader discovery) may add sibling hops — all still under the
        # ingress — but the eval's OWN parent must be a real hop span.
        ev = by_name["eval"][0]
        fwd = next(s for s in by_name["rpc.forward"]
                   if s["span_id"] == ev["parent_span_id"])
        assert fwd["parent_span_id"] == sub["span_id"]
        assert fwd["detail"]["method"] == "Server.job_register"
        # ...stamped by the server that led when the eval was processed:
        # the first dispatch's compile can starve the leader's heartbeats
        # long enough for the vote to move, so that need not be the
        # leader picked above — but only a server that led runs workers
        (ran,) = [a for a in agents
                  if ev["source"].startswith(a.config.node_id + ".")]
        assert ran.raft.metrics.counter("raft.leadership_gained").value >= 1
        # ...every scheduler phase under the eval span...
        phases = [s for s in recs if s["name"].startswith("eval.")]
        assert phases, "no scheduler phase spans mirrored"
        assert all(s["parent_span_id"] == ev["span_id"] for s in phases)
        # ...and the raft commit under the eval span too
        pa = by_name["plan.apply"][0]
        assert pa["parent_span_id"] == ev["span_id"]
        assert pa["detail"]["placed"] >= 1
        # every name used is vocabulary — the stitcher's contract
        assert {s["name"] for s in recs} <= SPAN_NAMES

    def test_trace_endpoint_and_cli_stitch(self, cluster3, capsys):
        from nomad_tpu.cli import main as cli_main

        agents, apis = cluster3
        assert _wait(lambda: _leader_of(agents) is not None)
        leader = _leader_of(agents)
        fidx = next(i for i, a in enumerate(agents) if a is not leader)
        leader.call("node_register", mock.node())
        api = NomadClient(apis[fidx].addr[0], apis[fidx].addr[1])
        out = api.register_job_traced(mock.job())
        tid = out["trace_id"]
        assert leader.server.wait_for_eval(out["eval_id"],
                                           timeout=30.0) is not None
        # let the trace quiesce so the cursor check below can't race a
        # late span (the store is process-global, seq is global too)
        def _settled():
            n = len(api.trace(tid)["spans"])
            time.sleep(0.2)
            return len(api.trace(tid)["spans"]) == n

        assert _wait(_settled, timeout=10.0)
        # GET /v1/trace/:id on any server returns that trace's spans,
        # with the long-poll cursor shape of the event stream
        t = api.trace(tid)
        assert t["trace_id"] == tid and t["index"] >= len(t["spans"]) > 0
        assert all(s["trace_id"] == tid for s in t["spans"])
        # cursor past the end + no wait → empty, prompt
        t2 = api.trace(tid, index=t["index"])
        assert t2["spans"] == []
        # the CLI stitches across gossip-discovered servers: rc 0 and a
        # waterfall that names the hops
        addr = f"http://{apis[fidx].addr[0]}:{apis[fidx].addr[1]}"
        rc = cli_main(["-address", addr, "trace", tid])
        got = capsys.readouterr().out
        assert rc == 0
        for name in ("http.submit", "eval", "plan.apply"):
            assert name in got
        assert f"Trace {tid}" in got

    def test_cli_unknown_trace_exit_1_one_line(self, cluster3, capsys):
        from nomad_tpu.cli import main as cli_main

        agents, apis = cluster3
        addr = f"http://{apis[0].addr[0]}:{apis[0].addr[1]}"
        rc = cli_main(["-address", addr, "trace", "f" * 32])
        cap = capsys.readouterr()
        assert rc == 1
        assert cap.err.startswith("Error:")
        assert "Traceback" not in cap.err

    def test_disabled_tracing_stamps_nothing(self, cluster3, monkeypatch):
        """NOMAD_TPU_TRACE=0: submits succeed,
        no trace id is returned, no spans are recorded for the job."""
        monkeypatch.setenv("NOMAD_TPU_TRACE", "0")
        agents, apis = cluster3
        assert _wait(lambda: _leader_of(agents) is not None)
        leader = _leader_of(agents)
        api = NomadClient(apis[0].addr[0], apis[0].addr[1])
        before = default_spans().last_index()
        out = api.register_job_traced(mock.job())
        assert out["trace_id"] == ""
        assert leader.server.wait_for_eval(out["eval_id"],
                                           timeout=30.0) is not None
        _, recs = default_spans().spans_after(before)
        assert recs == [], [s["name"] for s in recs]


@pytest.mark.slow
class TestTraceSoak:
    """Soak-length stitch gate: sustained traced submits through the
    3-server cluster, every trace read back complete. The fast suite
    proves one tree; this proves the stitch RATE holds under a steady
    stream (acceptance read: >= 0.99)."""

    def test_sustained_submits_stitch_rate(self, cluster3):
        agents, apis = cluster3
        assert _wait(lambda: _leader_of(agents) is not None)
        leader = _leader_of(agents)
        fidx = next(i for i, a in enumerate(agents) if a is not leader)
        leader.call("node_register", mock.node())
        api = NomadClient(apis[fidx].addr[0], apis[fidx].addr[1])
        outs = []
        for _ in range(40):
            out = api.register_job_traced(mock.job())
            assert out["trace_id"]
            outs.append(out)
        for out in outs:
            assert leader.server.wait_for_eval(out["eval_id"],
                                               timeout=60.0) is not None
        store = default_spans()
        stitched = 0
        for out in outs:
            tid = out["trace_id"]
            # complete = the eval span landed and every parent resolves
            # inside the tree (the ingress root has no in-store parent)
            if not _wait(lambda t=tid: any(
                    s["name"] == "eval" for s in store.for_trace(t)),
                    timeout=10.0):
                continue
            recs = store.for_trace(tid)
            ids = {s["span_id"] for s in recs}
            roots = [s for s in recs if not s["parent_span_id"]]
            orphans = [s for s in recs
                       if s["parent_span_id"]
                       and s["parent_span_id"] not in ids]
            if len(roots) == 1 and not orphans:
                stitched += 1
        assert stitched / len(outs) >= 0.99, \
            f"stitch rate {stitched}/{len(outs)}"
