"""Telemetry subsystem: MetricsRegistry instruments, eval-lifecycle
tracer, HTTP surfaces (/v1/metrics incl. Prometheus exposition,
/v1/evaluation/:id/trace), and the statsd push path.

Reference models: armon/go-metrics (IncrCounter/SetGauge/AddSample +
inmem sink served on /v1/metrics, command/agent/command.go:952
setupTelemetry) and the `telemetry { prometheus_metrics }` exposition.
The span tracer has no reference analog — its contract is pinned here
instead: ordered spans from broker enqueue through ack for an eval run
through the real control plane."""
import logging
import random
import socket
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.lib.metrics import (ErrorStreak, MetricsRegistry,
                                   StatsdSink, TelemetryEmitter, flatten)
from nomad_tpu.lib.trace import EvalTracer


def _wait(cond, timeout=15.0, every=0.05):
    dl = time.time() + timeout
    while time.time() < dl:
        if cond():
            return True
        time.sleep(every)
    return cond()


class TestRegistry:
    def test_concurrent_writers_lose_nothing(self):
        """8 threads hammering one counter/histogram/gauge: every
        increment and sample must land (the failure mode of the old
        unlocked stats dicts was silent lost updates)."""
        r = MetricsRegistry()
        n_threads, per = 8, 2000

        def work(tid):
            for k in range(per):
                r.inc("c")
                r.add_sample("h", k)
                r.set_gauge("g", k)
                r.counter(f"per.{tid}").inc()

        ts = [threading.Thread(target=work, args=(i,))
              for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert r.counter("c").value == n_threads * per
        h = r.histogram("h")
        assert h.count == n_threads * per
        assert h.sum == n_threads * sum(range(per))
        for i in range(n_threads):
            assert r.counter(f"per.{i}").value == per

    def test_histogram_quantiles_exact(self):
        r = MetricsRegistry()
        h = r.histogram("h", window=2048)
        vals = list(range(1, 1001))
        random.Random(3).shuffle(vals)
        for v in vals:
            h.add(v)
        s = h.summary()
        # nearest-rank over 1..1000
        assert s["p50"] == 500
        assert s["p95"] == 950
        assert s["p99"] == 990
        assert s["min"] == 1 and s["max"] == 1000
        assert s["count"] == 1000 and s["sum"] == 500500
        assert s["mean"] == 500.5

    def test_histogram_window_slides(self):
        h = MetricsRegistry().histogram("h", window=4)
        for v in range(1, 9):  # window keeps 5,6,7,8
            h.add(v)
        s = h.summary()
        assert s["count"] == 8 and s["min"] == 1 and s["max"] == 8
        assert s["p50"] == 6  # quantiles over the WINDOW only
        assert h.quantile(1.0) == 8

    def test_counters_prefix_view(self):
        r = MetricsRegistry()
        r.inc("worker.0.batch.evals", 3)
        r.inc("worker.0.batch.kernel_ms", 1.5)
        r.inc("other", 9)
        view = r.counters(prefix="worker.0.batch.")
        assert view == {"evals": 3, "kernel_ms": 1.5}
        assert isinstance(view["evals"], int)  # integral stays int-y

    def test_prometheus_exposition(self):
        r = MetricsRegistry()
        r.inc("broker.acked", 3)
        r.set_gauge("broker.ready", 2)
        for v in (1.0, 2.0, 3.0, 4.0):
            r.add_sample("eval.phase.kernel_ms", v)
        text = r.prometheus()
        lines = text.splitlines()
        assert "# TYPE nomad_broker_acked counter" in lines
        assert "nomad_broker_acked 3" in lines
        assert "# TYPE nomad_broker_ready gauge" in lines
        assert "# TYPE nomad_eval_phase_kernel_ms summary" in lines
        assert 'nomad_eval_phase_kernel_ms{quantile="0.5"} 2' in lines
        assert 'nomad_eval_phase_kernel_ms{quantile="0.99"} 4' in lines
        assert "nomad_eval_phase_kernel_ms_sum 10" in lines
        assert "nomad_eval_phase_kernel_ms_count 4" in lines
        assert text.endswith("\n")

    def test_error_streak_first_of_streak_warns(self, caplog):
        r = MetricsRegistry()
        es = ErrorStreak("unit.loop", registry=r)
        with caplog.at_level(logging.DEBUG, logger="nomad_tpu.loops"):
            es.record(ValueError("one"))
            es.record(ValueError("two"))
            es.ok()  # success re-arms the streak
            es.record(ValueError("three"))
        warns = [rec for rec in caplog.records
                 if rec.levelno == logging.WARNING]
        debugs = [rec for rec in caplog.records
                  if rec.levelno == logging.DEBUG]
        assert len(warns) == 2  # first of each streak
        assert len(debugs) == 1  # the streak tail
        assert es.count == 3
        assert r.counter("loop_errors.unit.loop").value == 3


class TestTracer:
    def test_span_ordering_and_phase_histograms(self):
        r = MetricsRegistry()
        tr = EvalTracer(r)
        tr.begin("e1")
        tr.span_from_mark("e1", "enqueue", "queue_wait")
        tr.mark("e1", "dequeue")
        with tr.span("e1", "schedule"):
            time.sleep(0.002)
        tr.record("e1", "ack")
        got = tr.get("e1")
        phases = [s["phase"] for s in got["spans"]]
        assert phases == ["queue_wait", "schedule", "ack"]
        starts = [s["start_s"] for s in got["spans"]]
        assert starts == sorted(starts)
        hist = r.snapshot()["histograms"]
        assert hist["eval.phase.schedule_ms"]["count"] == 1
        assert hist["eval.phase.schedule_ms"]["p50"] >= 2.0

    def test_unknown_ids_are_noops(self):
        tr = EvalTracer(MetricsRegistry())
        tr.mark("ghost", "dequeue")
        tr.span_from_mark("ghost", "enqueue", "queue_wait")
        tr.record("ghost", "ack")
        assert tr.get("ghost") is None

    def test_bounded_lru_evicts_oldest(self):
        tr = EvalTracer(MetricsRegistry(), capacity=3)
        for i in range(5):
            tr.begin(f"e{i}")
            tr.record(f"e{i}", "ack")
        assert tr.get("e0") is None and tr.get("e1") is None
        assert tr.get("e4") is not None
        assert len(tr.trace_ids()) == 3


class TestE2ETrace:
    """A real eval through Server → broker → worker → plan apply must
    leave a complete, ordered trace and per-phase histograms."""

    def _run(self, eval_batch, n_jobs):
        from nomad_tpu.server import Server, ServerConfig
        from nomad_tpu.synth import synth_node, synth_service_job

        rng = random.Random(11)
        s = Server(ServerConfig(num_schedulers=1, heartbeat_ttl=3600.0,
                                eval_batch=eval_batch))
        for i in range(16):
            s.state.upsert_node(synth_node(rng, i))
        jobs = [synth_service_job(rng, count=2) for _ in range(n_jobs)]
        evs = [s.job_register(j) for j in jobs]
        s.start()
        try:
            for ev in evs:
                got = s.wait_for_eval(
                    ev.id, statuses=("complete", "failed", "blocked",
                                     "cancelled"), timeout=60.0)
                assert got is not None and got.status == "complete", got
            traces = [s.tracer.get(ev.id) for ev in evs]
            snap = s.metrics.snapshot()
            wstats = dict(s.workers[0].batch_stats)
        finally:
            s.shutdown()
        return traces, snap, wstats

    def test_single_eval_trace_complete_and_ordered(self):
        (trace,), snap, _ = self._run(eval_batch=1, n_jobs=1)
        assert trace is not None
        phases = [s["phase"] for s in trace["spans"]]
        # one span per phase, queue_wait first, ack last
        for want in ("queue_wait", "claim", "snapshot", "schedule",
                     "plan_apply", "ack"):
            assert phases.count(want) == 1, (want, phases)
        assert phases[0] == "queue_wait" and phases[-1] == "ack"
        starts = [s["start_s"] for s in trace["spans"]]
        assert starts == sorted(starts)
        # schedule encloses plan_apply (the scheduler submits the plan)
        by = {s["phase"]: s for s in trace["spans"]}
        sched, pa = by["schedule"], by["plan_apply"]
        assert sched["start_s"] <= pa["start_s"]
        assert (pa["start_s"] + pa["duration_ms"] / 1e3
                <= sched["start_s"] + sched["duration_ms"] / 1e3 + 1e-6)
        hists = snap["histograms"]
        for want in ("queue_wait", "schedule", "plan_apply", "ack"):
            assert hists[f"eval.phase.{want}_ms"]["count"] == 1

    def test_batched_evals_carry_pack_and_kernel_spans(self):
        traces, snap, wstats = self._run(eval_batch=8, n_jobs=12)
        assert wstats.get("batched", 0) > 0, wstats
        fused = [t for t in traces if t is not None
                 and "kernel" in [s["phase"] for s in t["spans"]]]
        assert fused, "no eval carried a kernel span despite batching"
        for t in fused:
            phases = [s["phase"] for s in t["spans"]]
            assert "pack" in phases and "delta_apply" in phases
            # fused phases happen inside the schedule window
            by = {s["phase"]: s for s in t["spans"]}
            assert by["pack"]["start_s"] >= by["schedule"]["start_s"]
        hists = snap["histograms"]
        # recorded once a dispatch (its timeline record), joined into
        # each rider's trace: no per-eval copy of the interval
        for gone in ("pack", "delta_apply", "kernel"):
            assert f"eval.phase.{gone}_ms" not in hists
        assert 1 <= hists["pipeline.kernel_ms"]["count"] <= len(fused)
        assert hists["pipeline.pack_ms"]["count"] \
            == snap["counters"]["pipeline.dispatches"]


class TestHttpSurfaces:
    @pytest.fixture()
    def agent(self, tmp_path):
        from nomad_tpu.agent import Agent, AgentConfig
        from nomad_tpu.api import NomadClient

        a = Agent(AgentConfig(data_dir=str(tmp_path / "data"),
                              heartbeat_ttl=60.0))
        a.start()
        api = NomadClient(a.http_addr[0], a.http_addr[1])
        assert _wait(lambda: len(api.nodes()) == 1)
        yield a, api
        a.shutdown()

    def _run_job(self, api):
        job = mock.job()
        t = job.task_groups[0].tasks[0]
        t.driver = "mock_driver"
        t.config = {"run_for": 0.05}
        eval_id = api.register_job(job)
        ev = api.wait_for_eval(eval_id)
        assert ev.status == "complete"
        return eval_id

    def test_trace_route_and_404(self, agent):
        from nomad_tpu.api import ApiError

        a, api = agent
        eval_id = self._run_job(api)
        tr = api.evaluation_trace(eval_id)
        assert tr["eval_id"] == eval_id
        phases = [s["phase"] for s in tr["spans"]]
        for want in ("queue_wait", "schedule", "plan_apply", "ack"):
            assert want in phases
        assert phases[-1] == "ack"
        with pytest.raises(ApiError) as ei:
            api.evaluation_trace("does-not-exist")
        assert ei.value.code == 404

    def test_http_times_each_put_and_post_and_no_get(self, agent):
        """`http.requests` / `http.handler_ms` / `http.cpu_ms` (ISSUE 38):
        every PUT or POST that returns a response, an error too; no
        GET, the event stream's push and long-poll pages among them."""
        import http.client

        from nomad_tpu.lib.metrics import default_registry
        from nomad_tpu.api import ApiError

        a, api = agent
        reg = default_registry()
        n, h, cpu = (reg.counter("http.requests"),
                     reg.histogram("http.handler_ms"),
                     reg.counter("http.cpu_ms"))
        n0, h0, cpu0 = n.value, h.count, cpu.value
        api.nodes()
        api.metrics()
        api.events(index=0, wait=0.1)
        conn = http.client.HTTPConnection(*a.http_addr, timeout=10)
        conn.request("GET", "/v1/event/stream?stream=1&index=0"
                     "&heartbeat=0.05")
        res = conn.getresponse()
        assert res.status == 200 and res.fp.readline()  # one frame
        conn.close()
        assert (n.value, h.count) == (n0, h0)
        self._run_job(api)                         # PUT /v1/jobs
        with pytest.raises(ApiError):              # PUT, answered 404
            api._request("PUT", "/v1/no-such-route", body={})
        conn = http.client.HTTPConnection(*a.http_addr, timeout=10)
        conn.request("POST", "/v1/no-such-route", body=b"{}",
                     headers={"Content-Type": "application/json"})
        assert conn.getresponse().status == 404
        conn.close()
        # counted after the response is written: the client may read it
        # first
        assert _wait(lambda: n.value - n0 == 3 and h.count - h0 == 3,
                     timeout=10.0), (n.value - n0, h.count - h0)
        assert h.sum > 0.0 and cpu.value >= cpu0

    def test_metrics_carries_phase_histograms(self, agent):
        a, api = agent
        self._run_job(api)
        m = api.metrics()
        assert m["broker"]["acked"] >= 1
        phases = m["eval_phases"]
        assert phases["queue_wait_ms"]["count"] >= 1
        for k in ("p50", "p95", "p99", "mean", "count"):
            assert k in phases["schedule_ms"]
        # registry snapshot is also exported wholesale
        assert "eval.phase.schedule_ms" in m["telemetry"]["histograms"]

    def test_metrics_prometheus_exposition(self, agent):
        a, api = agent
        self._run_job(api)
        text = api.metrics_prometheus()
        assert "# TYPE nomad_broker_acked counter" in text
        assert "# TYPE nomad_eval_phase_schedule_ms summary" in text
        assert 'nomad_eval_phase_schedule_ms{quantile="0.99"}' in text
        # every exposed line is well-formed: comment or `name value`
        for line in text.splitlines():
            assert line.startswith("# ") or len(line.split(" ")) == 2


class TestStatsdRoundTrip:
    def test_registry_snapshot_reaches_statsd_socket(self):
        """Full push path: registry → snapshot → flatten → UDP statsd
        gauge lines on a loopback socket."""
        sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        sock.bind(("127.0.0.1", 0))
        sock.settimeout(10.0)
        port = sock.getsockname()[1]
        reg = MetricsRegistry()
        reg.inc("broker.acked", 2)
        reg.add_sample("eval.phase.kernel_ms", 5.0)
        em = TelemetryEmitter(lambda: reg.snapshot(),
                              StatsdSink(f"127.0.0.1:{port}"),
                              interval=0.05)
        em.start()
        try:
            payload = sock.recv(65536).decode()
        finally:
            em.stop()
            sock.close()
        lines = payload.splitlines()
        assert "nomad.counters.broker.acked:2|g" in lines
        assert "nomad.histograms.eval.phase.kernel_ms.count:1|g" in lines
        assert "nomad.histograms.eval.phase.kernel_ms.p50:5|g" in lines

    def test_flatten_skips_non_numeric(self):
        out = flatten({"a": {"b": 1, "s": "text"}, "ok": True})
        assert out == {"nomad.a.b": 1.0, "nomad.ok": 1.0}


class TestLabeledExposition:
    """Labeled Prometheus series for the transfer ledger + pipeline
    counters (ISSUE 6 satellite): label-value escaping lives in
    lib/metrics.py and is pinned here byte-for-byte."""

    def test_escape_label_value(self):
        from nomad_tpu.lib.metrics import escape_label_value

        assert escape_label_value("plain.site") == "plain.site"
        assert escape_label_value('say "hi"') == 'say \\"hi\\"'
        assert escape_label_value("a\\b") == "a\\\\b"
        assert escape_label_value("line\nbreak") == "line\\nbreak"
        # backslash escapes FIRST: a literal `\"` must not double-escape
        # into a broken sequence
        assert escape_label_value('\\"') == '\\\\\\"'

    def test_prometheus_line(self):
        from nomad_tpu.lib.metrics import prometheus_line

        assert prometheus_line("m", {}, 2.0) == "m 2"
        # labels sort by key for deterministic output
        line = prometheus_line("m", {"b": "2", "a": "1"}, 1.5)
        assert line == 'm{a="1",b="2"} 1.5'
        line = prometheus_line("m", {"site": 'we"ird\\x'}, 3)
        assert line == 'm{site="we\\"ird\\\\x"} 3'

    def test_ledger_exposition_labels_and_escaping(self):
        from nomad_tpu.lib.transfer import TransferLedger

        led = TransferLedger()
        led.record("stack.hot_delta", 100, seconds=0.001, count=2)
        led.record('odd"site\\n', 7)
        text = led.prometheus()
        lines = text.splitlines()
        assert "# TYPE nomad_transfer_bytes_total counter" in lines
        assert 'nomad_transfer_bytes_total{site="stack.hot_delta"} 100' \
            in lines
        assert 'nomad_transfer_count_total{site="stack.hot_delta"} 2' \
            in lines
        assert 'nomad_transfer_ms_total{site="stack.hot_delta"} 1' in lines
        assert 'nomad_transfer_bytes_total{site="odd\\"site\\\\n"} 7' \
            in lines
        assert text.endswith("\n")
        # empty ledger exposes nothing (no dangling TYPE headers)
        assert TransferLedger().prometheus() == ""

    def test_timeline_counters_reach_registry_exposition(self):
        from nomad_tpu.lib.transfer import DispatchTimeline

        reg = MetricsRegistry()
        tl = DispatchTimeline(registry=reg)
        b = tl.mono_anchor
        s1 = tl.commit(programs=2, batched=True, pack=(b, b + 0.001),
                       view=(b + 0.001, b + 0.002),
                       kernel_start=b + 0.002, transfer_bytes=64,
                       transfer_count=3)
        tl.kernel_end(s1, b + 0.004)
        text = reg.prometheus()
        assert "# TYPE nomad_pipeline_dispatches counter" in text
        assert "nomad_pipeline_dispatches 1" in text
        assert "nomad_pipeline_transfer_bytes 64" in text
        assert "# TYPE nomad_pipeline_kernel_ms summary" in text
        assert "# TYPE nomad_pipeline_pack_ms summary" in text

    def test_agent_exposition_carries_ledger_sites(self):
        """The agent's /v1/metrics?format=prometheus concatenation
        includes the process ledger's labeled family."""
        from nomad_tpu.lib.transfer import default_ledger

        led = default_ledger()
        led.record("test.exposition_site", 11)
        from nomad_tpu.agent import Agent, AgentConfig

        a = Agent(AgentConfig(client=False, heartbeat_ttl=60.0))
        a.start()
        try:
            text = a.metrics_prometheus()
        finally:
            a.shutdown()
            # the process ledger outlives this test: a made-up site left
            # in it fails test_metrics_names' site pin whenever both
            # files land on one xdist worker
            with led._lock:
                led._sites.pop("test.exposition_site", None)
        assert ('nomad_transfer_bytes_total{site="test.exposition_site"}'
                in text)
