"""HBM residency ledger (ISSUE 11).

Four contracts under test, all on JAX_PLATFORMS=cpu:

- LEDGER MECHANICS: finalizer-backed bookings (live-bytes leaves with
  the buffer's last reference), re-siting moves bytes instead of
  double-counting, per-(site, shard) rows, peak retention, labeled
  Prometheus exposition pinned byte-for-byte.
- LEASES: owner-token lifetime tracking mirrors the view leases the
  fused dispatch takes; a lease older than the age watermark is flagged
  stuck, counted, and warned ErrorStreak-style.
- LEAK GATE: after a steady-state fused window (the
  test_program_table.py counter-gated idiom) under
  `jax.transfer_guard("disallow")` there are ZERO outstanding leases,
  ZERO unfreed carries or lazy outputs, and ledger live-bytes is back
  at the post-warmup baseline — a leaked device buffer fails CI here,
  not production.
- CAPACITY PLANNER: the projection from measured per-row costs matches
  a directly-measured 2x cluster-size upload within 15% (the ISSUE 11
  acceptance bound; on the linear tensor layout it is near-exact).
"""
import gc
import random
import time
import uuid

import numpy as np
import pytest

from nomad_tpu.lib.hbm import (HbmLedger, default_hbm, device_memory_stats,
                               plan_capacity, reconcile)
from nomad_tpu.lib.metrics import MetricsRegistry

import tests.test_program_table as tpt


class TestLedgerMechanics:
    def test_track_and_gc_release(self):
        led = HbmLedger()
        a = np.zeros((16, 8), dtype=np.float32)
        led.track("t.site", a, rows=16)
        snap = led.snapshot()["t.site"]
        assert snap["live_bytes"] == a.nbytes
        assert snap["buffers"] == 1
        assert snap["rows"] == 16
        nbytes = a.nbytes
        del a
        gc.collect()
        snap = led.snapshot()["t.site"]
        assert snap["live_bytes"] == 0
        assert snap["buffers"] == 0
        # peak survives the release
        assert snap["peak_bytes"] == nbytes

    def test_track_is_idempotent_per_site(self):
        led = HbmLedger()
        a = np.zeros(64, dtype=np.uint8)
        led.track("t.a", a)
        led.track("t.a", a)
        assert led.snapshot()["t.a"]["live_bytes"] == 64
        assert led.snapshot()["t.a"]["buffers"] == 1

    def test_resite_moves_bytes_without_double_count(self):
        """The carry-adoption shape: a buffer booked at
        select_batch.carry becomes the view's hot buffer — bytes MOVE,
        they must not count twice."""
        led = HbmLedger()
        a = np.zeros(256, dtype=np.uint8)
        led.track("t.carry", a)
        led.track("t.view", a)
        snap = led.snapshot()
        assert snap["t.carry"]["live_bytes"] == 0
        assert snap["t.view"]["live_bytes"] == 256
        live, bufs, _peak = led.totals()
        assert (live, bufs) == (256, 1)
        del a
        gc.collect()
        assert led.totals()[0] == 0

    def test_jax_arrays_release_on_gc(self):
        import jax.numpy as jnp

        led = HbmLedger()
        a = jnp.zeros((32, 32), dtype=jnp.float32)
        led.track("t.jax", a)
        assert led.totals()[0] == 32 * 32 * 4
        del a
        gc.collect()
        assert led.totals()[0] == 0

    def test_untracked_scalars_do_not_leak(self):
        led = HbmLedger()
        led.track("t.x", 7)                 # no nbytes: ignored
        led.track("t.x", np.float64(3.0))   # no weakref: dropped
        live, bufs, _ = led.totals()
        assert (live, bufs) == (0, 0)

    def test_prometheus_exposition_pinned(self):
        led = HbmLedger()
        a = np.zeros(128, dtype=np.uint8)
        led.track("s.one", a)
        text = led.prometheus()
        assert text == (
            "# TYPE nomad_hbm_live_bytes gauge\n"
            'nomad_hbm_live_bytes{shard="0",site="s.one"} 128\n'
            "# TYPE nomad_hbm_buffers gauge\n"
            'nomad_hbm_buffers{shard="0",site="s.one"} 1\n'
            "# TYPE nomad_hbm_peak_bytes gauge\n"
            'nomad_hbm_peak_bytes{shard="0",site="s.one"} 128\n')

    def test_registry_mirror(self):
        reg = MetricsRegistry()
        led = HbmLedger(registry=reg)
        a = np.zeros(512, dtype=np.uint8)
        led.track("t.m", a)
        snap = reg.snapshot()
        assert snap["gauges"]["hbm.live_bytes_total"] == 512
        assert snap["gauges"]["hbm.buffers_total"] == 1
        assert snap["counters"]["hbm.allocs"] == 1
        del a
        gc.collect()
        snap = reg.snapshot()
        assert snap["gauges"]["hbm.live_bytes_total"] == 0
        assert snap["counters"]["hbm.releases"] == 1
        assert snap["gauges"]["hbm.peak_bytes_total"] == 512


class TestLeases:
    def test_lease_lifecycle_and_high_water(self):
        led = HbmLedger()
        led.lease("tok-1")
        led.lease("tok-2")
        assert led.outstanding_leases() == 2
        assert led.lease_high_water == 2
        age = led.release_lease("tok-1")
        assert age is not None and age >= 0.0
        assert led.release_lease("tok-1") is None  # idempotent
        led.release_lease("tok-2")
        assert led.outstanding_leases() == 0
        assert led.lease_high_water == 2
        assert led.lease_age_high_water_s >= 0.0

    def test_stuck_lease_watermark(self, monkeypatch):
        monkeypatch.setenv("NOMAD_TPU_HBM_LEASE_WATERMARK_S", "0.01")
        reg = MetricsRegistry()
        led = HbmLedger(registry=reg)
        led.lease("wedged", "stack.view")
        time.sleep(0.03)
        leases = led.leases()
        assert len(leases) == 1 and leases[0]["stuck"]
        assert leases[0]["age_s"] > 0.01
        assert reg.snapshot()["counters"]["hbm.stuck_leases"] == 1
        # a second check does not re-count the same stuck lease
        led.leases()
        assert reg.snapshot()["counters"]["hbm.stuck_leases"] == 1
        # release re-arms the streak; a fresh lease is not stuck
        led.release_lease("wedged")
        led.lease("fine")
        assert not led.leases()[0]["stuck"]
        led.release_lease("fine")

    def test_prometheus_scrape_runs_watermark_check(self, monkeypatch):
        """Metrics-only deployments (Prometheus scrape, nobody reading
        /v1/operator/hbm) must still surface a wedged lease."""
        monkeypatch.setenv("NOMAD_TPU_HBM_LEASE_WATERMARK_S", "0.01")
        reg = MetricsRegistry()
        led = HbmLedger(registry=reg)
        led.lease("wedged")
        time.sleep(0.03)
        led.prometheus()
        assert reg.snapshot()["counters"]["hbm.stuck_leases"] == 1
        led.release_lease("wedged")

    def test_watermark_disabled_by_zero(self, monkeypatch):
        monkeypatch.setenv("NOMAD_TPU_HBM_LEASE_WATERMARK_S", "0")
        led = HbmLedger()
        led.lease("t")
        time.sleep(0.01)
        assert not led.leases()[0]["stuck"]
        led.release_lease("t")


class TestPlannerMath:
    def test_invalid_args_raise(self):
        with pytest.raises(ValueError):
            plan_capacity(0, 10, HbmLedger())
        with pytest.raises(ValueError):
            plan_capacity(10, -1, HbmLedger())

    def test_projection_terms(self, monkeypatch):
        """node term scales per measured row, fixed stays, transient
        projects at peak; shards split only the node term."""
        led = HbmLedger()
        view = np.zeros((64, 16), dtype=np.float32)  # 64 B per row
        table = np.zeros(1000, dtype=np.uint8)
        led.track("stack.view_hot", view, rows=64)
        led.track("program_table.i32", table)
        transient = np.zeros(300, dtype=np.uint8)
        led.track("select_batch.batch_out", transient)
        del transient
        gc.collect()  # live 0, peak 300 — the planner must use peak
        plan = plan_capacity(1000, 50_000, led)
        assert plan["projected_n_cap"] == 1024
        assert plan["per_node_bytes"] == 64.0
        assert plan["node_bytes"] == 64 * 1024
        assert plan["fixed_bytes"] == 1000
        assert plan["transient_peak_bytes"] == 300
        assert plan["projected_bytes"] == 64 * 1024 + 1300
        assert plan["measured"] and plan["per_alloc_bytes"] == 0.0
        # force a tiny device: the node axis must shard until it fits
        monkeypatch.setenv("NOMAD_TPU_HBM_GB", str(20_000 / (1 << 30)))
        plan = plan_capacity(1000, 50_000, led)
        if plan["limit_source"] == "env":  # real memory_stats wins
            assert not plan["fits"]
            assert plan["shards_needed"] == 4  # 65536/4 + 1300 < 20000

    def test_unmeasured_ledger_flagged(self):
        plan = plan_capacity(100, 100, HbmLedger())
        assert not plan["measured"]
        assert plan["node_bytes"] == 0

    def test_unshardable_fixed_footprint_reports_zero_shards(
            self, monkeypatch):
        """When the replicated fixed state alone exceeds the device,
        no node-axis split helps — shards_needed must read 0, not an
        astronomically doubled count."""
        led = HbmLedger()
        view = np.zeros((64, 16), dtype=np.float32)
        table = np.zeros(5000, dtype=np.uint8)
        led.track("stack.view_hot", view, rows=64)
        led.track("program_table.i32", table)
        monkeypatch.setenv("NOMAD_TPU_HBM_GB", str(4000 / (1 << 30)))
        plan = plan_capacity(1000, 10, led)
        if plan["limit_source"] == "env":  # real memory_stats wins
            assert not plan["fits"]
            assert plan["shards_needed"] == 0

    def test_nonpositive_env_limit_falls_back_to_default(
            self, monkeypatch):
        from nomad_tpu.lib.hbm import device_limit_bytes

        monkeypatch.setenv("NOMAD_TPU_HBM_GB", "0")
        limit, src = device_limit_bytes()
        if src != "memory_stats":
            assert src == "default" and limit == 16 * (1 << 30)

    def test_absurd_shard_width_reports_zero(self, monkeypatch):
        """Replicated state just UNDER the limit: a split would 'work'
        only at thousands of shards, each ~100% full of replicated
        state — unactionable, so shards_needed must read 0 too."""
        led = HbmLedger()
        view = np.zeros(64, dtype=np.float32)          # 4 B per row
        fixed = np.zeros(99_000, dtype=np.uint8)       # limit − 1 KB
        led.track("stack.view_hot", view, rows=64)
        led.track("program_table.i32", fixed)
        monkeypatch.setenv("NOMAD_TPU_HBM_GB", str(100_000 / (1 << 30)))
        plan = plan_capacity(1_000_000, 10, led)
        if plan["limit_source"] == "env":  # real memory_stats wins
            assert not plan["fits"]
            assert plan["shards_needed"] == 0  # 4 MB / 1 KB budget


def _view_stack(cl):
    from nomad_tpu.scheduler.stack import TPUStack

    return TPUStack(cl)


def _fresh_global_ledger(monkeypatch):
    """Swap the process-global ledger for a fresh one so prior tests'
    still-referenced clusters don't pollute measurements (the stack
    resolves default_hbm() per call)."""
    import nomad_tpu.lib.hbm as hbm_mod

    led = HbmLedger(registry=MetricsRegistry())
    monkeypatch.setattr(hbm_mod, "_default_hbm", led)
    return led


class TestLeakGate:
    def test_steady_state_fused_window_leaks_nothing(self, monkeypatch):
        """ISSUE 11 leak gate: steady-state fused rounds under
        transfer_guard("disallow") leave zero outstanding leases, zero
        unfreed carries/lazy outputs, and total live-bytes exactly at
        the post-warmup baseline."""
        led = _fresh_global_ledger(monkeypatch)
        rng = random.Random(7)
        cl = tpt._mini_cluster()
        jobs = [tpt._job(rng, i) for i in range(4)]
        eval_ids = [f"ev-{i}" for i in range(4)]
        # warmup: cold uploads, table inserts, carry warm
        for _ in range(2):
            _coord, res = tpt._run_round(cl, jobs, eval_ids=eval_ids)
            tpt._commit_round(cl, res, eval_ids)
        # consume the last dispatch's carry so the baseline has no
        # in-flight state, then drop transients
        _view_stack(cl).device_arrays()
        res = None
        gc.collect()
        base = led.snapshot()
        base_live = led.totals()[0]
        assert base_live > 0
        assert led.outstanding_leases() == 0

        # the measured steady-state window, guard-fatal like the
        # acceptance criterion demands
        monkeypatch.setenv("NOMAD_TPU_TRANSFER_GUARD", "disallow")
        _coord, res = tpt._run_round(cl, jobs, eval_ids=eval_ids)
        tpt._commit_round(cl, res, eval_ids)
        monkeypatch.delenv("NOMAD_TPU_TRANSFER_GUARD")
        _view_stack(cl).device_arrays()
        res = None
        gc.collect()

        assert led.outstanding_leases() == 0, "leaked view lease"
        snap = led.snapshot()
        assert snap.get("select_batch.carry", {}).get(
            "live_bytes", 0) == 0, "unfreed dispatch carry"
        assert snap.get("select_batch.batch_out", {}).get(
            "live_bytes", 0) == 0, "unresolved lazy outputs"
        # per-site live back at the baseline: steady state replaces
        # same-shaped buffers, it never grows residency
        for site, row in sorted(snap.items()):
            assert row["live_bytes"] == base.get(site, {}).get(
                "live_bytes", 0), f"residency grew at {site}"
        assert led.totals()[0] == base_live
        # the window actually exercised the loop (not vacuous)
        assert snap["select_batch.batch_out"]["allocs"] > \
            base["select_batch.batch_out"]["allocs"]
        assert led.lease_high_water >= 1

    def test_rolled_back_speculation_leaks_nothing(self, monkeypatch):
        """ISSUE 15 extension of the leak gate: a speculative dispatch
        that certification ROLLS BACK in full (foreign conflict, no
        usable footprints) must leave zero outstanding leases, zero
        unfreed carries or lazy outputs, and per-site live-bytes back
        at the pre-speculation baseline — the rollback path frees
        everything the launch booked, under transfer-guard disallow."""
        import tests.test_spec as tsp
        from nomad_tpu.scheduler import stack as stack_mod
        from nomad_tpu.server.select_batch import SelectCoordinator

        led = _fresh_global_ledger(monkeypatch)
        monkeypatch.setenv("NOMAD_TPU_SPEC_ROLLBACK_MAX", "1.0")
        cl = tsp._dc_cluster()
        reg = MetricsRegistry()
        # round 0: warm compiles, fully committed + consumed — the
        # QUIESCED baseline (no in-flight carry) the end state must
        # return to
        _c0, res0 = tpt._run_round(
            cl, [tsp._dc_job("dc1"), tsp._dc_job("dc2")],
            eval_ids=["w1", "w2"])
        tpt._commit_round(cl, res0, ["w1", "w2"])
        _view_stack(cl).device_arrays()
        res0 = None
        gc.collect()
        base = led.snapshot()
        base_live = led.totals()[0]
        assert led.outstanding_leases() == 0
        # round 1: leaves the carry note the speculation chain seeds on
        _c1, res1 = tpt._run_round(
            cl, [tsp._dc_job("dc1"), tsp._dc_job("dc2")],
            eval_ids=["a", "b"])

        monkeypatch.setenv("NOMAD_TPU_TRANSFER_GUARD", "disallow")
        coord2 = SelectCoordinator(registry=reg)
        coord2.trace_ids = {0: "c", 1: "d"}
        coord2.group_ids = {0: 0, 1: 1}
        # NO footprints: every program conflicts with any stale row —
        # the foreign commit below forces a FULL rollback
        threads, res2 = tsp._start_parked(
            cl, [tsp._dc_job("dc1", cpu=250),
                 tsp._dc_job("dc2", cpu=250)], coord2)
        assert coord2.try_spec_launch(cl) == "launched"
        tpt._commit_round(cl, res1, ["a", "b"])
        dc1_node = next(nid for nid in cl.row_of
                        if cl.nodes[nid].datacenter == "dc1")
        cl.upsert_alloc(tsp._foreign_alloc(dc1_node))
        coord2.run()
        for t in threads:
            t.join(30.0)
        monkeypatch.delenv("NOMAD_TPU_TRANSFER_GUARD")
        assert reg.counters().get("spec.rolled_back") == 1
        assert reg.counters().get("spec.redispatch_programs") == 2
        assert all(res2[i][0][0] is not None for i in res2)

        # commit the re-dispatched placements and consume the
        # re-dispatch's in-flight carry, then drop transients
        tpt._commit_round(cl, {i: (r[0], r[2], r[3])
                               for i, r in res2.items()}, ["c", "d"])
        _view_stack(cl).device_arrays()
        res1 = res2 = None
        gc.collect()
        assert led.outstanding_leases() == 0, "leaked spec view lease"
        snap = led.snapshot()
        assert snap.get("select_batch.carry", {}).get(
            "live_bytes", 0) == 0, "unfreed speculative carry"
        assert snap.get("select_batch.batch_out", {}).get(
            "live_bytes", 0) == 0, "unresolved speculative outputs"
        for site, row in sorted(snap.items()):
            assert row["live_bytes"] == base.get(site, {}).get(
                "live_bytes", 0), f"residency grew at {site}"
        assert led.totals()[0] == base_live
        assert stack_mod.spec_chain_head_token(cl) is None

    def test_chain_adoption_round_leaks_nothing(self, monkeypatch):
        """ISSUE 20 extension of the leak gate: a certified-clean
        speculation chain whose HEAD carry the next refresh ADOPTS
        must leave zero outstanding leases, zero unfreed carries or
        lazy outputs, and per-site live-bytes exactly at the warm
        baseline — the adopted (used, dyn_free) buffers are re-sited
        from the carry to the view, accounted once, never twice."""
        import tests.test_spec as tsp
        from nomad_tpu.lib.transfer import guard_scope

        led = _fresh_global_ledger(monkeypatch)
        cl = tsp._dc_cluster()
        reg = MetricsRegistry()
        # warm round: the quiesced baseline with steady buffer shapes
        _c0, res0 = tpt._run_round(
            cl, [tsp._dc_job("dc1"), tsp._dc_job("dc2")],
            eval_ids=["w1", "w2"])
        tpt._commit_round(cl, res0, ["w1", "w2"])
        _view_stack(cl).device_arrays()
        res0 = None
        gc.collect()
        base = led.snapshot()
        base_live = led.totals()[0]
        assert base_live > 0
        assert led.outstanding_leases() == 0

        # three certified-clean speculative dispatches, nothing rolls
        # back, then the refresh adopts the chain HEAD carry
        _r, fin_res, fin_ids = tsp._drive_chain(cl, monkeypatch, k=3,
                                                reg=reg)
        tpt._commit_round(cl, fin_res, fin_ids)
        adopts0 = tpt._counter("chain_adopts")
        with guard_scope("disallow"):
            _view_stack(cl).device_arrays()
        assert tpt._counter("chain_adopts") == adopts0 + 1
        fin_res = None
        gc.collect()

        assert led.outstanding_leases() == 0, "leaked chain view lease"
        snap = led.snapshot()
        assert snap.get("select_batch.carry", {}).get(
            "live_bytes", 0) == 0, "unfreed chain carry"
        assert snap.get("select_batch.batch_out", {}).get(
            "live_bytes", 0) == 0, "unresolved chain outputs"
        for site, row in sorted(snap.items()):
            assert row["live_bytes"] == base.get(site, {}).get(
                "live_bytes", 0), f"residency grew at {site}"
        assert led.totals()[0] == base_live

    def test_unreleased_lease_is_visible(self, monkeypatch):
        """A dispatch that takes a view lease and never releases it
        must show up as outstanding (and, past the watermark, stuck) —
        the failure mode the gate exists to catch."""
        led = _fresh_global_ledger(monkeypatch)
        cl = tpt._mini_cluster()
        stack = _view_stack(cl)
        stack.device_arrays(lease_token="wedged-token")
        assert led.outstanding_leases() == 1
        monkeypatch.setenv("NOMAD_TPU_HBM_LEASE_WATERMARK_S", "0.001")
        time.sleep(0.01)
        assert any(lease["stuck"] for lease in led.leases())
        from nomad_tpu.scheduler.stack import release_view

        release_view(cl, "wedged-token")
        assert led.outstanding_leases() == 0


class TestReconciliation:
    def test_ledger_covers_allocator_growth(self, monkeypatch):
        """Acceptance: ledger live-bytes accounts for >=90% of
        memory_stats().bytes_in_use growth over the steady window.
        The CPU backend exposes no stats — the assertion arms on
        backends that do (TPU/GPU), and the plumbing (reconcile shape)
        is checked everywhere."""
        led = _fresh_global_ledger(monkeypatch)
        devs0 = device_memory_stats()
        in_use0 = sum(d["bytes_in_use"] for d in devs0) if devs0 else None
        rng = random.Random(3)
        cl = tpt._mini_cluster()
        jobs = [tpt._job(rng, i) for i in range(3)]
        eval_ids = [f"ev-{i}" for i in range(3)]
        for _ in range(2):
            _coord, res = tpt._run_round(cl, jobs, eval_ids=eval_ids)
            tpt._commit_round(cl, res, eval_ids)
        rec = reconcile(led)
        assert rec["ledger_live_bytes"] == led.totals()[0] > 0
        if in_use0 is None or rec["device_bytes_in_use"] is None:
            pytest.skip("backend exposes no memory_stats (CPU)")
        growth = rec["device_bytes_in_use"] - in_use0
        assert rec["ledger_live_bytes"] >= 0.9 * growth


class TestPlannerAgainstMeasurement:
    def test_2x_cluster_prediction_within_15pct(self, monkeypatch):
        """Acceptance: project a 2x cluster from one cluster's measured
        per-row costs, then actually build and upload the 2x cluster —
        prediction within 15% of the measured residency."""
        led_a = _fresh_global_ledger(monkeypatch)
        cl_a = tpt._mini_cluster(n_nodes=48)   # n_cap 64
        _view_stack(cl_a).device_arrays()
        assert led_a.totals()[0] > 0
        plan = plan_capacity(96, 1000, led_a)  # 2x nodes -> n_cap 128
        assert plan["measured"]
        predicted = plan["projected_bytes"]

        led_b = _fresh_global_ledger(monkeypatch)
        cl_b = tpt._mini_cluster(n_nodes=96)
        _view_stack(cl_b).device_arrays()
        gc.collect()
        measured = led_b.totals()[0]
        assert measured > 0
        assert abs(predicted - measured) <= 0.15 * measured, (
            predicted, measured)
        # keep both clusters alive through the assertions (their death
        # would drop the measurements mid-test)
        assert cl_a is not None and cl_b is not None

    def test_100k_projection_shape(self, monkeypatch):
        led = _fresh_global_ledger(monkeypatch)
        cl = tpt._mini_cluster()
        _view_stack(cl).device_arrays()
        plan = plan_capacity(100_000, 1_000_000, led)
        assert plan["projected_n_cap"] == 131072
        assert plan["node_bytes"] > 0
        assert plan["shards_needed"] >= 1
        # the dominant per-node cost is the port bitmap (8 KB/row): the
        # projection must be in that ballpark, not off by orders
        assert plan["per_node_bytes"] > 8192


class TestSiteTaxonomy:
    def test_fused_loop_populates_expected_sites(self, monkeypatch):
        """The residency-site vocabulary README documents — view slots,
        program table classes, in-flight dispatch state — must all be
        booked by one fused round (and nothing else)."""
        led = _fresh_global_ledger(monkeypatch)
        rng = random.Random(11)
        cl = tpt._mini_cluster()
        jobs = [tpt._job(rng, i) for i in range(3)]
        _coord, _res = tpt._run_round(
            cl, jobs, eval_ids=[f"e-{i}" for i in range(3)])
        sites = set(led.snapshot())
        assert {"stack.view_static", "stack.view_hot",
                "stack.view_ports", "program_table.i32",
                "program_table.f32", "program_table.u8",
                "select_batch.batch_out",
                "select_batch.carry"} <= sites
        from tests.test_metrics_names import ALLOWED_SITES

        assert sites <= ALLOWED_SITES


class TestOperatorSurface:
    """GET /v1/operator/hbm + SDK shape (the agent fixture idiom of
    test_agent_http.py, kept here so the whole ISSUE 11 surface tests
    in one file)."""

    @pytest.fixture()
    def agent(self, tmp_path):
        from nomad_tpu.agent import Agent, AgentConfig
        from nomad_tpu.api import NomadClient

        a = Agent(AgentConfig(data_dir=str(tmp_path / "data"),
                              heartbeat_ttl=60.0))
        a.start()
        api = NomadClient(a.http_addr[0], a.http_addr[1])
        yield a, api
        a.shutdown()

    def test_endpoint_shape(self, agent):
        a, api = agent
        out = api.operator_hbm()
        assert set(out) >= {"summary", "sites", "shards",
                            "reconciliation"}
        assert "leases" not in out
        summ = out["summary"]
        for k in ("live_bytes", "buffers", "peak_bytes",
                  "outstanding_leases", "lease_high_water",
                  "lease_watermark_s"):
            assert k in summ
        rec = out["reconciliation"]
        assert "ledger_live_bytes" in rec and "coverage_pct" in rec

    def test_watermarks_param(self, agent):
        a, api = agent
        out = api.operator_hbm(watermarks=True)
        assert isinstance(out["leases"], list)

    def test_plan_param_and_validation(self, agent):
        from nomad_tpu.api import ApiError

        a, api = agent
        out = api.operator_hbm(plan=(2000, 10_000))
        plan = out["plan"]
        assert plan["nodes"] == 2000 and plan["allocs"] == 10_000
        assert {"projected_bytes", "fits", "shards_needed",
                "headroom_bytes"} <= set(plan)
        # malformed plan args are a 400, not a 500
        with pytest.raises(ApiError) as e:
            api.operator_hbm(plan=(0, 5))
        assert "400" in str(e.value) or "plan needs" in str(e.value)

    def test_metrics_carries_hbm_sections(self, agent):
        a, api = agent
        m = api.metrics()
        assert "hbm" in m and "hbm_sites" in m
        assert "outstanding_leases" in m["hbm"]


class TestCliHbm:
    """CLI `operator hbm` (the eval trace / operator timeline exit-1
    convention; the happy path is covered via the agent fixture)."""

    def _run(self, addr, *argv):
        import io
        import sys as _sys

        from nomad_tpu.cli import main

        out, err = io.StringIO(), io.StringIO()
        old = _sys.stdout, _sys.stderr
        _sys.stdout, _sys.stderr = out, err
        try:
            rc = main(["-address", addr, *argv])
        finally:
            _sys.stdout, _sys.stderr = old
        return rc, out.getvalue(), err.getvalue()

    def test_malformed_plan_args_exit_one(self):
        # validated before any connection: no agent needed
        for argv in (("operator", "hbm", "-plan"),
                     ("operator", "hbm", "-plan", "-nodes", "100"),
                     ("operator", "hbm", "-plan", "-nodes", "0",
                      "-allocs", "5"),
                     ("operator", "hbm", "-plan", "-nodes", "10",
                      "-allocs", "-1")):
            rc, out, err = self._run("127.0.0.1:1", *argv)
            assert rc == 1, argv
            assert err.startswith("Error:"), argv
            assert "Traceback" not in err

    def test_unreachable_agent_exits_one(self):
        rc, out, err = self._run("127.0.0.1:1", "operator", "hbm")
        assert rc == 1
        assert err.startswith("Error:")

    def test_happy_path_with_plan(self, tmp_path):
        from nomad_tpu.agent import Agent, AgentConfig

        a = Agent(AgentConfig(data_dir=str(tmp_path / "data"),
                              heartbeat_ttl=60.0))
        a.start()
        try:
            addr = f"{a.http_addr[0]}:{a.http_addr[1]}"
            rc, out, err = self._run(addr, "operator", "hbm",
                                     "-watermarks", "-plan",
                                     "-nodes", "100000",
                                     "-allocs", "1000000")
            assert rc == 0, err
            assert "Live" in out and "Leases" in out
            assert "Plan for 100000 nodes" in out
            rc, out, err = self._run(addr, "operator", "hbm", "-json")
            assert rc == 0 and '"summary"' in out
        finally:
            a.shutdown()
