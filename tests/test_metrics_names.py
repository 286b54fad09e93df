"""Prometheus series-name stability (ISSUE 8 satellite).

Dashboards and alert rules key on metric/label NAMES; a rename ships a
silent observability outage. This test drives one representative
control-plane flow (batched fused dispatch, a successful placement, a
constraint-filtered failure, a dimension-exhausted blocked eval) and
snapshots every exposed series name:

- REQUIRED names must all be present — renaming any of them fails here
  DELIBERATELY (update the frozen list in the same PR as the rename).
- every observed name must belong to an ALLOWED family — a brand-new
  family must be added here consciously, not leak in silently.
- label names (and the transfer ledger's site values) are pinned too.
"""
import time

import pytest

from nomad_tpu import mock
# the frozen vocabularies live in analysis/vocab.py (ISSUE 14): one
# source of truth shared by this exposition test, lib/flight.py's
# recorder, and the NLV01 static vocabulary-ratchet lint rule. This
# module only drives the loaded-agent flow and pins the exposition
# against the shared sets.
from nomad_tpu.analysis.vocab import (ALLOWED_LABELS, ALLOWED_PREFIXES,
                                      ALLOWED_SITES, FSM_REQUIRED,
                                      PROM_REQUIRED, RAFT_REQUIRED)

REQUIRED = PROM_REQUIRED


def _wait(cond, timeout=20.0, every=0.05):
    dl = time.time() + timeout
    while time.time() < dl:
        if cond():
            return True
        time.sleep(every)
    return cond()






def _parse(text):
    """-> (names, label_names, site_values) from exposition text."""
    names, labels, sites = set(), set(), set()
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        series = line.split(" ")[0]
        if "{" in series:
            name, rest = series.split("{", 1)
            body = rest.rsplit("}", 1)[0]
            for pair in body.split(","):
                if not pair:
                    continue
                k, _, v = pair.partition("=")
                labels.add(k)
                if k == "site":
                    sites.add(v.strip('"'))
        else:
            name = series
        names.add(name)
    return names, labels, sites


def _strip_histo_suffix(name):
    for suf in ("_sum", "_count"):
        if name.endswith(suf):
            return name[: -len(suf)]
    return name


@pytest.fixture()
def loaded_agent(tmp_path, monkeypatch):
    """Dev agent driven through a BATCHED eval round (the fused
    coordinator dispatch) plus a filtered failure, an exhausted blocked
    eval, and a dc-pinned wave round (multi-lane wave dispatch) — the
    flow that populates every promised family."""
    # batch the worker BEFORE the server (Worker reads the env in init);
    # the pinned hold window makes each parked wave drain as ONE batch,
    # so the dc-pinned round reliably dispatches multi-lane
    monkeypatch.setenv("NOMAD_TPU_EVAL_BATCH", "4")
    monkeypatch.setenv("NOMAD_TPU_DRAIN_WINDOW_MS", "300")
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api import NomadClient
    from nomad_tpu.structs import Constraint

    a = Agent(AgentConfig(data_dir=str(tmp_path / "data"),
                          heartbeat_ttl=60.0))
    a.start()
    api = NomadClient(a.http_addr[0], a.http_addr[1])
    assert _wait(lambda: len(api.nodes()) == 1)

    def job(cpu=50, constraint=None, dc=None):
        j = mock.job()
        t = j.task_groups[0].tasks[0]
        t.driver = "mock_driver"
        t.config = {"run_for": 0.05}
        t.resources.cpu = cpu
        if constraint is not None:
            j.constraints.append(constraint)
        if dc is not None:
            j.datacenters = [dc]
        return j

    # clientless dc2/dc3 nodes: jobs pinned to different dcs have
    # DISJOINT footprints, so the pinned wave below drains into one
    # multi-lane wave dispatch (evals complete at plan apply; the
    # allocs never start, which the metrics flow doesn't need)
    for dc in ("dc2", "dc2", "dc3", "dc3"):
        a.server.state.upsert_node(mock.node(datacenter=dc))

    # park registrations while the broker is disabled, then restore —
    # each wave's pending evals drain as ONE worker batch (fused
    # dispatch). TWO waves: the second wave's dispatch pairs with the
    # first in the pipeline timeline (overlap/bubble histograms) and
    # adopts the first wave's device carry (view.carry_* counters) —
    # both promised families must be populated, not vacuously absent.
    s = a.server
    eval_ids = []
    for wave in range(3):
        s.broker.set_enabled(False)
        if wave == 2:
            # dc-pinned wave: two disjoint conflict groups in one drain
            # → a multi-lane wave dispatch (wave.* series non-vacuous)
            wave_ids = [api.register_job(job(dc=dc))
                        for dc in ("dc2", "dc3", "dc2", "dc3")]
        else:
            wave_ids = [api.register_job(job()) for _ in range(4)]
        if wave == 1:
            wave_ids.append(
                api.register_job(job(cpu=10**7)))  # exhausted → blocked
            wave_ids.append(api.register_job(job(
                constraint=Constraint("${attr.nope}", "x", "="))))  # filtered
        s.broker.set_enabled(True)
        s._restore_evals()
        for eid in wave_ids:
            ev = api.wait_for_eval(eid, timeout=30.0)
            assert ev is not None and ev.status == "complete"
        eval_ids.extend(wave_ids)

    # speculative-dispatch families (ISSUE 15), NON-vacuously: one
    # CERTIFIED and one ROLLED-BACK speculative dispatch, driven
    # deterministically at the coordinator level against a side
    # cluster with the agent server's registry — the exposition source
    # — so nomad_spec_* pins test real launch/certify/rollback flows,
    # not eagerly-created zeros.
    import tests.test_program_table as tpt
    import tests.test_spec as tsp
    from nomad_tpu.scheduler import stack as stack_mod
    from nomad_tpu.server.select_batch import SelectCoordinator

    monkeypatch.setenv("NOMAD_TPU_SPEC_ROLLBACK_MAX", "1.0")
    for conflict in (False, True):
        cl = tsp._dc_cluster()
        _c1, res1 = tpt._run_round(
            cl, [tsp._dc_job("dc1"), tsp._dc_job("dc2")],
            eval_ids=["m1", "m2"])
        coord2 = SelectCoordinator(registry=s.metrics)
        coord2.trace_ids = {0: "m3", 1: "m4"}
        coord2.group_ids = {0: 0, 1: 1}
        coord2.footprints = {0: tsp._dc_mask(cl, "dc1"),
                             1: tsp._dc_mask(cl, "dc2")}
        threads, _res2 = tsp._start_parked(
            cl, [tsp._dc_job("dc1", cpu=250),
                 tsp._dc_job("dc2", cpu=250)], coord2)
        assert coord2.try_spec_launch(cl)
        tpt._commit_round(cl, res1, ["m1", "m2"])
        if conflict:
            dc1_node = next(nid for nid in cl.row_of
                            if cl.nodes[nid].datacenter == "dc1")
            cl.upsert_alloc(tsp._foreign_alloc(dc1_node))
        coord2.run()
        for t in threads:
            t.join(30.0)
        stack_mod.spec_chain_reset(cl)

    # chain-carry adoption families (ISSUE 20), NON-vacuously: a
    # 3-deep certified chain whose next refresh ADOPTS the published
    # HEAD carry (view.chain_adopts/chain_rows,
    # spec.resync_bytes_saved — process registry, like view.carry_*)
    cl3 = tsp._dc_cluster()
    _r3, fin_res, fin_ids = tsp._drive_chain(cl3, monkeypatch, k=3,
                                             reg=s.metrics)
    tpt._commit_round(cl3, fin_res, fin_ids)
    stack_mod.TPUStack(cl3).device_arrays()
    # ...one delta-log ring WRAP mid-chain (certification can no
    # longer prove the interval → spec.chain_unprovable_wrap) whose
    # published carry the next refresh must then REJECT
    # (view.chain_rejects) — same unprovable tail
    monkeypatch.setenv("NOMAD_TPU_DELTA_LOG", "8")
    cl4 = tsp._dc_cluster()
    monkeypatch.delenv("NOMAD_TPU_DELTA_LOG")
    _r4, fin_res4, fin_ids4 = tsp._drive_chain(cl4, monkeypatch, k=1,
                                               reg=s.metrics)
    tpt._commit_round(cl4, fin_res4, fin_ids4)
    for _ in range(12):  # blow past the 8-slot ring
        cl4._log_hot(0)
        cl4.version += 1
    assert stack_mod.spec_chain_certify(cl4) is None
    stack_mod.TPUStack(cl4).device_arrays()

    # mesh-CA denial outcomes (ISSUE 14 + 16), NON-vacuously: one
    # identity rejection (unknown node) and one allocation-binding
    # rejection (verified node identity, but no live alloc of the
    # named service) — the nomad_connect_* pins are real deny flows
    with pytest.raises(PermissionError):
        s.connect_issue("svc-x", "no-such-node", "not-a-secret")
    n = a.client.node
    with pytest.raises(PermissionError):
        s.connect_issue("svc-never-scheduled", n.id, n.secret_id)
    yield a, api
    a.shutdown()


class TestSeriesNameStability:
    def test_every_promised_name_is_exposed(self, loaded_agent):
        a, api = loaded_agent
        names, _, _ = _parse(api.metrics_prometheus())
        missing = REQUIRED - names
        assert not missing, (
            f"promised series missing/renamed: {sorted(missing)} — if this "
            f"is a deliberate rename, update REQUIRED in the same PR")

    def test_no_series_outside_allowed_families(self, loaded_agent):
        a, api = loaded_agent
        names, _, _ = _parse(api.metrics_prometheus())
        stray = sorted(
            n for n in names
            if not any(n.startswith(p)
                       or _strip_histo_suffix(n).startswith(p)
                       for p in ALLOWED_PREFIXES))
        assert not stray, (
            f"series outside the frozen family taxonomy: {stray} — a new "
            f"family must be added to ALLOWED_PREFIXES deliberately")

    def test_label_names_and_site_values_pinned(self, loaded_agent):
        a, api = loaded_agent
        _, labels, sites = _parse(api.metrics_prometheus())
        assert labels <= ALLOWED_LABELS, labels - ALLOWED_LABELS
        assert sites <= ALLOWED_SITES, sites - ALLOWED_SITES
        # lint-side booking prefixes (hbm.track_cluster/lease) are NOT
        # legal label values — a bare prefix leaking into the
        # exposition must keep failing here
        from nomad_tpu.analysis.vocab import BOOKING_PREFIXES
        assert not (ALLOWED_SITES & BOOKING_PREFIXES)
        # the fused-dispatch sites must actually be present (the flow
        # above ran batched coordinator rounds on the device-resident
        # program-table transport)
        assert "select_batch.fetch" in sites
        assert "select_batch.table_insert" in sites
        assert "select_batch.dyn_rows" in sites
        # ...and the residency ledger must have booked the loop's
        # long-lived buffers (view slots, program table, carry)
        assert "stack.view_hot" in sites
        assert "program_table.i32" in sites
        assert "select_batch.carry" in sites

    def test_batched_flow_populated_pipeline(self, loaded_agent):
        """Guard the fixture itself: if the batched path silently stops
        batching, the pipeline/worker families would vanish from the
        exposition and the stability test would be vacuous."""
        a, api = loaded_agent
        snap = a.server.metrics.snapshot()
        assert snap["counters"].get("pipeline.dispatches", 0) >= 1
        assert any(k.startswith("worker.0.batch.")
                   for k in snap["counters"])
        # the dc-pinned wave actually dispatched multi-lane — without
        # this the wave.* pins above would be testing absence
        assert snap["counters"].get("wave.dispatches", 0) >= 1
        assert snap["histograms"]["wave.lanes"]["max"] >= 2
        # the speculative rounds drove one certified AND one
        # rolled-back dispatch — the nomad_spec_* pins are live flows
        assert snap["counters"].get("spec.launches", 0) >= 2
        assert snap["counters"].get("spec.certified", 0) >= 1
        assert snap["counters"].get("spec.rolled_back", 0) >= 1
        assert snap["counters"].get("spec.redispatch_programs", 0) >= 1
        assert snap["counters"].get("spec.wasted_kernel_ms", 0) > 0
        # the chain-adoption rounds drove one ADOPTED refresh, one
        # REJECTED carry, and one ring-wrap — the ISSUE 20 pins are
        # live flows (process registry, like the view.* family)
        from nomad_tpu.lib.metrics import default_registry
        view = default_registry().counters(prefix="view.")
        assert view.get("chain_adopts", 0) >= 1
        assert view.get("chain_rows", 0) >= 1
        assert view.get("chain_rejects", 0) >= 1
        proc_spec = default_registry().counters(prefix="spec.")
        assert proc_spec.get("resync_bytes_saved", 0) > 0
        assert proc_spec.get("chain_unprovable_wrap", 0) >= 1
        # the connect denial series are live deny flows with DISTINCT
        # per-reason counters (ISSUE 16), not eagerly-created zeros
        assert snap["counters"].get("connect.issue_denied", 0) >= 2
        assert snap["counters"].get(
            "connect.issue_denied_identity", 0) >= 1
        assert snap["counters"].get(
            "connect.issue_denied_no_alloc", 0) >= 1

    def test_partition_and_wave_slots_are_counted_once(self, loaded_agent):
        """ISSUE 32's instruments against the ones that stood: a drain
        leaves exactly one `drain.partition_ms` sample (a drain of one
        eval too), a batch one `sched.footprint_ms` sample, and a wave's
        slots (the bucketed [lanes, lane length] axis) hold at least
        its programs."""
        a, _api = loaded_agent

        def counted_once():
            # the agent is live and a snapshot is not one instant: a
            # drain or a batch under way reads one apart, and is over
            snap = a.server.metrics.snapshot()
            h, c = snap["histograms"], snap["counters"]
            return (h["drain.partition_ms"]["count"] == c["drain.drains"]
                    == h["drain.groups"]["count"] >= 3
                    and h["sched.footprint_ms"]["count"]
                    == c["worker.0.batch.batches"] >= 3)

        assert _wait(counted_once)
        c = a.server.metrics.snapshot()["counters"]
        assert c["wave.slots"] >= c["wave.programs"] >= 2
        # lanes and lane length are bucketed to powers of two, from 2 up
        assert c["wave.slots"] % 4 == 0

    def test_footprint_lookups_are_counted_and_hit(self, loaded_agent):
        """ISSUE 33: a drain's lookups of the static footprint masks
        reach the registry (once a drain, from plain integers), and the
        fixture's jobs, which share their datacenters and constraints
        by the handful, are answered from the cache."""
        a, _api = loaded_agent

        def counted():
            c = a.server.metrics.snapshot()["counters"]
            return (c["drain.footprint_estimates"]
                    >= c["drain.footprint_hits"] >= 2)

        assert _wait(counted)

    def test_trace_and_slo_series_are_live(self, loaded_agent):
        """The ninth-layer families (ISSUE 17) must be fed by real
        flows, not just pre-created at tracker init: every HTTP submit
        above minted an ingress span, and each placed alloc's
        pending→running flip recorded an SLO observation."""
        from nomad_tpu.lib.tracectx import SLO_BANDS, default_spans

        a, api = loaded_agent
        # ingress spans were recorded for the submits the fixture drove
        assert default_spans().counts().get("http.submit", 0) >= 3
        # eval spans were bound at broker enqueue and emitted at ack
        assert default_spans().counts().get("eval", 0) >= 1
        # alloc start-latency observations land asynchronously as
        # client allocs flip to running
        assert _wait(lambda: a.server.metrics.snapshot()["counters"]
                     .get("slo.observations", 0) >= 1)
        names, _, _ = _parse(api.metrics_prometheus())
        assert "nomad_trace_spans" in names
        assert "nomad_slo_observations" in names
        # per-band attainment/budget gauges exist from first exposition
        # (dashboards need the full band matrix, not lazily-appearing
        # rows)
        for band in SLO_BANDS:
            assert f"nomad_slo_attainment_{band}" in names
            assert f"nomad_slo_budget_remaining_{band}" in names




    def test_event_stream_series_are_live(self, loaded_agent):
        """The tenth-layer families (ISSUE 18) are fed by the real FSM
        apply flow, not eagerly-created zeros: every node/job/eval/
        alloc mutation above published a typed event, and the whole
        per-topic family is present from first exposition."""
        a, api = loaded_agent
        snap = a.server.metrics.snapshot()
        assert snap["counters"].get("events.published", 0) >= 1
        assert snap["counters"].get("events.topic.job", 0) >= 1
        assert snap["counters"].get("events.topic.eval", 0) >= 1
        assert snap["counters"].get("events.topic.alloc", 0) >= 1
        assert a.server.metrics.gauge("events.last_index").value >= 1
        names, _, _ = _parse(api.metrics_prometheus())
        for t in ("job", "eval", "alloc", "deployment", "node",
                  "plan"):
            assert f"nomad_events_topic_{t}" in names
        assert "nomad_events_published" in names
        assert "nomad_events_subscribers" in names
        assert "nomad_events_subscriber_evictions" in names
        assert "nomad_events_oldest_index" in names
        assert "nomad_events_last_index" in names


class TestControlPlaneSeries:
    """nomad_raft_* pinning + the flight-event type vocabulary,
    NON-vacuously: a 1-node ClusterServer drives a real leader
    transition (election → leadership.gained) and a delivery-limited
    nack drives broker.eval_failed — the ISSUE 13 fixture contract."""

    def test_raft_series_and_flight_vocabulary(self):
        from nomad_tpu.lib.flight import FLIGHT_TYPES, default_flight
        from nomad_tpu.server.broker import EvalBroker
        from nomad_tpu.server.cluster import (ClusterServer,
                                              ClusterServerConfig)

        idx0 = default_flight().last_index()
        cs = ClusterServer(ClusterServerConfig(
            node_id="mx0", heartbeat_ttl=60.0, gc_interval=3600.0))
        cs.start()
        try:
            assert _wait(cs.is_leader, timeout=30.0)
            cs.call("node_register", mock.node())  # commit traffic
            # a malformed entry exercises apply_resilient's skip path
            # (ISSUE 16): committed on every replica, dropped by the
            # FSM identically — fsm.apply_skipped must tick
            cs.raft.apply({"op": "bogus_op", "args": []})
            names, labels, _ = _parse(cs.raft.metrics.prometheus())
            missing = (RAFT_REQUIRED | FSM_REQUIRED) - names
            assert not missing, (
                f"promised raft/fsm series missing/renamed: "
                f"{sorted(missing)}")
            stray = sorted(n for n in names
                           if not _strip_histo_suffix(n)
                           .startswith(("nomad_raft_", "nomad_fsm_")))
            assert not stray, stray
            assert labels <= ALLOWED_LABELS
            # the election IS a leadership transition — non-vacuous
            assert cs.raft.metrics.counter(
                "raft.leadership_gained").value >= 1
            assert cs.raft.metrics.histogram("raft.commit_ms").count >= 1
            # FSM outcome counters are live flows: node_register was
            # applied, the bogus op was skipped (never fatal)
            assert cs.raft.metrics.counter("fsm.applied").value >= 1
            assert _wait(lambda: cs.raft.metrics.counter(
                "fsm.apply_skipped").value >= 1, timeout=10.0)
        finally:
            cs.shutdown()
        # nacked-to-exhaustion eval → broker.eval_failed flight event
        b = EvalBroker(nack_timeout=0, delivery_limit=1)
        b.set_enabled(True)
        ev = mock.eval_()
        b.enqueue(ev)
        got, tok = b.dequeue([ev.type], timeout=1.0)
        b.nack(got.id, tok)
        b.shutdown()
        _, evs = default_flight().records_after(idx0)
        types = {e["type"] for e in evs}
        assert types <= FLIGHT_TYPES, types - FLIGHT_TYPES
        assert {"leadership.gained", "raft.term",
                "broker.eval_failed"} <= types
        # lifetime counts carry the same closed vocabulary
        assert set(default_flight().counts()) <= FLIGHT_TYPES
