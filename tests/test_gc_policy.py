"""The collector's settings in a serving process (`lib/backend.py
GcPolicy`): installed and undone with the server agent, counted across
agents; no automatic generation-2 collection, one full sweep on the
server's GC ticker; and the property that makes that safe for memory —
the store's records are no cyclic garbage."""
import copy
import gc
import time
import weakref

import pytest

from nomad_tpu import mock
from nomad_tpu.agent import Agent, AgentConfig
from nomad_tpu.lib import backend
from nomad_tpu.lib.backend import GcPolicy
from nomad_tpu.lib.metrics import MetricsRegistry, default_registry

POLICY = (backend.GC_THRESHOLD0, backend.GC_THRESHOLD1, backend._GC_NEVER)


def _server_agent(gc_interval=None):
    a = Agent(AgentConfig(server=True, client=False, http_port=0,
                          data_dir=None, num_schedulers=1,
                          heartbeat_ttl=3600.0))
    if gc_interval is not None:
        a.server.config.gc_interval = gc_interval
    return a


def _wait(cond, timeout=10.0):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(0.02)
    return cond()


@pytest.fixture()
def thresholds():
    """What stood before the test stands after it, whatever it did."""
    before = gc.get_threshold()
    assert GcPolicy._installed == 0, "a policy leaked from an earlier test"
    yield before
    leaked = GcPolicy._installed
    gc.set_threshold(*before)
    GcPolicy._installed, GcPolicy._before = 0, None
    assert leaked == 0


def test_constants_stay_in_the_range_that_was_measured():
    assert 20_000 <= backend.GC_THRESHOLD0 <= 100_000
    assert 1 <= backend.GC_THRESHOLD1 <= 10
    assert backend.GC_SWEEP_TICKS >= 1


def test_server_agent_installs_and_shutdown_restores(thresholds):
    a = _server_agent()
    assert gc.get_threshold() == thresholds  # nothing before start
    a.start()
    try:
        assert gc.get_threshold() == POLICY
        assert a.server.gc_policy is a._gc_policy
    finally:
        a.shutdown()
    assert gc.get_threshold() == thresholds
    a.shutdown()  # a second shutdown takes nothing more away
    assert GcPolicy._installed == 0


@pytest.mark.parametrize("first_out", [0, 1])
def test_two_server_agents_in_either_order(thresholds, first_out):
    gc.set_threshold(701, 11, 12)  # "before" is whatever stood, not 700
    agents = [_server_agent(), _server_agent()]
    try:
        for a in agents:
            a.start()
            assert gc.get_threshold() == POLICY
        agents[first_out].shutdown()
        assert gc.get_threshold() == POLICY  # one server still serves
        agents[1 - first_out].shutdown()
        assert gc.get_threshold() == (701, 11, 12)
    finally:
        for a in agents:
            a.shutdown()


def test_client_only_agent_leaves_the_collector_alone(thresholds):
    from nomad_tpu.server.cluster import ClusterServer, ClusterServerConfig

    cs = ClusterServer(ClusterServerConfig(node_id="s1", num_schedulers=1))
    cs.start()
    try:
        assert gc.get_threshold() == thresholds  # a bare server: no policy
        assert cs.server.gc_policy is None
        a = Agent(AgentConfig(server=False, client=True,
                              server_addrs=[cs.addr]))
        a.start()
        try:
            assert gc.get_threshold() == thresholds
            assert a._gc_policy is None and a._gc_watch is None
        finally:
            a.shutdown()
        assert gc.get_threshold() == thresholds
    finally:
        cs.shutdown()


def test_policy_counts_holders_not_calls(thresholds):
    reg = MetricsRegistry()
    p, q = GcPolicy(reg), GcPolicy(reg)
    p.install()
    p.install()
    q.install()
    assert GcPolicy._installed == 2 and gc.get_threshold() == POLICY
    p.remove()
    p.remove()
    assert GcPolicy._installed == 1 and gc.get_threshold() == POLICY
    q.remove()
    assert gc.get_threshold() == thresholds


def test_tick_sweeps_every_kth():
    reg = MetricsRegistry()
    p = GcPolicy(reg)
    k = backend.GC_SWEEP_TICKS
    for _ in range(k - 1):
        p.tick()
    assert reg.counter("runtime.gc_sweeps").value == 0
    for _ in range(k + 1):
        p.tick()
    assert reg.counter("runtime.gc_sweeps").value == 2
    assert reg.histogram("runtime.gc_sweep_ms").count == 2


class _Node:
    def __init__(self):
        self.other = None


def _promoted_cycle():
    """A two-object cycle, unreachable, in generation 2."""
    a, b = _Node(), _Node()
    a.other, b.other = b, a
    ref = weakref.ref(a)
    gc.collect(1)  # generation 0 -> 1 ... (referenced: it survives)
    gc.collect(1)  # ... -> 2
    del a, b
    return ref


def test_a_cycle_in_generation_2_waits_for_the_tickers_sweep(thresholds):
    proc = default_registry()
    a = _server_agent(gc_interval=0.05)
    a.start()
    try:
        ref = _promoted_cycle()
        c0 = {n: proc.counter(n).value for n in
              ("runtime.gc_sweeps", "runtime.gc_full",
               "runtime.gc_sweep_collected")}
        h = proc.histogram("runtime.gc_sweep_ms")
        n0, sum0 = h.count, h.sum
        # young collections do not reach it, and nothing else comes
        gc.collect(1)
        junk = [[i] for i in range(3 * backend.GC_THRESHOLD0)]
        assert ref() is not None
        del junk
        assert _wait(lambda: ref() is None, timeout=20.0), \
            "no sweep within 20 s at a GC period of 0.05 s"
        assert _wait(lambda: proc.counter("runtime.gc_sweeps").value
                     > c0["runtime.gc_sweeps"])
        assert _wait(lambda: proc.counter("runtime.gc_full").value
                     > c0["runtime.gc_full"])
        assert proc.counter("runtime.gc_sweep_collected").value \
            >= c0["runtime.gc_sweep_collected"] + 2
        assert h.count > n0 and h.sum > sum0
    finally:
        a.shutdown()


def test_no_automatic_full_collection_over_200000_kept_containers(
        thresholds):
    seen = []

    def on_gc(phase, info):
        if phase == "start":
            seen.append(info["generation"])

    a = _server_agent()
    a.start()
    gc.callbacks.append(on_gc)
    try:
        kept = [[i] for i in range(200_000)]
    finally:
        gc.callbacks.remove(on_gc)
        a.shutdown()
    assert len(kept) == 200_000
    assert 2 not in seen, seen
    # the young generation was collected, at the policy's pace
    assert 1 <= len(seen) <= 200_000 // backend.GC_THRESHOLD0 + 2, seen


def test_the_stores_records_are_no_cyclic_garbage():
    """What makes "no automatic full collection" safe for memory: an
    `Allocation`, `Job`, `Evaluation` or `AllocMetric` that leaves the
    store is freed by its reference count, not by a collection."""
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.structs import AllocMetric, Allocation, Evaluation, Job

    s = Server(ServerConfig(num_schedulers=0))
    nodes = [mock.node() for _ in range(8)]
    for n in nodes:
        s.state.upsert_node(n)
    mine = set()  # ids of this test's records: a server that an earlier
    # test shut down is cyclic garbage as a whole, its store with it, and
    # may come free only now
    mark = 31_031  # ... and its `AllocMetric`s
    gc.collect()
    was_enabled = gc.isenabled()
    gc.disable()  # so that nothing cyclic is freed unseen on the way
    gc.set_debug(gc.DEBUG_SAVEALL)
    try:
        for r in range(300):
            job = mock.job()
            s.state.upsert_job(job)
            ev = mock.eval_(job_id=job.id, namespace=job.namespace,
                            status="complete")
            s.state.upsert_eval(ev)
            mine.update((job.id, ev.id))
            for i in range(3):
                al = mock.alloc(job=job, job_id=job.id, eval_id=ev.id,
                                node_id=nodes[(r + i) % 8].id,
                                namespace=job.namespace)
                al.metrics = AllocMetric(nodes_evaluated=mark)
                s.state.upsert_alloc(al)
                mine.add(al.id)
                # a client's update supersedes the record in place
                done = copy.copy(al)
                done.client_status = "complete"
                done.desired_status = "stop"
                s.state.upsert_alloc(done)
            del al, done
            # deregistration, then the core scheduler's eval and job GC
            dereg = s.job_deregister(job.namespace, job.id)
            dereg.status = "complete"
            s.state.upsert_eval(dereg)
            mine.add(dereg.id)
            del job, ev, dereg
            if r % 10 == 9:
                s.run_gc()
        assert not s.state.jobs() and not s.state.evals()
        gc.collect()
        trapped = [type(o).__name__ for o in gc.garbage
                   if (isinstance(o, (Allocation, Job, Evaluation))
                       and o.id in mine)
                   or (isinstance(o, AllocMetric)
                       and o.nodes_evaluated == mark)]
        assert not trapped, (len(trapped), sorted(set(trapped)))
    finally:
        gc.set_debug(0)
        gc.garbage.clear()
        if was_enabled:
            gc.enable()
