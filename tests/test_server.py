"""Control-plane end-to-end tests (reference test strategy SURVEY §4.3:
in-process server, real broker/planner/workers, mock fixtures)."""
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.worker import EvalContext
from nomad_tpu.structs import Evaluation, Plan
from nomad_tpu.structs.node import NODE_STATUS_DOWN


@pytest.fixture()
def server():
    s = Server(ServerConfig(num_schedulers=1, heartbeat_ttl=60.0))
    s.start()
    yield s
    s.shutdown()


def _ready_cluster(server, n=3):
    nodes = []
    for _ in range(n):
        node = mock.node()
        server.node_register(node)
        nodes.append(node)
    return nodes


def test_job_register_places_allocs(server):
    _ready_cluster(server, 3)
    job = mock.job()
    job.task_groups[0].count = 4
    ev = server.job_register(job)
    done = server.wait_for_eval(ev.id)
    assert done is not None and done.status == "complete", (
        done.status_description if done else "eval never finished"
    )
    allocs = server.state.allocs_by_job("default", job.id)
    assert len(allocs) == 4
    assert all(a.node_id for a in allocs)


def test_exhausted_capacity_blocks_then_unblocks(server):
    # One small node: job wants more memory than available → partial placement
    node = mock.node()
    server.node_register(node)
    job = mock.job()
    job.task_groups[0].count = 2
    job.task_groups[0].tasks[0].resources.memory_mb = 6000  # fits once
    ev = server.job_register(job)
    done = server.wait_for_eval(ev.id)
    assert done is not None and done.status == "complete"
    allocs = server.state.allocs_by_job("default", job.id)
    assert len(allocs) == 1
    # A blocked eval exists for the leftover alloc
    assert server.blocked.blocked_count() == 1

    # New capacity arrives → blocked eval unblocks → remaining alloc placed
    server.node_register(mock.node())
    deadline = time.time() + 10
    while time.time() < deadline:
        allocs = [
            a for a in server.state.allocs_by_job("default", job.id)
            if not a.terminal_status()
        ]
        if len(allocs) == 2:
            break
        time.sleep(0.05)
    assert len(allocs) == 2
    assert server.blocked.blocked_count() == 0


def test_node_down_reschedules(server):
    nodes = _ready_cluster(server, 2)
    job = mock.job()
    job.task_groups[0].count = 2
    # Disable reschedule delay so replacements are immediate
    job.task_groups[0].reschedule_policy.delay_s = 0
    job.task_groups[0].reschedule_policy.unlimited = True
    ev = server.job_register(job)
    assert server.wait_for_eval(ev.id).status == "complete"
    allocs = server.wait_for_allocs("default", job.id, 2)
    # Mark the allocs running so the reconciler sees healthy state
    for a in allocs:
        up = type(a)(**{**a.__dict__})
        up.client_status = "running"
        server.state.update_alloc_from_client(up)

    victim = allocs[0].node_id
    server.node_update_status(victim, NODE_STATUS_DOWN, "test")

    deadline = time.time() + 10
    while time.time() < deadline:
        live = [
            a for a in server.state.allocs_by_job("default", job.id)
            if not a.terminal_status() and a.client_status != "lost"
            and a.node_id != victim
        ]
        if len(live) >= 2:
            break
        time.sleep(0.05)
    assert len(live) >= 2, "lost allocs were not replaced"


def test_job_deregister_stops_allocs(server):
    _ready_cluster(server, 2)
    job = mock.job()
    job.task_groups[0].count = 2
    ev = server.job_register(job)
    assert server.wait_for_eval(ev.id).status == "complete"
    server.wait_for_allocs("default", job.id, 2)

    ev2 = server.job_deregister("default", job.id)
    assert server.wait_for_eval(ev2.id).status == "complete"
    deadline = time.time() + 5
    while time.time() < deadline:
        live = [
            a for a in server.state.allocs_by_job("default", job.id)
            if a.desired_status == "run"
        ]
        if not live:
            break
        time.sleep(0.05)
    assert not live


def test_system_job_runs_on_new_nodes(server):
    _ready_cluster(server, 2)
    job = mock.system_job()
    ev = server.job_register(job)
    assert server.wait_for_eval(ev.id).status == "complete"
    allocs = server.wait_for_allocs("default", job.id, 2)
    assert len(allocs) == 2

    # A third node joins → system job extends to it automatically
    server.node_register(mock.node())
    allocs = server.wait_for_allocs("default", job.id, 3)
    assert len(allocs) == 3
    assert len({a.node_id for a in allocs}) == 3


def test_heartbeat_expiry_marks_down():
    s = Server(ServerConfig(num_schedulers=1, heartbeat_ttl=0.3))
    s.start()
    try:
        node = mock.node()
        s.node_register(node)
        assert s.state.node_by_id(node.id).status == "ready"
        time.sleep(0.8)
        assert s.state.node_by_id(node.id).status == NODE_STATUS_DOWN
        # Heartbeat after re-registration revives it
        node2 = mock.node()
        s.node_register(node2)
        assert s.node_heartbeat(node2.id)["ok"]
    finally:
        s.shutdown()


def test_broker_serializes_per_job(server):
    """Two evals for one job: the second stays pending until the first acks."""
    _ready_cluster(server, 2)
    job = mock.job()
    job.task_groups[0].count = 1
    ev1 = server.job_register(job)
    ev2 = server.job_register(job)
    d1 = server.wait_for_eval(ev1.id)
    d2 = server.wait_for_eval(ev2.id)
    assert d1 is not None and d1.status == "complete"
    assert d2 is not None and d2.status == "complete"


@pytest.mark.parametrize("where", ["queued", "in_flight", "after_shutdown"])
def test_shutdown_answers_a_plan_that_is_never_applied(where):
    """A worker's `_submit_plan` waits up to 30 s for its plan's future:
    when the applier stops, every future still queued, dequeued but not
    committed, or enqueued afterwards is failed at once (no scheduler
    thread sleeps out the time-out after `Server.shutdown()`)."""
    s = Server(ServerConfig(num_schedulers=0, heartbeat_ttl=60.0))
    s.start()
    stopper = threading.Thread(target=s.shutdown)
    errs = []

    def submit():  # what a scheduler thread does with its plan
        ctx = EvalContext(s, Evaluation(id="e-late"), "tok", None)
        try:
            ctx._submit_plan(Plan(eval_id="e-late"))
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    # the commit-point mutex held: the applier dequeues the first plan
    # and can apply neither it nor the one queued behind it
    with s.planner._apply_lock:
        futs = [s.plan_queue.enqueue(Plan(eval_id=f"e{i}")) for i in (0, 1)]
        dl = time.monotonic() + 5.0
        while not s.plan_queue._in_flight and time.monotonic() < dl:
            time.sleep(0.01)
        assert s.plan_queue._in_flight == futs[:1]
        t0 = time.monotonic()
        stopper.start()  # joins the blocked applier for 2 s
        if where == "after_shutdown":
            while not s.plan_queue._shutdown:
                time.sleep(0.001)
            t = threading.Thread(target=submit)
            t.start()
            t.join(5.0)
            assert not t.is_alive()
        else:
            with pytest.raises(RuntimeError, match="plan queue shutdown"):
                futs[where == "queued"].wait(5.0)
        assert time.monotonic() - t0 < 1.0
    stopper.join(10.0)
    assert not stopper.is_alive()
    if where == "after_shutdown":
        assert "plan queue shutdown" in str(errs[0])
    # the plan that was in flight is not committed behind its worker's back
    assert int(s.planner.stats["applied"]) == 0
