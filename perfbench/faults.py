"""Faults planted under the timed path, for the benchmark's tests only: the
rest of a run is driven as it is, and `correct` has to come out false.

A scheduler that serves placements can have two of the contract's faults:
an answer altered where it is produced (`wrong-node`), and half of the
batch left out (`half-left-out`: the later half of every task group's
allocations is never placed). It has no optimizer state to return unchanged
and, on one chip, no exchange to leave out. Its system path can leave a
node out (`system-node-left-out`: the first node each system eval places on
gets nothing) and alter an answer (`preempt-too-high`: a live allocation of
the job's own priority is added to a victim set).
"""
from __future__ import annotations

FAULTS = ("wrong-node", "half-left-out", "system-node-left-out",
          "preempt-too-high")


def plant(name: str) -> None:
    if name not in FAULTS:
        raise ValueError(f"unknown fault {name!r}")
    if name == "system-node-left-out":
        _plant_node_left_out()
    elif name == "preempt-too-high":
        _plant_preempt_too_high()
    else:
        _plant_select(name)


def _plant_select(name: str) -> None:
    from nomad_tpu.scheduler import stack

    orig = stack.TPUStack.select

    def select(self, *a, **kw):
        res = orig(self, *a, **kw)
        ids = list(res.node_ids)
        if name == "half-left-out":
            res.node_ids = ids[:len(ids) // 2] + [None] * (len(ids)
                                                           - len(ids) // 2)
        elif ids and ids[0] is not None:
            # the first allocation goes to a node the kernel did not choose
            res.node_ids = [next(n for n in self.cluster.row_of
                                 if n not in ids)] + ids[1:]
        return res

    stack.TPUStack.select = select


def _plant_node_left_out() -> None:
    from nomad_tpu.scheduler.system import SystemScheduler

    orig = SystemScheduler._compute_placements

    def compute_placements(self, place):
        err = orig(self, place)
        # the node of the plan's first placement, with what it evicted
        node_id = next(iter(self.plan.node_allocation), None)
        if node_id is not None:
            del self.plan.node_allocation[node_id]
            self.plan.node_preemptions.pop(node_id, None)
        return err

    SystemScheduler._compute_placements = compute_placements


def _plant_preempt_too_high() -> None:
    from nomad_tpu.scheduler import preemption
    from nomad_tpu.scheduler.util import proposed_allocs

    orig = preemption.preempt_on_node

    def preempt_on_node(state, job, tg, node_id, plan):
        victims = orig(state, job, tg, node_id, plan)
        if victims:
            taken = {v.id for v in victims}
            peer = next((a for a in proposed_allocs(state, plan, node_id)
                         if a.job is not None and a.id not in taken
                         and a.job.priority == job.priority
                         and a.job_id != job.id), None)
            if peer is not None:
                victims = victims + [peer]
        return victims

    preemption.preempt_on_node = preempt_on_node
