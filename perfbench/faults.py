"""Faults planted under the timed path, for `tests/test_run.py` only: the
rest of a run is driven as it is, and `correct` has to come out false.

A scheduler that serves placements can have two of the contract's faults:
an answer altered where it is produced (`wrong-node`), and half of the
batch left out (`half-left-out`: the later half of every task group's
allocations is never placed). It has no optimizer state to return unchanged
and, on one chip, no exchange to leave out.
"""
from __future__ import annotations


def plant(name: str) -> None:
    if name not in ("wrong-node", "half-left-out"):
        raise ValueError(f"unknown fault {name!r}")
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
