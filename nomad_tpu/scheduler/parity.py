"""Kernel-vs-reference parity: the same programs on the same frozen state
through the device path (`TPUStack.select`) and through the plain
references — the scalar oracle (`oracle.py`) and, where it covers the
stanza, the compiled core (`native/core.cpp`).

Both references are exact full-scan argmax, like the kernel, so a
disagreement can only come from fp associativity, from ties — or from a
kernel that moved a value inexactly. `chip_smoke.py` runs these loops on
the chip; the benchmark's own reference is `perfbench/reference.py`.
"""
from __future__ import annotations

from typing import Optional, Sequence

#: equal-score nodes are interchangeable under the reference's shuffle:
#: a different node at the same normalized score is agreement
TIE_EPS = 1e-5


def oracle_parity(state, nodes, jobs: Sequence, stack, count: int) -> dict:
    """Run `jobs` through the scalar oracle — full-node-scan Select per
    alloc, sequential, plan threaded from step to step — and through the
    kernel on the identical snapshot, comparing per-step normalized
    scores and node choices (the north star's ≤1 %-deviation half;
    reference normalization rank.go:696-710)."""
    from ..mock import alloc_resources
    from ..structs import Allocation
    from .oracle import OracleContext, select_option

    allocs_by_node = {
        nid: list(d.values()) for nid, d in state._allocs_by_node.items()
    }
    devs = []
    agree = 0
    steps = 0
    total = 0
    for job in jobs:
        ctx = OracleContext(nodes=nodes, allocs_by_node=allocs_by_node)
        tg = job.task_groups[0]
        res = job.combined_task_resources(tg)
        sel = stack.select(job, tg, count)
        for step in range(count):
            opt = select_option(ctx, job, tg)
            k_node = sel.node_ids[step]
            steps += 1
            if opt is None or k_node is None:
                # both-failed = agreement; one-sided placement is a
                # plain disagreement (the kernel's 0.0 unplaced
                # sentinel must not enter the deviation stats)
                agree += opt is None and k_node is None
            else:
                dev = abs(sel.scores[step] - opt.final_score)
                devs.append(dev)
                agree += k_node == opt.node.id or dev <= TIE_EPS
            if opt is None:
                continue
            fake = Allocation(
                id=f"parity-{job.id}-{step}", namespace="default",
                job_id=job.id, job=job, task_group=tg.name,
                node_id=opt.node.id,
                allocated_resources=alloc_resources(
                    cpu=res.cpu, memory_mb=res.memory_mb, disk_mb=res.disk_mb
                ),
                desired_status="run", client_status="pending",
            )
            if any(t.resources.devices for t in tg.tasks):
                # carry real instance IDs so the next step's accounting
                # matches the kernel's in-scan device-column consumption
                from .device import DeviceAllocator, assign_task_devices

                da = DeviceAllocator(opt.node,
                                     ctx.proposed_allocs(opt.node.id))
                offers, _ = assign_task_devices(da, tg)
                if offers:
                    tr = next(iter(fake.allocated_resources.tasks.values()))
                    tr.devices.extend(d for offs in offers.values()
                                      for d in offs)
            ctx.plan_node_alloc.setdefault(opt.node.id, []).append(fake)
        total += 1
    return {
        "score_deviation_pct": round(100.0 * (
            sum(devs) / len(devs) if devs else 0.0), 4),
        "score_deviation_max_pct": round(
            100.0 * (max(devs) if devs else 0.0), 4),
        "node_agreement_pct": round(100.0 * agree / steps, 2),
        "parity_evals": total,
        "parity_placements": steps,
    }


def compiled_parity(stack, jobs: Sequence, count: int) -> Optional[dict]:
    """`jobs` through the kernel and through the compiled scalar select
    loop (`native.compiled_select`) on the stack's cluster. Jobs that ask
    for devices are left out: the compiled loop has no device-instance
    stage. None when the native core did not load."""
    from .. import native

    if not native.available():
        return None
    cl = stack.cluster
    agree = steps = 0
    dev_max = 0.0
    for job in jobs:
        tg = job.task_groups[0]
        if any(t.resources.devices for t in tg.tasks):
            continue
        sel = stack.select(job, tg, count)
        c_sel, c_score = native.compiled_select(stack, job, tg, count)
        for step in range(count):
            k_node = sel.node_ids[step]
            c_node = cl.node_of_row[c_sel[step]] if c_sel[step] >= 0 else None
            steps += 1
            if k_node is None or c_node is None:
                agree += k_node is None and c_node is None
                continue
            dev = abs(sel.scores[step] - float(c_score[step]))
            dev_max = max(dev_max, dev)
            agree += k_node == c_node or dev <= TIE_EPS
    return {
        "compiled_node_agreement_pct":
            round(100.0 * agree / steps, 2) if steps else None,
        "compiled_score_deviation_max_pct": round(100.0 * dev_max, 4),
        "compiled_parity_placements": steps,
    }
