"""Scheduling math — the parity anchors.

Behavioral reference: `nomad/structs/funcs.go` — `AllocsFit` :103,
`computeFreePercentage` :150, `ScoreFitBinPack` :175 (Google BestFit v3),
`ScoreFitSpread` :202 (worst fit), `FilterTerminalAllocs` :62.

These scalar forms are the oracle; `nomad_tpu/kernels/placement.py` holds the
vectorized versions and is golden-tested against these.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

from .alloc import Allocation
from .node import Node
from .resources import ComparableResources

BINPACK_MAX_FIT_SCORE = 18.0  # reference scheduler/rank.go:13


def filter_terminal_allocs(
    allocs: List[Allocation],
) -> Tuple[List[Allocation], Dict[str, Allocation]]:
    """Remove server-terminal allocs; index client-terminal ones by name
    keeping the highest create-index (reference funcs.go:62)."""
    terminal: Dict[str, Allocation] = {}
    live: List[Allocation] = []
    for alloc in allocs:
        if alloc.terminal_status():
            prev = terminal.get(alloc.name)
            if prev is None or prev.create_index < alloc.create_index:
                terminal[alloc.name] = alloc
            continue
        live.append(alloc)
    return live, terminal


def allocs_fit(
    node: Node,
    allocs: List[Allocation],
    net_idx=None,
    check_devices: bool = False,
) -> Tuple[bool, str, ComparableResources]:
    """Check whether `allocs` fit on `node` (reference funcs.go:103).

    Returns (fit, exhausted-dimension, total-utilization). Terminal allocs are
    ignored; fit is a superset check of (node resources − reserved) over the
    summed utilization, then port-collision / bandwidth, then devices.
    """
    used = ComparableResources()
    for alloc in allocs:
        if alloc.terminal_status():
            continue
        used.add(alloc.comparable_resources())

    available = node.comparable_resources()
    available.subtract(node.comparable_reserved_resources())
    ok, dim = available.superset(used)
    if not ok:
        return False, dim, used

    if net_idx is None:
        from .network import NetworkIndex

        net_idx = NetworkIndex()
        if net_idx.set_node(node) or net_idx.add_allocs(allocs):
            return False, "reserved port collision", used

    if net_idx.overcommitted():
        return False, "bandwidth exceeded", used

    if check_devices:
        from .devices import DeviceAccounter

        acct = DeviceAccounter(node)
        if acct.add_allocs(allocs):
            return False, "device oversubscribed", used

    return True, "", used


def compute_free_percentage(
    node: Node, util: ComparableResources
) -> Tuple[float, float]:
    """Free CPU/RAM fraction after `util` is placed (reference funcs.go:150)."""
    res = node.comparable_resources()
    reserved = node.comparable_reserved_resources()
    node_cpu = res.cpu - reserved.cpu
    node_mem = res.memory_mb - reserved.memory_mb
    free_cpu = 1.0 - (util.cpu / node_cpu)
    free_ram = 1.0 - (util.memory_mb / node_mem)
    return free_cpu, free_ram


def score_fit_binpack(node: Node, util: ComparableResources) -> float:
    """Google BestFit-v3 bin-pack score in [0, 18] (reference funcs.go:175):
    score = 20 − (10^freeCpu + 10^freeRam), clamped."""
    free_cpu, free_ram = compute_free_percentage(node, util)
    total = math.pow(10, free_cpu) + math.pow(10, free_ram)
    score = 20.0 - total
    if score > 18.0:
        score = 18.0
    elif score < 0.0:
        score = 0.0
    return score


def score_fit_spread(node: Node, util: ComparableResources) -> float:
    """Worst-fit spread score in [0, 18] (reference funcs.go:202):
    score = (10^freeCpu + 10^freeRam) − 2, clamped."""
    free_cpu, free_ram = compute_free_percentage(node, util)
    total = math.pow(10, free_cpu) + math.pow(10, free_ram)
    score = total - 2.0
    if score > 18.0:
        score = 18.0
    elif score < 0.0:
        score = 0.0
    return score


def score_fit(algorithm: str, node: Node, util: ComparableResources) -> float:
    """Dispatch on SchedulerConfiguration.EffectiveSchedulerAlgorithm
    (reference scheduler/rank.go:160-166, structs.go SchedulerAlgorithm)."""
    if algorithm == "spread":
        return score_fit_spread(node, util)
    return score_fit_binpack(node, util)


# Logistic preemption score (reference rank.go:775-782). Single source of
# truth — the host Preemptor and the device kernel must stay in exact parity.
PREEMPTION_SCORE_RATE = 0.0048
PREEMPTION_SCORE_ORIGIN = 2048.0

# Score penalty applied per already-preempted alloc of the same job/tg beyond
# its migrate max_parallel (reference preemption.go:13); the host Preemptor
# and the system scheduler's device greedy share it.
MAX_PARALLEL_PENALTY = 50.0


def preemption_score(net_prio: float) -> float:
    """Score in [0, 1]; inflection at net priority 2048 (rank.go:773)."""
    return 1.0 / (1.0 + math.exp(PREEMPTION_SCORE_RATE *
                                 (net_prio - PREEMPTION_SCORE_ORIGIN)))
