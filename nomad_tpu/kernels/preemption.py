"""Preemption candidate-ranking kernel.

Reference behavior being re-expressed: when normal bin-packing finds no node
with room, `rank.go:228-448` retries each candidate with eviction enabled —
a scalar per-node loop calling the greedy Preemptor. Here the *search over
nodes* is one dense kernel: per node, sort that node's preemptible allocs by
job priority ascending, prefix-scan the released resources, and find the
minimal victim prefix whose release admits the ask. Scoring mirrors the
reference's combination of bin-pack fit (after eviction, `funcs.go:175`) and
the logistic net-priority preemption score (`rank.go:747-783`), mean-combined
as ScoreNormalization does.

The winning node's exact victim set is then refined host-side by the faithful
greedy `scheduler/preemption.py` Preemptor (distance scoring + superset
filter) — only the O(N·A) node scan belongs on the VPU.

Shapes: N nodes × A candidate-alloc slots (bucketed). Ineligible slots
(padding, priority delta < 10, same job) carry priority +INF so the sort
pushes them past every real candidate and the cumulative-eligibility mask
cuts any prefix that would include them.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..structs.funcs import PREEMPTION_SCORE_ORIGIN, PREEMPTION_SCORE_RATE
from .placement import (
    ClusterArrays,
    TGParams,
    _EXACT,
    _dp_feasible,
    _lut_gather,
    _onehot_tokens,
    _scatter_counts,
    _select_tokens,
    fit_scores,
)

NEG_INF = -1e30
INF_PRIO = 1e9


class PreemptionCandidates(NamedTuple):
    """Per-node candidate-alloc table (host-built, device-resident)."""

    prio: jax.Array    # f32[N, A] — victim job priority; +INF = ineligible/pad
    usage: jax.Array   # f32[N, A, R] — per-alloc resource rows


class PreemptionResult(NamedTuple):
    best_row: jax.Array     # i32 — chosen node row, −1 if none feasible
    best_k: jax.Array       # i32 — victims in the minimal prefix on that node
    best_score: jax.Array   # f32 — combined normalized score
    order: jax.Array        # i32[N, A] — priority-ascending sort permutation
    feasible: jax.Array     # bool[N] — admits the ask after some eviction
    scores: jax.Array       # f32[N] — per-node combined score (−inf infeasible)


def preempt_rank(cluster: ClusterArrays, p: TGParams,
                 cand: PreemptionCandidates) -> PreemptionResult:
    cap = cluster.capacity
    n, a = cand.prio.shape

    # Constraint feasibility mirrors the placement kernel's — including
    # distinct_hosts and the distinct_property node mask: the reference
    # keeps DistinctHosts/DistinctPropertyIterator ahead of the
    # evict-enabled BinPackIterator (stack.go:321-411), so a preemption
    # retry must never select a node the distinct checks would have
    # rejected. (The literal-LTarget dp *placement clamp* is host-side:
    # find_preemption_placement bails when params.n_place is clamped to 0.)
    feas_c = _lut_gather(p.lut, p.key_idx, cluster.attrs)
    feas = cluster.node_ok & p.extra_mask & jnp.all(feas_c, axis=1)

    if p.jc_idx.shape[0]:
        job_cnt0 = _scatter_counts(p.jc_idx, p.jc_val, n)
        feas = feas & ~(p.distinct_hosts & (job_cnt0 > 0))

    if p.dp_key_idx.shape[0]:
        d_v = p.dp_counts0.shape[1]
        dtok = _select_tokens(cluster.attrs, p.dp_key_idx, d_v)   # [N, P]
        dtok_oh = _onehot_tokens(dtok, d_v)                       # [N, P, V]
        feas = feas & _dp_feasible(dtok, dtok_oh, p.dp_counts0, p)

    used = cluster.used
    if p.delta_idx.shape[0]:
        # comparison-einsum instead of scatter (TPU scatters serialize;
        # −1 pads match no row — same idiom as the placement kernel)
        eq = (p.delta_idx[:, None] == jnp.arange(n)[None, :]
              ).astype(jnp.float32)
        used = used - jnp.einsum("dn,dr->nr", eq, p.delta_res,
                                 precision=_EXACT)

    # Sort each node's candidates by priority ascending (victims cheapest
    # first — reference filterAndGroupPreemptibleAllocs order).
    order = jnp.argsort(cand.prio, axis=1)                      # i32[N, A]
    prio_s = jnp.take_along_axis(cand.prio, order, axis=1)      # [N, A]
    usage_s = jnp.take_along_axis(
        cand.usage, order[:, :, None], axis=1
    )                                                           # [N, A, R]

    eligible = prio_s < INF_PRIO                                # [N, A]
    # A prefix is valid only while every slot in it is eligible.
    prefix_ok = jnp.cumprod(eligible.astype(jnp.int32), axis=1).astype(bool)

    release = jnp.cumsum(usage_s, axis=1)                       # [N, A, R]
    util_k = used[:, None, :] - release + p.ask[None, None, :]  # [N, A, R]
    fits_k = jnp.all(util_k <= cap[:, None, :], axis=2) & prefix_ok

    any_fit = jnp.any(fits_k, axis=1) & feas                    # [N]
    # Minimal prefix: first k (1-based) where evicting k allocs admits ask.
    k_idx = jnp.argmax(fits_k, axis=1)                          # [N] 0-based
    k = k_idx + 1

    # net priority of the minimal prefix (rank.go:747 netPriority).
    # Per-row prefix selection as one-hot einsums, not [rows, k_idx]
    # advanced indexing — TPU gathers serialize; every slot is finite
    # (INF_PRIO = 1e9) and the selector is 0/1, so under `_EXACT` the
    # selected value comes back bit for bit.
    psum = jnp.cumsum(jnp.where(eligible, prio_s, 0.0), axis=1)  # [N, A]
    k_oh = (jnp.arange(a)[None, :] == k_idx[:, None]
            ).astype(jnp.float32)                               # [N, A]
    max_p = jnp.einsum("na,na->n", prio_s, k_oh,
                       precision=_EXACT)           # sorted ⇒ last = max
    sum_p = jnp.einsum("na,na->n", psum, k_oh, precision=_EXACT)
    net_prio = jnp.where(max_p > 0, max_p + sum_p / jnp.maximum(max_p, 1.0),
                         0.0)
    pre_score = 1.0 / (
        1.0 + jnp.exp(PREEMPTION_SCORE_RATE *
                      (net_prio - PREEMPTION_SCORE_ORIGIN))
    )

    # Bin-pack score at the post-eviction utilization (funcs.go:175).
    util_sel = jnp.einsum("nar,na->nr", util_k, k_oh,
                          precision=_EXACT)                     # [N, R]
    binpack, _ = fit_scores(util_sel, cap)

    combined = (binpack + pre_score) / 2.0
    scores = jnp.where(any_fit, combined, NEG_INF)

    best = jnp.argmax(scores)
    found = scores[best] > NEG_INF
    return PreemptionResult(
        best_row=jnp.where(found, best, -1).astype(jnp.int32),
        best_k=jnp.where(found, k[best], 0).astype(jnp.int32),
        best_score=jnp.where(found, scores[best], 0.0),
        order=order.astype(jnp.int32),
        feasible=any_fit,
        scores=scores,
    )


preempt_rank_jit = jax.jit(preempt_rank)
