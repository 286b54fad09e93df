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

`system_place` is the system scheduler's program: every node of a system
task group decided at once — fits, needs an eviction, and for each such
node the victim set upstream's greedy (`PreemptForTaskGroup` +
`filterSuperset`, preemption.go:198/:702) chooses there, the node fixed.

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

from ..structs.funcs import (MAX_PARALLEL_PENALTY, PREEMPTION_SCORE_ORIGIN,
                             PREEMPTION_SCORE_RATE)
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
    system_feasibility,
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


# ---------------------------------------------------------------------------
# System task groups: every node's fit, eviction and victims in one launch
# ---------------------------------------------------------------------------

#: `system_place`'s row status codes
SYS_INFEASIBLE, SYS_FITS, SYS_FREED, SYS_REFUSED, SYS_UNPACKED = range(5)


class SystemCandidates(NamedTuple):
    """One system task group's victim candidates, host-packed per node row
    (the row bucket × a power-of-two candidate bucket C): the proposed
    allocations of the row (live less the plan's stops and preemptions,
    plus its placements; never the job's own) that the job may evict —
    priority at least 10 below its own — in the order the host lists
    them. `remaining` is the node's usable cpu / memory / disk less EVERY
    such proposed allocation, evictable or not (the Preemptor's
    `remaining`)."""

    packed: jax.Array     # bool[N] — the row's candidates are in the table
    remaining: jax.Array  # f32[N, 3]
    res: jax.Array        # f32[N, C, 3] — candidate cpu / memory / disk
    prio: jax.Array       # f32[N, C] — candidate job priority; INF_PRIO pad
    # the max_parallel penalty (coupled variant; [1, 1] / [1] otherwise)
    max_par: jax.Array    # f32[N, C] — candidate group's migrate max_parallel
    slot: jax.Array       # i32[N, C] — its (job, task group) count slot, −1 pad
    counts: jax.Array     # f32[S] — the plan's preemptions per slot at launch
    order: jax.Array      # i32[K] — packed rows in the host's node order, −1 pad


def _distance(need: jax.Array, used: jax.Array) -> jax.Array:
    """`basic_resource_distance` (preemption.go:608) over the last axis
    (cpu, memory, disk), summed in the host's order: memory, cpu, disk."""
    def coord(k):
        n = need[..., k]
        return jnp.where(n > 0, (n - used[..., k]) / jnp.where(n > 0, n, 1.0),
                         0.0)

    cpu, mem, disk = coord(0), coord(1), coord(2)
    return jnp.sqrt(mem * mem + cpu * cpu + disk * disk)


def _greedy(remaining, res, prio, penalty, ask):
    """Upstream's victim greedy on B nodes at once, each node alone.

    Per node: the lowest priority among the candidates not yet taken is
    the group; in it take the least `score_for_task_group` against the
    SHRINKING need (first in table order on a tie), until remaining plus
    what was taken holds the ask on every dimension; a group used up
    without that passes to the next priority. Then `filterSuperset`: the
    taken ones re-added far to near by distance to the ask (a tie in the
    order taken) and cut at the first prefix that fits.
    -> (victims i32[B], order i32[B, C]): the first `victims` of `order`
    are the victims' candidate indices in upstream's order."""
    b, c = prio.shape
    live0 = prio < INF_PRIO
    cols = jnp.arange(c)[None, :]

    def cond(st):
        i, _chosen, _step, _avail, _need, done = st
        return (i < c) & ~jnp.all(done)

    def body(st):
        i, chosen, step, avail, need, done = st
        live = live0 & ~chosen
        low = jnp.min(jnp.where(live, prio, INF_PRIO), axis=1)
        grp = live & (prio == low[:, None]) & ~done[:, None]
        score = jnp.where(grp, _distance(need[:, None, :], res) + penalty,
                          jnp.inf)
        pick = jnp.argmin(score, axis=1)
        took = jnp.any(grp, axis=1)
        hit = (cols == pick[:, None]) & took[:, None]
        freed = jnp.sum(jnp.where(hit[..., None], res, 0.0), axis=1)
        avail = avail + freed
        met = took & jnp.all(avail >= ask, axis=1)
        return (i + 1, chosen | hit, jnp.where(hit, i, step), avail,
                need - freed, done | met | ~took)

    st0 = (jnp.int32(0), jnp.zeros((b, c), bool), jnp.full((b, c), c),
           remaining, jnp.broadcast_to(ask, (b, 3)), ~jnp.any(live0, axis=1))
    _, chosen, step, avail, _, _ = jax.lax.while_loop(cond, body, st0)
    met = jnp.all(avail >= ask, axis=1) & jnp.any(chosen, axis=1)

    far = jnp.where(chosen, -_distance(ask, res), jnp.inf)
    _, _, order = jax.lax.sort(
        (far, step, jnp.broadcast_to(cols, (b, c)).astype(jnp.int32)),
        dimension=1, num_keys=2)
    back = jnp.cumsum(jnp.take_along_axis(res, order[..., None], axis=1),
                      axis=1)
    fits = jnp.all(remaining[:, None, :] + back >= ask, axis=2) \
        & (cols < jnp.sum(chosen, axis=1)[:, None])
    victims = jnp.where(met, jnp.argmax(fits, axis=1) + 1, 0)
    return victims.astype(jnp.int32), order


def _onehot_take(x, idx):
    """`x[idx]` for one row of candidates, as a one-hot select."""
    return jnp.sum(jnp.where(idx[:, None] == jnp.arange(x.shape[0])[None, :],
                             x[None, :], 0), axis=1)


def _penalty(max_par, slot, counts):
    """`score_for_task_group`'s max_parallel term at the given counts."""
    n = jnp.where(slot >= 0, counts[jnp.maximum(slot, 0)], 0.0)
    return jnp.where((max_par > 0) & (n >= max_par),
                     (n + 1.0 - max_par) * MAX_PARALLEL_PENALTY, 0.0)


@functools.partial(jax.jit, static_argnames=("coupled",))
def system_place(cluster: ClusterArrays, p: TGParams, cand: SystemCandidates,
                 coupled: bool = False):
    """Every node of one system task group in one launch (reference
    `scheduler/system_sched.go:268` computePlacements with preemption on,
    the node fixed: `stack.go:256` + `PreemptForTaskGroup`).

    -> (status i8[N], victims i16[N, C]). `status` is `SYS_FITS` where
    the ask fits as the view stands, `SYS_FREED` where it fits once the
    row's victims go — the first of `victims` up to the first −1, in
    upstream's order, as indices into the row's candidates —,
    `SYS_REFUSED` where even every candidate would not free enough,
    `SYS_UNPACKED` where it needs an eviction and the host packed no
    candidates for the row, `SYS_INFEASIBLE` where a constraint rules
    it out.

    Nodes are independent unless a candidate's group has a migrate
    `max_parallel`: its penalty counts the plan's preemptions of that
    group, node after node. `coupled` (static) walks the packed rows in
    the host's node order and carries those counts, each row's victims
    added as it is decided; otherwise every row is decided at once with
    no penalty."""
    feas, fits = system_feasibility(cluster, p)
    need = feas & ~fits & cand.packed
    ask = p.ask[:3]
    n, c = cand.prio.shape
    if not coupled:
        victims, order = _greedy(cand.remaining, cand.res, cand.prio,
                                 jnp.zeros((n, c), jnp.float32), ask)
    else:
        s_ax = jnp.arange(cand.counts.shape[0])

        def row_step(counts, r):
            rr = jnp.maximum(r, 0)
            slot = cand.slot[rr]
            v, o = _greedy(cand.remaining[rr][None], cand.res[rr][None],
                           cand.prio[rr][None],
                           _penalty(cand.max_par[rr], slot, counts)[None],
                           ask)
            v = jnp.where((r >= 0) & need[rr], v[0], 0)
            # the row's victims, as the host loop adds them to the plan
            taken = jnp.sum(jnp.where(jnp.arange(c) < v,
                                      _onehot_take(slot, o[0]), -1)
                            [:, None] == s_ax[None, :], axis=0)
            return counts + taken, (v, o[0])

        _, (v_k, o_k) = jax.lax.scan(row_step, cand.counts, cand.order)
        # each row's decision back from its place in the walk (a gather)
        at = jnp.argmax(cand.order[None, :] == jnp.arange(n)[:, None],
                        axis=1)
        walked = jnp.any(cand.order[None, :] == jnp.arange(n)[:, None],
                         axis=1)
        victims = jnp.where(walked, v_k[at], 0)
        order = o_k[at]
    freed = need & (victims > 0)
    status = jnp.where(
        ~feas, SYS_INFEASIBLE, jnp.where(
            fits, SYS_FITS, jnp.where(
                ~cand.packed, SYS_UNPACKED,
                jnp.where(freed, SYS_FREED, SYS_REFUSED))))
    out = jnp.where(jnp.arange(c)[None, :] < jnp.where(freed, victims,
                                                       0)[:, None],
                    order, -1)
    return status.astype(jnp.int8), out.astype(jnp.int16)
