"""The placement kernel.

This is the dense-SPMD re-expression of the reference's evaluation hot loop
(`scheduler/generic_sched.go:468` computePlacements → `stack.go:116` Select →
`rank.go:188` BinPackIterator.Next → `structs/funcs.go:103,175`):

  reference (scalar, per candidate node, early-exit):
      RandomIterator → FeasibilityWrapper(constraint/driver/…) →
      DistinctHosts → BinPack → JobAntiAffinity → ReschedulePenalty →
      NodeAffinity → Spread → ScoreNormalization → Limit(log₂ n) → MaxScore

  here (vectorized, full-width over the node axis):
      feasibility = AND of LUT-gather masks           [N]
      score       = fused binpack + conditional aux terms, mean-normalized
      select      = argmax over N (exact; beats the log₂(n) sample — a
                    documented better-scoring deviation). Sampled mode
                    (`cand_idx`/`use_cand`) restricts selection to a
                    host-shuffled candidate subset shared with the oracle's
                    `sampled=` mode, so strict parity runs are well-defined.
      multi-alloc = lax.scan carrying (used, counts) so successive allocs of
                    one group see each other (reference: plan-relative
                    ProposedAllocs, context.go:120)

All per-node scoring semantics (conditional inclusion of each score term and
mean normalization) mirror `scheduler/rank.go`: binpack :440-447 (always,
/18), job-anti-affinity :521-530 (iff collisions>0), reschedule penalty
:570-575 (iff penalized), node affinity :652-659 (iff ≠0), spread
(`spread.go:167-174`, iff ≠0).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30

#: Matmul precision for every einsum that moves a VALUE (token id,
#: resource units, weight, count) through a 0/1 selector. An accelerator
#: runs an f32 matmul at DEFAULT precision as a single bf16 pass — eight
#: significant bits, so 1500 reads back 1504 and token 9999 reads back
#: 9984. HIGHEST splits each f32 operand into three bf16 terms and
#: accumulates the partial products in f32; with one side exactly 0/1
#: the three terms of the other side sum back to the original f32, bit
#: for bit. The values are exact because of THIS, not because "f32 holds
#: integers below 2^24" (true, and not what the MXU computes).
_EXACT = jax.lax.Precision.HIGHEST


class ClusterArrays(NamedTuple):
    """Device-resident cluster view (from tensor.ClusterSnapshot)."""

    capacity: jax.Array   # f32[N, R]
    used: jax.Array       # f32[N, R]
    node_ok: jax.Array    # bool[N]
    attrs: jax.Array      # i32[N, K]
    ports_used: jax.Array  # u32[N, 2048] — packed used-port bitmap
    dyn_free: jax.Array   # f32[N] — free dynamic-range ports


class TGParams(NamedTuple):
    """One task group's compiled placement request (padded/bucketed shapes)."""

    ask: jax.Array               # f32[R]
    n_place: jax.Array           # i32 — how many allocs to place (≤ M)
    desired_count: jax.Array     # f32 — tg.Count for anti-affinity denominator
    algorithm: jax.Array         # i32 — 0 binpack | 1 spread
    # feasibility LUT program (tensor/constraints.py)
    key_idx: jax.Array           # i32[C]
    lut: jax.Array               # bool[C, V]
    # affinity LUT program
    aff_key_idx: jax.Array       # i32[A]
    aff_lut: jax.Array           # f32[A, V]
    aff_inv_sum: jax.Array       # f32
    # per-step sparse vectors (rows beyond n_place are padding)
    penalty_idx: jax.Array       # i32[M, P] — reschedule-penalty node rows, −1 pad
    preferred_idx: jax.Array     # i32[M] — preferred node row (sticky disk), −1 none
    extra_mask: jax.Array        # bool[N] — host-evaluated checks (CSI, …)
    distinct_hosts: jax.Array    # bool — job or tg has distinct_hosts
    # sparse proposed-alloc counts, scattered to dense [N] on device (a job
    # touches few nodes; dense per-eval [N] vectors would dominate the
    # host→device batch transfer)
    jc_idx: jax.Array            # i32[J] — node rows with allocs of job, −1 pad
    jc_val: jax.Array            # f32[J] — distinct-hosts counts per row
    jtc_idx: jax.Array           # i32[J2] — node rows with allocs of (job,tg)
    jtc_val: jax.Array           # f32[J2] — anti-affinity counts per row
    # plan-relative resource deltas (stops/preemptions), sparse scatter
    delta_idx: jax.Array         # i32[D] — node row or −1
    delta_res: jax.Array         # f32[D, R] — resources to subtract
    # sampled-candidate mode (stack.go:77-89 log₂(n) limit analog): when
    # use_cand, selection is restricted to the cand_idx node rows — the
    # SAME host-shuffled subset the oracle's sampled mode scans, so strict
    # kernel-vs-oracle parity is well-defined (−1 rows are padding)
    cand_idx: jax.Array          # i32[L]
    use_cand: jax.Array          # bool
    # distinct_property program (feasible.go:569 DistinctPropertyIterator,
    # propertyset.go:14): per-constraint value-count tables; a node is
    # feasible iff count[value] < allowed for every active constraint and
    # the property resolves (missing ⇒ infeasible). Counts update in-scan
    # as allocs place (PopulateProposed analog).
    dp_key_idx: jax.Array        # i32[P]
    dp_allowed: jax.Array        # f32[P] — RTarget count (default 1)
    dp_counts0: jax.Array        # f32[P, V] — existing+plan combined use
    dp_active: jax.Array         # bool[P]
    # port feasibility (reference rank.go:231-320 — AssignPorts inside
    # BinPackIterator ranks out port-infeasible nodes; here the asks are
    # static per TG so the checks fold into the node mask). Plan-relative
    # port deltas ship as sparse (node-row, port) pairs: pclr_* release
    # ports of in-plan stopped/preempted allocs, pset_* consume ports of
    # in-plan placements (the NetworkIndex plan threading of rank.go:240).
    res_ports: jax.Array         # i32[PP] — static host-port asks, −1 pad
    n_dyn: jax.Array             # f32 — dynamic ports requested per alloc
    pclr_idx: jax.Array          # i32[PC] — node rows releasing a port, −1 pad
    pclr_port: jax.Array         # i32[PC] — the released port
    pset_idx: jax.Array          # i32[PS] — node rows consuming a port, −1 pad
    pset_port: jax.Array         # i32[PS] — the consumed port
    # spread program
    spread_key_idx: jax.Array    # i32[S]
    spread_weight: jax.Array     # f32[S] — weight/ΣW (target mode)
    spread_has_targets: jax.Array  # bool[S]
    spread_desired: jax.Array    # f32[S, V] — desired count per token; −1 ⇒ −1 penalty
    spread_counts0: jax.Array    # f32[S, V] — current counts per token
    spread_active: jax.Array     # bool[S]


#: top-K score-breakdown width (reference `lib/kheap` capacity used by
#: AllocMetric.PopulateScoreMetaData — structs.go:9370 keeps 5)
EXPLAIN_TOPK = 5

#: score components carried per top-K node, in order (reference rank.go
#: iterator names as they appear in NodeScoreMeta.Scores)
EXPLAIN_SCORE_NAMES = ("binpack", "job-anti-affinity",
                       "node-reschedule-penalty", "node-affinity",
                       "allocation-spread")


class PlacementExplain(NamedTuple):
    """Reduced attribution outputs for one placement program — the
    device half of `structs.AllocMetric` (structs.go:9172). Everything
    here is a REDUCTION of masks the kernel already computes: emitting
    it adds no per-node work beyond a handful of sums and one top_k, so
    `sel_idx`/`sel_score` are bit-identical with explain on or off
    (tests/test_explain.py pins this).

    Stage taxonomy mirrors the reference iterator chain: static
    feasibility first (constraint/class/driver LUT, then the
    host-evaluated device-plugin/CSI mask), then per-step checks in
    chain order — distinct_hosts, distinct_property (both "filtered",
    feasible.go), then rank-time exhaustion (BinPack's resource
    dimensions in column order, dynamic ports, reserved ports —
    rank.go:231-320 ranks port-infeasible nodes out as exhausted, not
    filtered)."""

    nodes_evaluated: jax.Array    # i32 — candidate nodes entering the chain
    filt_constraint: jax.Array    # i32[C] — evaluated nodes failing LUT row c
    filt_lut: jax.Array           # i32 — evaluated nodes failing ANY LUT row
    filt_extra: jax.Array         # i32 — LUT-clean nodes failing extra_mask
    filt_distinct: jax.Array      # i32[M] — feasible, distinct_hosts collision
    filt_dp: jax.Array            # i32[M] — feasible, distinct_property full
    exh_dim: jax.Array            # i32[M, R] — first-exhausted resource column
    exh_dyn_ports: jax.Array      # i32[M] — resource-fit, dynamic ports short
    exh_res_ports: jax.Array      # i32[M] — resource-fit, reserved port taken
    topk_idx: jax.Array           # i32[M, K] — best node rows by masked score
    topk_score: jax.Array         # f32[M, K] — their normalized final scores
    topk_parts: jax.Array         # f32[M, K, 5] — EXPLAIN_SCORE_NAMES values


class PlacementResult(NamedTuple):
    sel_idx: jax.Array       # i32[M] — chosen node row per alloc, −1 = failed
    sel_score: jax.Array     # f32[M] — normalized score of the chosen node
    new_used: jax.Array      # f32[N, R] — used after this group's placements
    nodes_feasible: jax.Array  # i32 — nodes passing constraint masks
    nodes_fit: jax.Array     # i32[M] — nodes passing fit per step
    final_scores0: jax.Array  # f32[N] — first step's normalized score vector
    explain: Optional[PlacementExplain] = None  # set iff explain=True


def fit_scores(util: jax.Array, cap: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """(binpack, spread) fit scores per node, each in [0, 1]
    (reference funcs.go:175/:202, normalized by 18 per rank.go:11-13).
    10^x computed as exp2(x·log₂10) — VPU-friendly."""
    free_cpu = 1.0 - util[:, 0] / jnp.maximum(cap[:, 0], 1.0)
    free_ram = 1.0 - util[:, 1] / jnp.maximum(cap[:, 1], 1.0)
    total = jnp.exp2(free_cpu * 3.321928094887362) + jnp.exp2(
        free_ram * 3.321928094887362
    )
    binpack = jnp.clip(20.0 - total, 0.0, 18.0) / 18.0
    spread = jnp.clip(total - 2.0, 0.0, 18.0) / 18.0
    return binpack, spread


def _select_tokens(attrs: jax.Array, key_idx: jax.Array, v: int) -> jax.Array:
    """tok[n, c] = attrs[n, key_idx[c]], normalized into [0, v):
    missing (−1) → last slot; clamp above: LUT widths are per-program
    (sized to the keys the program references), but PAD rows point at an
    arbitrary key whose tokens may exceed V — clamping them onto the
    missing slot keeps padding inert (pad rows are all-true / zero-weight
    in every column) instead of out-of-bounds.

    Expressed as a one-hot matmul over the key axis rather than a gather:
    TPU gathers serialize, matmuls ride the MXU. Token ids are exact
    under `_EXACT` (and below 2^24, so the f32 round trip is too)."""
    k = attrs.shape[1]
    oh = (key_idx[:, None] == jnp.arange(k)[None, :]).astype(jnp.float32)
    tok = jnp.einsum("nk,ck->nc", attrs.astype(jnp.float32), oh,
                     precision=_EXACT)
    tok = tok.astype(jnp.int32)
    return jnp.where(tok < 0, v - 1, jnp.minimum(tok, v - 1))


def _onehot_tokens(tok: jax.Array, v: int) -> jax.Array:
    """[..., C] int tokens → [..., C, V] f32 one-hot."""
    return (tok[..., None] == jnp.arange(v)).astype(jnp.float32)


def _lut_gather(lut: jax.Array, key_idx: jax.Array, attrs: jax.Array) -> jax.Array:
    """out[n, c] = lut[c, tok(n, key_idx[c])] with missing → last slot,
    as one-hot einsum (gather-free). A bool LUT needs no `_EXACT`: both
    operands are 0/1, which a bf16 pass carries exactly; an f32 LUT
    (affinity weights) does."""
    if lut.shape[0] == 0:
        return jnp.ones((attrs.shape[0], 0), dtype=lut.dtype)
    v = lut.shape[1]
    tok = _select_tokens(attrs, key_idx, v)
    oh = _onehot_tokens(tok, v)                    # [N, C, V]
    if lut.dtype == jnp.bool_ or lut.dtype == np.bool_:
        return jnp.einsum("ncv,cv->nc", oh, lut.astype(jnp.float32)) > 0.5
    return jnp.einsum("ncv,cv->nc", oh, lut.astype(jnp.float32),
                      precision=_EXACT)


def _scatter_counts(idx: jax.Array, val: jax.Array, n: int) -> jax.Array:
    """Dense f32[N] from sparse (node-row, count) pairs; −1 pads match no
    row. Comparison-einsum instead of scatter (TPU scatters serialize)."""
    eq = (idx[:, None] == jnp.arange(n)[None, :]).astype(jnp.float32)
    return jnp.einsum("jn,j->n", eq, val, precision=_EXACT)


def _dp_feasible(dtok: jax.Array, dtok_oh: jax.Array, dcounts: jax.Array,
                 p: TGParams) -> jax.Array:
    """distinct_property node mask (propertyset.go:214
    SatisfiesDistinctProperties): feasible iff use count of the node's
    value < allowed and the property resolves (missing slot ⇒ infeasible),
    per active row. Shared by the placement scan (evolving counts) and the
    preemption ranker (counts0) so the two paths can't diverge."""
    d_v = dcounts.shape[1]
    cur_d = jnp.einsum("npv,pv->np", dtok_oh, dcounts,
                       precision=_EXACT)                        # [N, P]
    row_ok = ((cur_d < p.dp_allowed[None, :])
              & (dtok != d_v - 1)) | ~p.dp_active[None, :]
    return jnp.all(row_ok, axis=1)


def _reserved_ports_free(cluster: ClusterArrays, p: TGParams) -> jax.Array:
    """bool[N]: every statically-asked host port is free on the node
    (reference AssignPorts inside BinPackIterator, rank.go:231-320 +
    network.go:316 — a taken port ranks the node out). −1 rows are padding.
    Word lookup is a small take along the packed axis (PP ≤ a few ports).
    Plan-relative adjustments: a port released by an in-plan stop/preempt
    (pclr) reads as free; one consumed by an in-plan placement (pset) reads
    as taken — mirroring the proposed-alloc NetworkIndex (rank.go:240)."""
    n = cluster.ports_used.shape[0]
    if p.res_ports.shape[0] == 0:
        return jnp.ones(n, dtype=bool)
    rp = jnp.maximum(p.res_ports, 0)
    words = jnp.take(cluster.ports_used, rp >> 5, axis=1)        # [N, PP]
    bit = (words >> (rp & 31).astype(jnp.uint32)[None, :]) & jnp.uint32(1)
    taken = bit != 0                                             # [N, PP]
    if p.pclr_idx.shape[0]:
        cleared = jnp.any(
            (p.pclr_idx[:, None, None] == jnp.arange(n)[None, :, None])
            & (p.pclr_port[:, None, None] == p.res_ports[None, None, :]),
            axis=0)                                              # [N, PP]
        taken = taken & ~cleared
    if p.pset_idx.shape[0]:
        pset = jnp.any(
            (p.pset_idx[:, None, None] == jnp.arange(n)[None, :, None])
            & (p.pset_port[:, None, None] == p.res_ports[None, None, :]),
            axis=0)
        taken = taken | pset
    free = ~taken | (p.res_ports < 0)[None, :]
    return jnp.all(free, axis=1)


def _dyn_free_adjusted(cluster: ClusterArrays, p: TGParams) -> jax.Array:
    """f32[N]: free dynamic-port counts with plan-relative credit/debit."""
    n = cluster.dyn_free.shape[0]
    dyn = cluster.dyn_free
    if p.pclr_idx.shape[0]:
        in_rng = ((p.pclr_port >= 20000) & (p.pclr_port <= 32000)
                  ).astype(jnp.float32)
        dyn = dyn + _scatter_counts(p.pclr_idx, in_rng, n)
    if p.pset_idx.shape[0]:
        in_rng = ((p.pset_port >= 20000) & (p.pset_port <= 32000)
                  ).astype(jnp.float32)
        dyn = dyn - _scatter_counts(p.pset_idx, in_rng, n)
    return dyn


def _spread_boost(
    stok: jax.Array,        # i32[N, S] normalized value tokens (miss = V−1)
    stok_oh: jax.Array,     # f32[N, S, V] one-hot of stok
    counts: jax.Array,      # f32[S, V]
    p: TGParams,
) -> jax.Array:
    """Per-node total spread boost (reference spread.go:120-174 +
    evenSpreadScoreBoost :178). Token lookups are one-hot einsums — this
    runs inside the alloc scan, and TPU gathers would serialize it."""
    S, V = counts.shape
    if S == 0:
        return jnp.zeros(stok.shape[0], dtype=jnp.float32)
    miss = V - 1
    tok = stok                                     # [N, S]
    cur = jnp.einsum("nsv,sv->ns", stok_oh, counts,
                     precision=_EXACT)             # counts[s, tok]

    # -- target mode: boost = (desired − (cur+1))/desired · w, or −1 --
    desired = jnp.einsum("nsv,sv->ns", stok_oh, p.spread_desired,
                         precision=_EXACT)
    used_count = cur + 1.0
    target_boost = jnp.where(
        desired > 0.0,
        (desired - used_count) / jnp.where(desired > 0, desired, 1.0)
        * p.spread_weight[None, :],
        -1.0,
    )

    # -- even mode (evenSpreadScoreBoost) --
    seen = counts > 0.0                             # [S, V]
    any_seen = jnp.any(seen, axis=1)                # [S]
    big = jnp.float32(3.4e38)
    minc = jnp.min(jnp.where(seen, counts, big), axis=1)    # [S]
    maxc = jnp.max(jnp.where(seen, counts, -big), axis=1)   # [S]
    minc_safe = jnp.where(minc > 0, minc, 1.0)
    delta_boost = jnp.where(minc[None, :] == 0.0, -1.0,
                            (minc[None, :] - cur) / minc_safe[None, :])
    even = jnp.where(
        cur != minc[None, :],
        delta_boost,
        jnp.where(
            (minc == maxc)[None, :],
            -1.0,
            jnp.where(
                (minc == 0.0)[None, :],
                1.0,
                ((maxc - minc) / minc_safe)[None, :] * jnp.ones_like(cur),
            ),
        ),
    )
    even = jnp.where(tok == miss, -1.0, even)
    even = jnp.where(any_seen[None, :], even, 0.0)

    boost = jnp.where(p.spread_has_targets[None, :], target_boost, even)
    boost = jnp.where(p.spread_active[None, :], boost, 0.0)
    return jnp.sum(boost, axis=1)                   # [N]


def place_task_group(cluster: ClusterArrays, p: TGParams, max_allocs: int,
                     explain: bool = False) -> PlacementResult:
    """Place up to `max_allocs` allocations of one task group.

    Pure function: jit/vmap-safe. The scan carry mirrors the plan-relative
    state the reference threads through `ctx.Plan()` (context.go:120).

    `explain` (static) additionally emits PlacementExplain — reduced
    attribution counters + a top-K score breakdown in the SAME dispatch.
    The selection math is untouched either way: explain only reduces
    masks the kernel already computes.
    """
    cap = cluster.capacity
    n = cap.shape[0]

    # ---- static (per-group) feasibility, computed once ----
    with jax.named_scope("nomad.feasibility_mask"):
        feas_c = _lut_gather(p.lut, p.key_idx, cluster.attrs)          # [N, C] bool
        lut_all = jnp.all(feas_c, axis=1)
        feas = cluster.node_ok & p.extra_mask & lut_all
        in_cand = None
        if p.cand_idx.shape[0]:
            in_cand = jnp.any(p.cand_idx[:, None] == jnp.arange(n)[None, :],
                              axis=0)
            feas = feas & (in_cand | ~p.use_cand)

    if explain:
        # candidate base: every node the iterator chain would scan
        # (sampled mode restricts the scan itself — unscanned nodes are
        # not "evaluated", matching the reference Limit iterator)
        base = cluster.node_ok
        if p.cand_idx.shape[0]:
            base = base & (in_cand | ~p.use_cand)
        ex_evaluated = jnp.sum(base.astype(jnp.int32))
        # per-LUT-row filtered counts (independent per row — padding
        # rows are all-true and count 0); plus first-fail stage totals
        ex_filt_constraint = jnp.sum(
            (~feas_c) & base[:, None], axis=0).astype(jnp.int32)
        ex_filt_lut = jnp.sum((base & ~lut_all).astype(jnp.int32))
        ex_filt_extra = jnp.sum(
            (base & lut_all & ~p.extra_mask).astype(jnp.int32))

    aff_vals = _lut_gather(p.aff_lut, p.aff_key_idx, cluster.attrs)  # [N, A] f32
    aff_score = jnp.sum(aff_vals, axis=1) * p.aff_inv_sum            # [N]

    s_v = p.spread_desired.shape[1]
    if p.spread_key_idx.shape[0]:
        stok = _select_tokens(cluster.attrs, p.spread_key_idx, s_v)
        stok_oh = _onehot_tokens(stok, s_v)        # [N, S, V]
    else:
        stok = jnp.zeros((n, 0), dtype=jnp.int32)
        stok_oh = jnp.zeros((n, 0, s_v), dtype=jnp.float32)

    d_v = p.dp_counts0.shape[1]
    if p.dp_key_idx.shape[0]:
        dtok = _select_tokens(cluster.attrs, p.dp_key_idx, d_v)  # [N, P]
        dtok_oh = _onehot_tokens(dtok, d_v)        # [N, P, V]
    else:
        dtok = jnp.zeros((n, 0), dtype=jnp.int32)
        dtok_oh = jnp.zeros((n, 0, d_v), dtype=jnp.float32)

    # plan-relative deltas (stopped/preempted allocs release resources);
    # comparison-einsum instead of scatter (−1 pads match no row)
    used0 = cluster.used
    if p.delta_idx.shape[0]:
        eq = (p.delta_idx[:, None] == jnp.arange(n)[None, :]
              ).astype(jnp.float32)                # [D, N]
        used0 = used0 - jnp.einsum("dn,dr->nr", eq, p.delta_res,
                                   precision=_EXACT)

    nodes_feasible = jnp.sum(feas.astype(jnp.int32))

    # port feasibility (rank-time, so failures count as "exhausted" like the
    # reference's BinPack rank-out, not constraint-"filtered"): static asks
    # against the packed bitmap once; dynamic-count and same-node-reuse
    # tracked in the scan as this group's own placements consume ports
    res_free = _reserved_ports_free(cluster, p)
    dyn_free = _dyn_free_adjusted(cluster, p)
    has_res_ask = jnp.any(p.res_ports >= 0)

    def step(carry, xs):
        i, pen_idx, pref_idx = xs
        used, job_cnt, tg_cnt, scounts, dcounts, splaced = carry
        active = i < p.n_place

        # per-step reschedule penalty nodes (rank.go:570 SetPenaltyNodes);
        # compare, don't scatter (−1 pads match no row)
        penalty = jnp.any(pen_idx[:, None] == jnp.arange(n)[None, :], axis=0)

        with jax.named_scope("nomad.fit_mask"):
            util = used + p.ask[None, :]                   # [N, R]
            res_over = util > cap                          # [N, R]
            fits = ~jnp.any(res_over, axis=1)
            dyn_ok = (dyn_free - splaced * p.n_dyn) >= p.n_dyn
            res_ok = res_free & ~(has_res_ask & (splaced > 0))
            ports_ok = dyn_ok & res_ok
            fits = fits & ports_ok
            ok = feas & fits
            dh_collide = p.distinct_hosts & (job_cnt > 0)
            ok = ok & ~dh_collide

            dp_mask = None
            if dcounts.shape[0]:
                dp_mask = _dp_feasible(dtok, dtok_oh, dcounts, p)
                ok = ok & dp_mask

        # ---- fused scoring (rank.go semantics) ----
        with jax.named_scope("nomad.score"):
            binpack, spreadfit = fit_scores(util, cap)
            fit_score = jnp.where(p.algorithm == 1, spreadfit, binpack)

            ssum = fit_score
            scnt = jnp.ones_like(fit_score)

            collide = tg_cnt > 0
            anti = -(tg_cnt + 1.0) / jnp.maximum(p.desired_count, 1.0)
            ssum = ssum + jnp.where(collide, anti, 0.0)
            scnt = scnt + collide

            ssum = ssum + jnp.where(penalty, -1.0, 0.0)
            scnt = scnt + penalty

            inc_aff = aff_score != 0.0
            ssum = ssum + jnp.where(inc_aff, aff_score, 0.0)
            scnt = scnt + inc_aff

            spread_score = _spread_boost(stok, stok_oh, scounts, p)
            inc_spread = spread_score != 0.0
            ssum = ssum + jnp.where(inc_spread, spread_score, 0.0)
            scnt = scnt + inc_spread

            final = ssum / scnt
            masked = jnp.where(ok, final, NEG_INF)

        # Preferred node (sticky ephemeral disk / prev-node rescheduling:
        # generic_sched.go findPreferredNode + stack SelectPreferringNodes)
        with jax.named_scope("nomad.select"):
            best = jnp.argmax(masked)
            pref_ok = (pref_idx >= 0) & ok[jnp.maximum(pref_idx, 0)]
            idx = jnp.where(pref_ok, jnp.maximum(pref_idx, 0), best)
            found = ok[idx] & active
            sel = jnp.where(found, idx, -1)

        with jax.named_scope("nomad.carry_update"):
            onehot = (jnp.arange(n) == idx) & found
            used = used + jnp.where(onehot[:, None], p.ask[None, :], 0.0)
            job_cnt = job_cnt + onehot
            tg_cnt = tg_cnt + onehot
            splaced = splaced + onehot.astype(jnp.float32)
            if scounts.shape[0]:
                sel_tok = stok[idx]                     # [S], normalized
                # missing values never enter the use map (spread.go:326);
                # miss is the last slot after _select_tokens normalization
                valid = (sel_tok != scounts.shape[1] - 1) & found
                upd = jax.nn.one_hot(
                    sel_tok, scounts.shape[1], dtype=scounts.dtype,
                ) * valid[:, None]
                scounts = scounts + upd
            if dcounts.shape[0]:
                sel_dtok = dtok[idx]                    # [P]
                dvalid = (sel_dtok != dcounts.shape[1] - 1) & found
                dupd = jax.nn.one_hot(
                    sel_dtok, dcounts.shape[1], dtype=dcounts.dtype,
                ) * dvalid[:, None]
                dcounts = dcounts + dupd

        n_fit = jnp.sum((feas & fits).astype(jnp.int32))
        ys = (
            sel,
            jnp.where(found, final[idx], 0.0),
            n_fit,
            masked,
        )
        if explain:
            # chain-order attribution over masks already computed above:
            # distinct_hosts / distinct_property are feasibility stages
            # (filtered); resource/port shortfalls at rank time are
            # exhaustion (rank.go:231-320 BinPack rank-out)
            dh_fail = feas & dh_collide
            dp_fail = jnp.zeros_like(feas)
            if dcounts.shape[0]:
                dp_fail = feas & ~dh_fail & ~dp_mask
            cand_m = feas & ~dh_fail & ~dp_fail
            any_over = jnp.any(res_over, axis=1)
            # first-exceeded resource column (AllocsFit reports the
            # FIRST dimension over, structs/funcs.go:103)
            ff = jnp.argmax(res_over, axis=1)                  # [N]
            r_tot = cap.shape[1]
            ff_oh = (ff[:, None] == jnp.arange(r_tot)[None, :]) \
                & (cand_m & any_over)[:, None]
            ex_dim = jnp.sum(ff_oh.astype(jnp.int32), axis=0)  # [R]
            ex_dyn = jnp.sum((cand_m & ~any_over
                              & ~dyn_ok).astype(jnp.int32))
            ex_res = jnp.sum((cand_m & ~any_over & dyn_ok
                              & ~res_ok).astype(jnp.int32))
            # top-K score breakdown (the kheap idiom, device-side):
            # K best masked scores + their per-component values. The
            # component vectors are the INCLUDED values (0 when a term
            # did not apply — rank.go's conditional inclusion).
            k = min(EXPLAIN_TOPK, n)
            tk_score, tk_idx = jax.lax.top_k(masked, k)
            parts = jnp.stack(
                (fit_score,
                 jnp.where(collide, anti, 0.0),
                 jnp.where(penalty, -1.0, 0.0),
                 jnp.where(inc_aff, aff_score, 0.0),
                 jnp.where(inc_spread, spread_score, 0.0)),
                axis=1)                                        # [N, 5]
            tk_oh = (tk_idx[:, None] == jnp.arange(n)[None, :]
                     ).astype(jnp.float32)                     # [K, N]
            tk_parts = jnp.einsum("kn,np->kp", tk_oh, parts,
                                  precision=_EXACT)            # [K, 5]
            # zero the parts of infeasible tail entries (score at the
            # mask floor): the host drops them unread, and their raw
            # values would otherwise depend on `used` rows OUTSIDE the
            # program's footprint — breaking the wave dispatch's
            # bit-parity contract for bytes nobody consumes
            tk_parts = tk_parts * (tk_score > NEG_INF / 2)[:, None]
            ys = ys + (
                jnp.sum(dh_fail.astype(jnp.int32)),
                jnp.sum(dp_fail.astype(jnp.int32)),
                ex_dim, ex_dyn, ex_res,
                tk_idx.astype(jnp.int32), tk_score, tk_parts,
            )
        return (used, job_cnt, tg_cnt, scounts, dcounts, splaced), ys

    job_cnt0 = _scatter_counts(p.jc_idx, p.jc_val, n)
    tg_cnt0 = _scatter_counts(p.jtc_idx, p.jtc_val, n)
    splaced0 = jnp.zeros(n, dtype=jnp.float32)
    init = (used0, job_cnt0, tg_cnt0, p.spread_counts0, p.dp_counts0,
            splaced0)
    xs = (jnp.arange(max_allocs), p.penalty_idx, p.preferred_idx)
    with jax.named_scope("nomad.alloc_scan"):
        (used_f, _, _, _, _, _), ys = jax.lax.scan(step, init, xs)
    sels, scores, n_fits, finals = ys[:4]
    ex = None
    if explain:
        (filt_dh, filt_dp, ex_dim, ex_dyn, ex_res,
         tk_idx, tk_score, tk_parts) = ys[4:]
        ex = PlacementExplain(
            nodes_evaluated=ex_evaluated,
            filt_constraint=ex_filt_constraint,
            filt_lut=ex_filt_lut,
            filt_extra=ex_filt_extra,
            filt_distinct=filt_dh,
            filt_dp=filt_dp,
            exh_dim=ex_dim,
            exh_dyn_ports=ex_dyn,
            exh_res_ports=ex_res,
            topk_idx=tk_idx,
            topk_score=tk_score,
            topk_parts=tk_parts,
        )
    return PlacementResult(
        sel_idx=sels.astype(jnp.int32),
        sel_score=scores,
        new_used=used_f,
        nodes_feasible=nodes_feasible,
        nodes_fit=n_fits,
        final_scores0=finals[0],
        explain=ex,
    )


@functools.partial(jax.jit, static_argnames=("max_allocs", "explain"))
def place_task_group_jit(cluster: ClusterArrays, p: TGParams, max_allocs: int,
                         explain: bool = False) -> PlacementResult:
    return place_task_group(cluster, p, max_allocs, explain=explain)


# ---- packed transport ------------------------------------------------------
# A batched TGParams is ~24 small arrays, each its own host→device
# transfer if shipped as leaves. Packing into one buffer per dtype class
# turns that into 3 transfers; the jitted unpack (static offsets,
# slice+reshape) fuses to nothing. What a transfer costs on an attached
# chip is not measured.

_PACK_I32 = ("n_place", "algorithm", "key_idx", "aff_key_idx", "penalty_idx",
             "preferred_idx", "jc_idx", "jtc_idx", "delta_idx",
             "cand_idx", "dp_key_idx", "spread_key_idx", "res_ports",
             "pclr_idx", "pclr_port", "pset_idx", "pset_port")
_PACK_F32 = ("ask", "desired_count", "aff_lut", "aff_inv_sum", "jc_val",
             "jtc_val", "delta_res", "dp_allowed", "dp_counts0",
             "spread_weight", "spread_desired", "spread_counts0", "n_dyn")
_PACK_U8 = ("lut", "extra_mask", "distinct_hosts", "use_cand", "dp_active",
            "spread_has_targets", "spread_active")


#: TGParams partition for the device-resident program table (ISSUE 10).
#: STATIC fields are plan-independent — they come from the job spec's
#: compiled program (`TPUStack._static_program`) and are identical every
#: time the same job spec is evaluated, so their packed rows live ON
#: DEVICE in a persistent table (server/program_table.py) and steady-state
#: dispatches ship only a row index. DYNAMIC fields are per-eval
#: plan-relative state (deltas, counts, penalty rows) and ship per
#: dispatch as one small packed row per program.
STATIC_FIELDS = (
    "ask", "desired_count", "algorithm", "key_idx", "lut", "aff_key_idx",
    "aff_lut", "aff_inv_sum", "extra_mask", "distinct_hosts", "res_ports",
    "n_dyn", "dp_key_idx", "dp_allowed", "dp_active", "spread_key_idx",
    "spread_weight", "spread_has_targets", "spread_desired",
    "spread_active",
)
DYN_FIELDS = tuple(f for f in TGParams._fields if f not in STATIC_FIELDS)


def _pack_class(name: str):
    if name in _PACK_I32:
        return "i", np.int32
    if name in _PACK_F32:
        return "f", np.float32
    return "u", np.uint8


#: field → (class, dtype), precomputed: _pack_class scans tuples, and
#: the row-pack paths look this up per field per program per dispatch
_PACK_CLASS = {name: _pack_class(name) for name in TGParams._fields}


def pack_param_rows_batch(padded, fields):
    """Pack a BATCH of same-shaped programs' `fields` into row-major
    [B, L*] class buffers + the shared spec — the whole-batch form of
    `pack_param_rows` (identical layout per row, pinned by
    tests/test_drain.py). One vectorized stack per FIELD instead of
    ~|fields| numpy ops per PROGRAM: at 256-program mega-batch waves the
    per-program loop was the host-pack floor the drain cadence exists
    to amortize."""
    bufs = {"i": [], "f": [], "u": []}
    offs = {"i": 0, "f": 0, "u": 0}
    spec = []
    b = len(padded)
    for name in fields:
        cls, dt = _PACK_CLASS[name]
        stacked = np.stack([np.asarray(getattr(p, name))
                            for p in padded])
        flat = np.ascontiguousarray(stacked, dtype=dt).reshape(b, -1)
        spec.append((name, cls, offs[cls], stacked.shape[1:]))
        offs[cls] += flat.shape[1]
        bufs[cls].append(flat)
    cat = {c: (np.concatenate(v, axis=1) if v
               else np.zeros((b, 0), dtype=d))
           for (c, v), d in zip(bufs.items(),
                                (np.int32, np.float32, np.uint8))}
    return cat["i"], cat["f"], cat["u"], tuple(spec)


def pack_params(batch: TGParams):
    """Flatten a (batched) TGParams into (i32, f32, u8) numpy buffers plus a
    static spec for the on-device unpack."""
    bufs = {"i": [], "f": [], "u": []}
    spec = []
    for name in TGParams._fields:
        a = np.asarray(getattr(batch, name))
        cls, dt = _pack_class(name)
        flat = np.ascontiguousarray(a, dtype=dt).reshape(-1)
        off = sum(x.size for x in bufs[cls])
        bufs[cls].append(flat)
        spec.append((name, cls, off, a.shape))
    cat = {c: (np.concatenate(v) if v else np.zeros(0, dtype=d))
           for (c, v), d in zip(bufs.items(),
                                (np.int32, np.float32, np.uint8))}
    return cat["i"], cat["f"], cat["u"], tuple(spec)


def pack_param_rows(p: TGParams, fields):
    """Pack ONE program's `fields` into flat (i32, f32, u8) rows + spec.

    Row-major per program (unlike `pack_params`, which concatenates
    field-major across a whole batch): rows of programs packed at the
    same shapes are interchangeable table entries, and a batch of them
    stacks into [B, L] buffers whose on-device unpack slices static
    column ranges. Runs once per program per mega-batch dispatch, so the
    offsets are tracked as running counters — re-summing the buffer list
    per field was quadratic in field count and a measured ~40% of the
    table-transport pack floor at 256-program waves."""
    bufs = {"i": [], "f": [], "u": []}
    offs = {"i": 0, "f": 0, "u": 0}
    spec = []
    for name in fields:
        a = np.asarray(getattr(p, name))
        cls, dt = _pack_class(name)
        flat = np.ascontiguousarray(a, dtype=dt).reshape(-1)
        spec.append((name, cls, offs[cls], a.shape))
        offs[cls] += flat.size
        bufs[cls].append(flat)
    cat = {c: (np.concatenate(v) if v else np.zeros(0, dtype=d))
           for (c, v), d in zip(bufs.items(),
                                (np.int32, np.float32, np.uint8))}
    return cat["i"], cat["f"], cat["u"], tuple(spec)




def _unpack_params(i32buf, f32buf, u8buf, spec) -> TGParams:
    fields = {}
    bufs = {"i": i32buf, "f": f32buf, "u": u8buf}
    for name, cls, off, shape in spec:
        size = int(np.prod(shape)) if shape else 1
        seg = jax.lax.dynamic_slice_in_dim(bufs[cls], off, size)
        a = seg.reshape(shape)
        if cls == "u":
            a = a != 0
        fields[name] = a
    return TGParams(**fields)


def _chain_with_carry(cluster: ClusterArrays, batch: TGParams,
                      max_allocs: int, explain: bool = False):
    """Chain body shared by the packed and table dispatches: scan over
    the program axis; ALSO returns the final (used, dyn_free) carry —
    the device-resident post-placement view the D2D plan-delta path
    (scheduler/stack.py carry adoption) feeds back into the cached
    cluster buffers without a host round-trip."""
    n = cluster.used.shape[0]

    def prog(carry, p):
        used, dyn = carry
        cl = cluster._replace(used=used, dyn_free=dyn)
        r = place_task_group(cl, p, max_allocs, explain=explain)
        with jax.named_scope("nomad.chain_carry"):
            placed = jnp.sum(
                ((r.sel_idx[:, None] == jnp.arange(n)[None, :])
                 & (r.sel_idx >= 0)[:, None]).astype(jnp.float32), axis=0)
            dyn = dyn - placed * p.n_dyn
        return (r.new_used, dyn), r

    with jax.named_scope("nomad.program_chain"):
        (used_f, dyn_f), results = jax.lax.scan(
            prog, (cluster.used, cluster.dyn_free), batch)
    return results, (used_f, dyn_f)


@functools.partial(jax.jit, static_argnames=("max_allocs", "explain"))
def place_task_group_chain(cluster: ClusterArrays, batch: TGParams,
                           max_allocs: int,
                           explain: bool = False) -> PlacementResult:
    """Chained batched placement: scan over the program axis carrying
    (used, dyn_free) so program i sees programs 0..i-1's placements.

    This is the conflict-FREE form of eval batching: where `_batch`
    (vmap) mirrors the reference's N workers racing on one MVCC snapshot
    (`nomad/server.go:1419`) and leaves collisions to plan-apply
    (`nomad/plan_apply.go:437`), the chain threads the optimistic
    resource view through the batch the way a single worker's in-plan
    accounting does (`scheduler/context.go:120` ProposedAllocs) — two
    evals in one batch can never over-commit cpu/mem/disk or the dynamic
    port budget on a node. Reserved-port collisions across programs are
    still resolved at apply (port VALUES are assigned host-side).
    Serial over B programs on-device, but it's ONE dispatch; the inner
    node-axis work stays full-width SPMD."""
    results, _carry = _chain_with_carry(cluster, batch, max_allocs,
                                        explain=explain)
    return results


@functools.partial(jax.jit,
                   static_argnames=("spec", "max_allocs", "explain"))
def place_packed_chain(cluster: ClusterArrays, i32buf, f32buf, u8buf,
                       spec, max_allocs: int, explain: bool = False):
    """Packed-transport chained placement (the SelectCoordinator's
    dispatch): one buffer per dtype class up, four small arrays down,
    instead of the ~40 per-leaf transfers of an unpacked batched
    TGParams (see pack_params; per-transfer cost on an attached chip is
    not measured). With
    `explain` the PlacementExplain leaves ride the SAME fetch, flattened
    after the four base outputs (every leaf gains a leading program
    axis from the chain scan)."""
    batch = _unpack_params(i32buf, f32buf, u8buf, spec)
    r = place_task_group_chain(cluster, batch, max_allocs, explain=explain)
    base = (r.sel_idx, r.sel_score, r.nodes_feasible, r.nodes_fit)
    if explain:
        return base + tuple(r.explain)
    return base


def _assemble_table_batch(ti, tf, tu, rows, di, df, du, sspec, dspec
                          ) -> TGParams:
    """Gather static rows from the device program table and unpack a
    batched TGParams: per-class whole-row `jnp.take` (embedding-style
    DMA, not an element gather), then [B, L*] class buffers →
    {field: [B, *shape]} via STATIC column slices (fuse to nothing
    under jit — the `_unpack_params` contract with a leading batch
    axis). Shared by the chain and wave table dispatches."""
    gi = jnp.take(ti, rows, axis=0)
    gf = jnp.take(tf, rows, axis=0)
    gu = jnp.take(tu, rows, axis=0)
    fields = {}
    sbufs = {"i": gi, "f": gf, "u": gu}
    for name, cls, off, shape in sspec:
        size = int(np.prod(shape)) if shape else 1
        seg = sbufs[cls][:, off:off + size]
        a = seg.reshape((seg.shape[0],) + tuple(shape))
        fields[name] = (a != 0) if cls == "u" else a
    dbufs = {"i": di, "f": df, "u": du}
    for name, cls, off, shape in dspec:
        size = int(np.prod(shape)) if shape else 1
        seg = dbufs[cls][:, off:off + size]
        a = seg.reshape((seg.shape[0],) + tuple(shape))
        fields[name] = (a != 0) if cls == "u" else a
    return TGParams(**fields)


@functools.partial(jax.jit,
                   static_argnames=("sspec", "dspec", "max_allocs",
                                    "explain"))
def place_table_chain(cluster: ClusterArrays, ti, tf, tu, rows,
                      di, df, du, sspec, dspec, max_allocs: int,
                      explain: bool = False):
    """Device-resident chained placement (ISSUE 10): the STATIC half of
    every program is a row of a persistent device table (ti/tf/tu, one
    per dtype class — server/program_table.py), so the dispatch ships
    only `rows` (i32[B] table indices) and the small DYNAMIC rows
    (di/df/du, [B, Ld*]) instead of whole packed programs.

    Assembly is a per-class ROW gather (`jnp.take` along the table axis
    — embedding-style whole-row DMA, not an element gather) followed by
    the static-offset unpack; both fuse into the chain compile. Returns
    the flat fetchable outputs (sel/score/feasible/fit [+ explain
    leaves]) plus the final (used, dyn_free) carry as DEVICE arrays —
    the carry never rides the host fetch; it is handed to the view
    cache for the device-to-device plan-delta update."""
    batch = _assemble_table_batch(ti, tf, tu, rows, di, df, du,
                                  sspec, dspec)
    r, carry = _chain_with_carry(cluster, batch, max_allocs,
                                 explain=explain)
    base = (r.sel_idx, r.sel_score, r.nodes_feasible, r.nodes_fit)
    if explain:
        base = base + tuple(r.explain)
    return base, carry


@functools.partial(jax.jit,
                   static_argnames=("sspec", "dspec", "max_allocs",
                                    "explain"))
def place_table_wave(cluster: ClusterArrays, ti, tf, tu, rows,
                     di, df, du, sspec, dspec, max_allocs: int,
                     explain: bool = False):
    """Wave-partitioned device-resident placement (ISSUE 12): the
    program axis arrives as LANES — `rows` i32[L, P] table indices and
    [L, P, Ld*] dynamic rows, one lane per set of conflict groups whose
    node footprints are DISJOINT from every other lane's (the broker's
    `dequeue_batch` partition). Each lane runs the same sequential
    conflict-aware chain as `place_table_chain` over its own programs;
    lanes run vmapped in parallel, so the serial scan length is the
    LONGEST LANE instead of the whole batch width — the chain no longer
    grows linearly with mega-batch size.

    Lane carries fold into ONE view carry by exact per-row lane
    selection: a row's final (used, dyn_free) comes VERBATIM from the
    single lane whose programs touched it (disjoint footprints ⇒ at most
    one lane per row), untouched rows keep the input view. Because a
    program only reads/writes rows inside its own footprint (its
    feasibility mask confines selection; its plan-relative deltas land
    on its own alloc rows), both the per-program outputs and the folded
    carry are BIT-IDENTICAL to the sequential chain whenever the
    footprint partition was truly disjoint (tests/test_drain.py pins
    this).

    Stale footprints (a node added between estimate and dispatch) can
    make two lanes touch one row anyway: the fold counts those
    CROSS-LANE COLLISION rows and returns the count as the LAST flat
    output. The host rejects the carry for such dispatches (the rows'
    true combined usage exists in no lane) and plan-apply per-node
    verification resolves any over-commit — the reference's optimistic
    worker race (plan_apply.go:437), never a silently wrong placement.

    Returns (flat outputs [L·P, ...] in lane-major order + the
    collision-count scalar, (used, dyn_free) device carry)."""
    def lane(rows_l, di_l, df_l, du_l):
        batch = _assemble_table_batch(ti, tf, tu, rows_l, di_l, df_l,
                                      du_l, sspec, dspec)
        return _chain_with_carry(cluster, batch, max_allocs,
                                 explain=explain)

    r, (used_l, dyn_l) = jax.vmap(lane)(rows, di, df, du)
    used0, dyn0 = cluster.used, cluster.dyn_free
    changed = jnp.any(used_l != used0[None], axis=-1) \
        | (dyn_l != dyn0[None])                              # [L, N]
    collisions = jnp.sum((jnp.sum(changed.astype(jnp.int32), axis=0)
                          > 1).astype(jnp.int32))
    used_f, dyn_f = used0, dyn0
    for l in range(rows.shape[0]):
        # static unroll of a where-select per lane: the chosen row is
        # copied BITWISE from its owning lane (no arithmetic fold — a
        # float re-accumulation would break carry == host-fold parity)
        m = changed[l]
        used_f = jnp.where(m[:, None], used_l[l], used_f)
        dyn_f = jnp.where(m, dyn_l[l], dyn_f)
    b = rows.shape[0] * rows.shape[1]

    def flat(x):
        return x.reshape((b,) + tuple(x.shape[2:]))

    base = (flat(r.sel_idx), flat(r.sel_score),
            flat(r.nodes_feasible), flat(r.nodes_fit))
    if explain:
        base = base + tuple(flat(leaf) for leaf in r.explain)
    return base + (collisions,), (used_f, dyn_f)


@functools.partial(jax.jit, static_argnames=("max_allocs", "explain"))
def place_task_group_batch(cluster: ClusterArrays, batch: TGParams,
                           max_allocs: int,
                           explain: bool = False) -> PlacementResult:
    """Batched placement: vmap over independent evaluations against one shared
    snapshot — the TPU analog of the reference's N scheduler workers racing on
    MVCC snapshots (`nomad/worker.go:105`); conflicts are resolved at
    plan-apply exactly as in the reference (`nomad/plan_apply.go:437`)."""
    fn = functools.partial(place_task_group, max_allocs=max_allocs,
                           explain=explain)
    return jax.vmap(fn, in_axes=(None, 0))(cluster, batch)


@jax.jit
def system_feasibility(cluster: ClusterArrays, p: TGParams
                       ) -> Tuple[jax.Array, jax.Array]:
    """System-scheduler masks: (constraint-feasible, feasible-and-fits) per
    node (reference `scheduler/system_sched.go:268` — per-node
    feasibility+fit, no ranking across nodes). The gap between the two masks
    is the preemption-candidate set."""
    feas_c = _lut_gather(p.lut, p.key_idx, cluster.attrs)
    feas = cluster.node_ok & p.extra_mask & jnp.all(feas_c, axis=1)
    used = cluster.used
    if p.delta_idx.shape[0]:
        n = used.shape[0]
        eq = (p.delta_idx[:, None] == jnp.arange(n)[None, :]
              ).astype(jnp.float32)
        used = used - jnp.einsum("dn,dr->nr", eq, p.delta_res,
                                 precision=_EXACT)
    util = used + p.ask[None, :]
    fits = jnp.all(util <= cluster.capacity, axis=1)
    fits = fits & (_dyn_free_adjusted(cluster, p) >= p.n_dyn) \
        & _reserved_ports_free(cluster, p)
    return feas, feas & fits
