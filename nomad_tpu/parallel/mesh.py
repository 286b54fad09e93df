"""Device mesh + sharding layout for the scheduling kernels.

The reference scales scheduling with N worker goroutines racing on MVCC
snapshots (`nomad/server.go:1419`, `nomad/worker.go:105`) and bounds per-eval
work with log₂(n) candidate sampling (`scheduler/stack.go:77-89`). The TPU
build replaces both with SPMD over a 2-D mesh:

  axis "batch" — independent pending evaluations (the domain's data
                 parallelism; the broker already serializes per-JobID,
                 `nomad/structs/structs.go:9524`, so a dequeued batch is safe)
  axis "nodes" — the cluster's node axis (the domain's sequence/context
                 parallelism; full-width masks instead of sampling)

Shardings are annotated with `jax.sharding.NamedSharding`; XLA GSPMD inserts
the collectives (the global argmax over the sharded node axis becomes a
local argmax + all-reduce over ICI).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels.placement import ClusterArrays, TGParams, place_task_group
from ..utils import bucket as _bucket, widen_lut as _widen_v

BATCH_AXIS = "batch"
NODE_AXIS = "nodes"

#: process-wide mesh the LIVE control plane shards cluster uploads over
#: (None = single-device dispatch). Set by Server from config/env; read by
#: TPUStack.device_arrays so the code the workers run is the code the
#: multichip dryrun proves (SURVEY §2.7).
#:
#: Deliberately a process singleton rather than a per-Server field: the
#: dispatch layer (TPUStack) is constructed per-eval from snapshots that
#: carry no server reference, and the devices being meshed are a process
#: resource anyway — two servers in one process sharding differently
#: over the same chips has no sensible semantics. A mesh-owning Server
#: uninstalls its mesh on shutdown (server.py); servers with mesh=None
#: never touch the global.
_ACTIVE_MESH: Optional[Mesh] = None


def set_active_mesh(mesh: Optional[Mesh]) -> None:
    """Install (or clear, with None) the control plane's device mesh."""
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def get_active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH


def mesh_from_env() -> Optional[Mesh]:
    """Build a mesh from NOMAD_TPU_MESH: unset/"0"/"1" → None (single
    device), "auto" → all visible devices, an integer → that many."""
    import os

    spec = os.environ.get("NOMAD_TPU_MESH", "").strip().lower()
    if spec in ("", "0", "1", "off", "none"):
        return None
    if spec == "auto":
        n = None
    else:
        try:
            n = int(spec)
        except ValueError:
            raise ValueError(
                f"NOMAD_TPU_MESH={spec!r}: must be an integer device "
                f"count, 'auto', or unset/'off'") from None
    if n is not None and n <= 1:
        return None
    return make_mesh(n)

# TGParams no longer carries node-width per-eval vectors: job counts ship
# sparse (jc_idx/jc_val) and the host-check mask is width-1 when trivial.
# Params are therefore replicated across the node ring; only the cluster
# snapshot is sharded along NODE_AXIS (GSPMD broadcasts the mask AND).
_NODE_AXIS_FIELDS = frozenset()


def make_mesh(n_devices: Optional[int] = None,
              batch: Optional[int] = None) -> Mesh:
    """Build a ("batch", "nodes") mesh over the first `n_devices` devices."""
    devices = jax.devices()
    n = len(devices) if n_devices is None else n_devices
    if n > len(devices):
        raise ValueError(
            f"mesh of {n} devices requested, {len(devices)} "
            f"{devices[0].platform} device(s) present")
    devices = devices[:n]
    if batch is None:
        # Node-axis size must divide the cluster row bucket (a power of two ≥
        # 64), so give NODE_AXIS the largest power-of-two divisor of n and put
        # the remainder on the eval-batch axis; with a pure power of two,
        # still keep a batch axis of 2 to exercise both parallelism forms.
        node = 1
        while n % (node * 2) == 0:
            node *= 2
        batch = n // node
        if batch == 1 and node >= 4:
            batch = 2
    assert n % batch == 0, f"{n} devices not divisible by batch={batch}"
    nodes_dim = n // batch
    assert nodes_dim & (nodes_dim - 1) == 0, (
        f"node axis {nodes_dim} must be a power of two to divide row buckets"
    )
    arr = np.asarray(devices).reshape(batch, nodes_dim)
    return Mesh(arr, (BATCH_AXIS, NODE_AXIS))


def cluster_sharding(mesh: Mesh) -> ClusterArrays:
    """Shardings for the cluster snapshot: node axis split over NODE_AXIS,
    replicated over the eval batch."""
    row = NamedSharding(mesh, P(NODE_AXIS))
    mat = NamedSharding(mesh, P(NODE_AXIS, None))
    return ClusterArrays(capacity=mat, used=mat, node_ok=row, attrs=mat,
                         ports_used=mat, dyn_free=row)


def params_sharding(mesh: Mesh, batched: bool = True) -> TGParams:
    """Shardings for (batched) TGParams: batch axis over BATCH_AXIS; the three
    node-axis vectors additionally split over NODE_AXIS; everything else
    replicated across the node ring."""
    lead = (BATCH_AXIS,) if batched else ()
    out = {}
    for name in TGParams._fields:
        if name in _NODE_AXIS_FIELDS:
            spec = P(*lead, NODE_AXIS)
        else:
            spec = P(*lead)
        out[name] = NamedSharding(mesh, spec)
    return TGParams(**out)


def shard_cluster(arrays: ClusterArrays, mesh: Mesh) -> ClusterArrays:
    from ..lib.hbm import default_hbm
    from ..lib.transfer import default_ledger

    shardings = cluster_sharding(mesh)
    # .nbytes reads metadata on numpy AND jax arrays — np.asarray here
    # would round-trip device-resident inputs through the host just to
    # size them, adding exactly the traffic this ledger exists to expose
    nb = sum(a.nbytes for a in arrays)
    with default_ledger().timed("mesh.shard_cluster", nb,
                                count=len(arrays)):
        out = ClusterArrays(
            *[jax.device_put(a, s) for a, s in zip(arrays, shardings)]
        )
    # residency ledger: book the sharded snapshot per device shard (the
    # ledger splits a sharded array by addressable_shards), with the
    # node-axis length so the capacity planner can price a node row
    hbm = default_hbm()
    for a in out:
        hbm.track("mesh.cluster", a, rows=int(a.shape[0]))
    return out


def _pad_rows(a: np.ndarray, n: int, fill) -> np.ndarray:
    """Pad axis 0 to n rows with a constant."""
    if a.shape[0] == n:
        return a
    pad = np.full((n - a.shape[0],) + a.shape[1:], fill, dtype=a.dtype)
    return np.concatenate([a, pad], axis=0)


#: pad_params dims that shape STATIC program fields (the LUT block the
#: device program table holds per job spec); everything else shapes only
#: per-eval dynamic rows and is free to vary per dispatch.
STATIC_DIMS = ("v", "c", "a_n", "e_n", "s_n", "dp_n", "rp_n")


def param_dims(params_list: Sequence[TGParams]) -> dict:
    """Bucketed common shape dims a set of programs needs (the pad_params
    targets, exposed so the device program table can hold shape FLOORS
    stable across dispatches — shape churn is compile churn)."""
    ps = [TGParams(*[np.asarray(x) for x in p]) for p in params_list]
    return {
        "v": _bucket(max(max(p.lut.shape[1] if p.lut.size else 2,
                             p.aff_lut.shape[1] if p.aff_lut.size else 2,
                             p.spread_desired.shape[1]) for p in ps), lo=2),
        "c": _bucket(max(p.key_idx.shape[0] for p in ps)),
        "a_n": _bucket(max(p.aff_key_idx.shape[0] for p in ps)),
        "m": _bucket(max(p.penalty_idx.shape[0] for p in ps)),
        "p_n": _bucket(max(p.penalty_idx.shape[1] for p in ps)),
        "d_n": _bucket(max(p.delta_idx.shape[0] for p in ps)),
        "s_n": _bucket(max(p.spread_key_idx.shape[0] for p in ps)),
        "j_n": _bucket(max(p.jc_idx.shape[0] for p in ps)),
        "j2_n": _bucket(max(p.jtc_idx.shape[0] for p in ps)),
        "e_n": max(p.extra_mask.shape[0] for p in ps),
        "l_n": _bucket(max(p.cand_idx.shape[0] for p in ps)),
        "dp_n": _bucket(max(p.dp_key_idx.shape[0] for p in ps)),
        "rp_n": _bucket(max(p.res_ports.shape[0] for p in ps)),
        "pc_n": _bucket(max(p.pclr_idx.shape[0] for p in ps)),
        "pst_n": _bucket(max(p.pset_idx.shape[0] for p in ps)),
    }


def pad_params(params_list: Sequence[TGParams],
               dims: Optional[dict] = None,
               need: Optional[dict] = None
               ) -> Tuple[Tuple[TGParams, ...], int]:
    """Bucket-pad heterogeneous per-eval placement programs to common shapes
    so they batch along one leading axis (SURVEY §7 hard-part (d): variable
    shapes → bucketed padding + masking, avoiding recompiles).

    Padding is semantically inert: extra constraint rows are all-true LUTs,
    extra affinity/spread rows carry zero weight / inactive flags, extra
    penalty/preferred/delta rows are −1 (dropped scatters), and extra scan
    steps sit beyond `n_place`. `dims` (optional) sets per-dim FLOORS —
    the program table passes its running caps so the padded shapes (and
    therefore the packed row layout + the chain's XLA compile) stay
    identical across dispatches; `need` short-circuits the dim
    computation when the caller already ran param_dims on the same list
    (the program table's ceiling check). Returns (padded params, common
    scan length)."""
    ps = [TGParams(*[np.asarray(x) for x in p]) for p in params_list]
    need = dict(need) if need is not None else param_dims(ps)
    if dims:
        for k, floor in dims.items():
            if k in need:
                need[k] = max(need[k], floor)
    v, c, a_n, m = need["v"], need["c"], need["a_n"], need["m"]
    p_n, d_n, s_n = need["p_n"], need["d_n"], need["s_n"]
    j_n, j2_n, e_n = need["j_n"], need["j2_n"], need["e_n"]
    l_n, dp_n, rp_n = need["l_n"], need["dp_n"], need["rp_n"]
    pc_n, pst_n = need["pc_n"], need["pst_n"]

    out = []
    for p in ps:
        lut = _pad_rows(_widen_v(p.lut, v, False) if p.lut.size
                        else np.zeros((0, v), np.bool_), c, True)
        key_idx = _pad_rows(p.key_idx, c, 0)
        aff_lut = _pad_rows(_widen_v(p.aff_lut, v, 0.0) if p.aff_lut.size
                            else np.zeros((0, v), np.float32), a_n, 0.0)
        aff_key_idx = _pad_rows(p.aff_key_idx, a_n, 0)
        pen = _pad_rows(p.penalty_idx, m, -1)
        if pen.shape[1] != p_n:
            wide = np.full((m, p_n), -1, dtype=pen.dtype)
            wide[:, : pen.shape[1]] = pen
            pen = wide
        out.append(p._replace(
            extra_mask=_pad_rows(p.extra_mask, e_n, True),
            key_idx=key_idx, lut=lut,
            aff_key_idx=aff_key_idx, aff_lut=aff_lut,
            penalty_idx=pen,
            preferred_idx=_pad_rows(p.preferred_idx, m, -1),
            jc_idx=_pad_rows(p.jc_idx, j_n, -1),
            jc_val=_pad_rows(p.jc_val, j_n, 0.0),
            jtc_idx=_pad_rows(p.jtc_idx, j2_n, -1),
            jtc_val=_pad_rows(p.jtc_val, j2_n, 0.0),
            cand_idx=_pad_rows(p.cand_idx, l_n, -1),
            res_ports=_pad_rows(p.res_ports, rp_n, -1),
            pclr_idx=_pad_rows(p.pclr_idx, pc_n, -1),
            pclr_port=_pad_rows(p.pclr_port, pc_n, -1),
            pset_idx=_pad_rows(p.pset_idx, pst_n, -1),
            pset_port=_pad_rows(p.pset_port, pst_n, -1),
            dp_key_idx=_pad_rows(p.dp_key_idx, dp_n, 0),
            dp_allowed=_pad_rows(p.dp_allowed, dp_n, 0.0),
            dp_counts0=_pad_rows(_widen_v(p.dp_counts0, v, 0.0), dp_n, 0.0),
            dp_active=_pad_rows(p.dp_active, dp_n, False),
            delta_idx=_pad_rows(p.delta_idx, d_n, -1),
            delta_res=_pad_rows(p.delta_res, d_n, 0.0),
            spread_key_idx=_pad_rows(p.spread_key_idx, s_n, 0),
            spread_weight=_pad_rows(p.spread_weight, s_n, 0.0),
            spread_has_targets=_pad_rows(p.spread_has_targets, s_n, False),
            spread_desired=_pad_rows(_widen_v(p.spread_desired, v, -1.0),
                                     s_n, -1.0),
            spread_counts0=_pad_rows(_widen_v(p.spread_counts0, v, 0.0),
                                     s_n, 0.0),
            spread_active=_pad_rows(p.spread_active, s_n, False),
        ))
    return tuple(out), m


def stack_params(params_list: Sequence[TGParams]) -> Tuple[TGParams, int]:
    """Bucket-pad then stack per-eval TGParams along a new batch axis.
    Returns (batched params, common max_allocs scan length)."""
    padded, m = pad_params(params_list)
    batched = jax.tree_util.tree_map(
        lambda *xs: np.stack(xs), *padded
    )
    return batched, m


def _batch_place(cluster: ClusterArrays, batch: TGParams, max_allocs: int):
    fn = functools.partial(place_task_group, max_allocs=max_allocs)
    return jax.vmap(fn, in_axes=(None, 0))(cluster, batch)


def place_batch_sharded(mesh: Mesh, max_allocs: int):
    """A jitted batched placement dispatch with mesh shardings annotated on
    the inputs; XLA GSPMD partitions the scan body and inserts the argmax
    all-reduce over the node ring."""
    in_shardings = (cluster_sharding(mesh), params_sharding(mesh, batched=True))
    return jax.jit(
        functools.partial(_batch_place, max_allocs=max_allocs),
        in_shardings=in_shardings,
    )


def _step(cluster: ClusterArrays, batch: TGParams, max_allocs: int):
    """One full scheduler step: batched placement + state fold-in.

    The fold-in (sum of per-eval used deltas) is the device-side analog of
    the leader's plan-apply commit (`nomad/plan_apply.go:204`): each eval's
    placements consume capacity in the shared snapshot for the next round.
    Conflicts (overcommit) are detected host-side exactly as the reference's
    `evaluateNodePlan` does; this step only advances the optimistic view.
    """
    result = _batch_place(cluster, batch, max_allocs)
    delta = jnp.sum(result.new_used - cluster.used[None, :, :], axis=0)
    new_cluster = cluster._replace(used=cluster.used + delta)
    return new_cluster, result


def scheduler_step(mesh: Mesh, max_allocs: int):
    """Jitted full step (placement + snapshot advance) under mesh shardings.
    This is the function `__graft_entry__.dryrun_multichip` compiles."""
    cs = cluster_sharding(mesh)
    in_shardings = (cs, params_sharding(mesh, batched=True))
    return jax.jit(
        functools.partial(_step, max_allocs=max_allocs),
        in_shardings=in_shardings,
        out_shardings=(cs, None),
    )
