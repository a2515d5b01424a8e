#
# DBSCAN kernel — the TPU-native replacement for
# `cuml.cluster.dbscan_mg.DBSCANMG.fit_predict` (called from reference
# clustering.py:1058-1074).  The reference broadcasts the whole dataset to
# every GPU in <=8GB chunks (clustering.py:1104-1155) and runs a CSR/BFS
# cluster expansion; here the dataset is replicated per device (the same
# N x d memory contract), row *responsibility* is sharded, and cluster
# expansion is min-label connected components:
#
#   - Core detection: block distance passes per shard -> degree counts
#     (an MXU matmul via the ||a-b||^2 identity).
#   - Expansion: labels start as the global row index on core points.  Each
#     sweep takes, for every local row, the min label over its in-eps core
#     neighbors; a pointer-jumping step (label <- label[label]) collapses
#     chains so convergence is ~O(log N) sweeps instead of O(graph
#     diameter).  Labels are re-replicated after every sweep — N int32s
#     over ICI, negligible next to the distance pass.
#   - Border points attach to their minimum-label core neighbor after
#     convergence; everything else is noise (-1), matching
#     sklearn/cuML semantics (neighbor counts include the point itself).
#
# Dispatch structure: sweeps are driven FROM THE HOST — one compiled
# program per sweep (prep / sweep / border are separate dispatches), with
# the `changed` scalar fetched after each sweep as both the convergence
# decision and the sync point.  Per-sweep dispatch stops exactly at
# convergence instead of tracing the worst-case bound of a single
# all-sweeps while_loop program.  (It was first chosen to keep every
# program short for a development link that no longer exists; whether
# one program is faster on the chip is open — ROADMAP Design 2.)
#
# Memory contract: the peak per-device footprint is the replicated dataset
# (N x d, same as the reference's broadcast) plus ONE (m, block) distance
# tile.  For small problems (m*N under `_ADJ_BUDGET` elements) the in-eps
# adjacency could be materialized once; with host-driven sweeps the
# adjacency would have to be re-materialized or carried across dispatches,
# so every sweep recomputes distances tile-by-tile — the N^2/p adjacency
# never exists in memory, and the recompute is the same MXU matmul the
# dense path ran once (measured parity on the CPU mesh; the dense-path
# FLOP saving only ever applied below 64M-element adjacencies).
#
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import DATA_AXIS

# default per-device distance working-set BYTE budget (the models layer
# overrides it from `max_mbytes_per_batch`); bounds the column-tile width
_ADJ_BUDGET = 1 << 26
# column-tile width of the recompute path: one (m, _BLOCK) f32 tile
_BLOCK = 8192


def _sqdist(A: jax.Array, B: jax.Array) -> jax.Array:
    """Pairwise squared distances (shared rank-critical form)."""
    from .distances import sqdist

    return sqdist(A, B)


def _reduce_kernel(Xl, Xf, vf, labf, eps2, SENT, block):
    """Per-device: degree counts and min in-eps label over ALL columns,
    one (m, block) tile at a time.  labf/vf/Xf are full (replicated)."""
    m = Xl.shape[0]
    N = Xf.shape[0]
    blk = min(block, N)
    nb = -(-N // blk)
    Npad = nb * blk
    Xp = jnp.pad(Xf, ((0, Npad - N), (0, 0)))
    vp = jnp.pad(vf, (0, Npad - N))
    lp = jnp.pad(labf, (0, Npad - N), constant_values=SENT)

    def body(i, carry):
        deg, cand = carry
        o = jnp.asarray(i * blk, jnp.int32)
        Xb = jax.lax.dynamic_slice(
            Xp, (o, jnp.zeros((), jnp.int32)), (blk, Xp.shape[1])
        )
        vb = jax.lax.dynamic_slice(vp, (o,), (blk,))
        lb = jax.lax.dynamic_slice(lp, (o,), (blk,))
        d2 = _sqdist(Xl, Xb)
        adj = (d2 <= eps2) & (vb > 0)[None, :]
        # int32 accumulator: bool-sum defaults to int64 under x64
        deg = deg + adj.sum(axis=1).astype(jnp.int32)
        cand = jnp.minimum(
            cand, jnp.min(jnp.where(adj, lb[None, :], SENT), axis=1)
        )
        return deg, cand

    carry0 = jax.lax.pcast(
        (jnp.zeros((m,), jnp.int32), jnp.full((m,), SENT, jnp.int32)),
        (DATA_AXIS,),
        to="varying",
    )
    return jax.lax.fori_loop(0, nb, body, carry0)


@partial(jax.jit, static_argnames=("mesh",))
def _replicate(x, mesh=None):
    """One-shot replication of a sharded array (XLA inserts the
    all_gather): the dataset is gathered ONCE per fit, not per sweep."""
    from jax.sharding import NamedSharding

    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))


@partial(jax.jit, static_argnames=("mesh", "block"))
def _dbscan_prep(X_sharded, Xf, vf, valid_sharded, min_samples, eps,
                 mesh=None, block: int = _BLOCK):
    """One dispatch: degree pass -> (labels0, core_mask), both sharded.
    Xf/vf are the pre-replicated dataset/validity."""
    N = X_sharded.shape[0]
    SENT = jnp.int32(N)
    eps2 = eps * eps

    def kernel(Xl, Xf_, vf_, valid_l_f):
        m = Xl.shape[0]
        row0 = jax.lax.axis_index(DATA_AXIS) * m
        local_idx = row0 + jnp.arange(m, dtype=jnp.int32)
        deg, _ = _reduce_kernel(
            Xl, Xf_, vf_, jnp.full((N,), SENT, jnp.int32), eps2, SENT, block
        )
        core_l = (deg >= min_samples) & (valid_l_f > 0)
        labels0_l = jnp.where(core_l, local_idx, SENT)
        return labels0_l, core_l

    shard = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(), P(), P(DATA_AXIS)),
        out_specs=(P(DATA_AXIS), P(DATA_AXIS)),
    )
    return shard(X_sharded, Xf, vf, valid_sharded)


@partial(jax.jit, static_argnames=("mesh", "block", "border"))
def _dbscan_sweep(
    X_sharded, Xf, vf, core_f, valid_sharded, core_sharded, labels_sharded,
    eps, mesh=None, block: int = _BLOCK, border: bool = False,
):
    """One min-label propagation sweep (+ pointer jump), or — with
    `border=True` — the final border-attachment pass.  Xf/vf/core_f are
    pre-replicated; only the N int32 labels re-gather per sweep (the
    "negligible next to the distance pass" traffic of the header).
    Returns (labels (N_pad,) sharded, changed scalar)."""
    N = X_sharded.shape[0]
    SENT = jnp.int32(N)
    eps2 = eps * eps

    def kernel(Xl, Xf_, vf_, core_f_, valid_l_f, core_l, lab_l):
        Xf, vf, core_f = Xf_, vf_, core_f_
        labels = jax.lax.all_gather(lab_l, DATA_AXIS, tiled=True)
        core_lab = jnp.where(core_f, labels, SENT)  # only core labels spread
        _, cand = _reduce_kernel(Xl, Xf, vf, core_lab, eps2, SENT, block)
        if border:
            final_l = jnp.where(
                core_l, lab_l, jnp.where(cand < SENT, cand, jnp.int32(-1))
            )
            final_l = jnp.where(valid_l_f > 0, final_l, jnp.int32(-1))
            ch = jax.lax.pmax(
                jnp.any(final_l != lab_l).astype(jnp.int32), DATA_AXIS
            )
            return final_l, ch
        new_l = jnp.where(core_l, jnp.minimum(lab_l, cand), lab_l)
        new = jax.lax.all_gather(new_l, DATA_AXIS, tiled=True)
        # pointer jumping: follow the representative one hop
        safe = jnp.clip(new, 0, N - 1)
        hop = jnp.where(new < SENT, jnp.take(new, safe), SENT)
        new = jnp.minimum(new, hop)
        # pmax makes the exit flag provably replicated (out_specs P())
        changed = jax.lax.pmax(
            jnp.any(new != labels).astype(jnp.int32), DATA_AXIS
        )
        row0 = jax.lax.axis_index(DATA_AXIS) * Xl.shape[0]
        return jax.lax.dynamic_slice(new, (row0,), (Xl.shape[0],)), changed

    shard = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(), P(), P(), P(DATA_AXIS), P(DATA_AXIS),
                  P(DATA_AXIS)),
        out_specs=(P(DATA_AXIS), P()),
    )
    return shard(X_sharded, Xf, vf, core_f, valid_sharded, core_sharded,
                 labels_sharded)


def dbscan_fit_predict(
    X_sharded: jax.Array,  # (N_pad, d) rows sharded over DATA_AXIS
    valid_sharded: jax.Array,  # (N_pad,) validity, sharded
    eps: jax.Array,  # scalar
    min_samples: jax.Array,  # scalar int
    mesh=None,
    max_sweeps: int = 64,
    adj_budget: int = _ADJ_BUDGET,  # kept in the signature (models layer
    # passes the max_mbytes_per_batch cap); tiles are bounded by `block`
    block: int = _BLOCK,
):
    """Returns (labels (N_pad,) int32 row-sharded, core_mask (N_pad,) bool).

    Labels are min-row-index cluster representatives; -1 is noise.  The API
    layer renumbers to consecutive ids on the host (the reference's labels
    come back from rank 0 the same way, clustering.py:1160-1182).  Sweeps
    are host-dispatched; the fetched `changed` scalar is the loop exit.
    """
    import numpy as np

    # honor the working-set cap by shrinking the column tile: adj_budget
    # is a BYTE budget (models layer maps max_mbytes_per_batch to bytes)
    # and the recompute tile is f32, so the tile width is budget/4/m rows
    # (floor-divided — never exceed the cap; floor 8 keeps degenerate caps
    # runnable and an explicitly smaller caller `block` is respected)
    m_local = int(X_sharded.shape[0]) // max(int(mesh.devices.size), 1)
    if m_local > 0:
        block = min(block, max(8, (adj_budget // 4) // m_local))
    Xf = _replicate(X_sharded, mesh=mesh)
    vf = _replicate(valid_sharded, mesh=mesh)
    labels, core = _dbscan_prep(
        X_sharded, Xf, vf, valid_sharded, min_samples, eps,
        mesh=mesh, block=block,
    )
    core_f = _replicate(core, mesh=mesh)
    for _ in range(max_sweeps):
        labels, changed = _dbscan_sweep(
            X_sharded, Xf, vf, core_f, valid_sharded, core, labels, eps,
            mesh=mesh, block=block,
        )
        if not bool(np.asarray(changed)):  # fetch = sync + exit decision
            break
    labels, _ = _dbscan_sweep(
        X_sharded, Xf, vf, core_f, valid_sharded, core, labels, eps,
        mesh=mesh, block=block, border=True,
    )
    return labels, core
