#
# Exact k-NN kernel — the TPU-native replacement for
# `cuml.neighbors.nearest_neighbors_mg.NearestNeighborsMG.kneighbors`
# (called from reference knn.py:688-779), whose hot loop exchanges item
# blocks between ranks over UCX p2p and brute-force top-k's on GPU.
#
# Design notes (TPU-first):
#   - Both item rows and query rows are sharded over the mesh's data axis.
#   - A ring of `ppermute` steps rotates each item shard (rows + global ids
#     + validity) around the mesh; every device folds each visiting block
#     into a running per-query top-k.  This is the ICI-native analog of the
#     UCX endpoint mesh: O(N/p) peak memory per device, bandwidth-optimal,
#     and the distance matmul (MXU) overlaps with the permute collective.
#   - The block distance computation is one X_q @ X_i^T matmul via the
#     ||a-b||^2 identity; the top-k merge concatenates the running (q,k)
#     state with the (q,m) block and runs lax.top_k — no sorting networks,
#     no dynamic shapes.
#   - Distances are computed in the input dtype (f32) and returned as
#     *squared* euclidean; the API layer takes sqrt on the host to match
#     the reference's euclidean output (knn.py:768-779).
#
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import DATA_AXIS


def _block_sqdist(Q: jax.Array, X: jax.Array) -> jax.Array:
    """(q, m) squared euclidean distances via the matmul identity."""
    from .distances import sqdist

    return sqdist(Q, X)


def _merge_topk(run_d, run_i, blk_d, blk_i, k: int):
    """Fold a (q, m) distance block into the running (q, k) top-k state."""
    cat_d = jnp.concatenate([run_d, blk_d], axis=1)
    cat_i = jnp.concatenate([run_i, jnp.broadcast_to(blk_i, blk_d.shape)], axis=1)
    neg_d, pos = jax.lax.top_k(-cat_d, k)
    return -neg_d, jnp.take_along_axis(cat_i, pos, axis=1)


@partial(jax.jit, static_argnames=("k", "mesh"))
def knn_ring_topk(
    items: jax.Array,  # (N_pad, d) rows sharded over DATA_AXIS
    item_valid: jax.Array,  # (N_pad,) 1.0 real / 0.0 pad, sharded
    item_ids: jax.Array,  # (N_pad,) int32 global ids, sharded
    queries: jax.Array,  # (Q_pad, d) rows sharded over DATA_AXIS
    k: int,
    mesh=None,
):
    """Distributed brute-force k nearest neighbors.

    Returns (sq_distances (Q_pad, k), ids (Q_pad, k)) sharded like queries.
    Invalid (padding) items never appear in results (their distance is +inf);
    if k exceeds the number of valid items the tail ids are -1.
    """
    n_shards = mesh.devices.size
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def kernel(Xi, vi, ids, Xq):
        q = Xq.shape[0]
        # pcast marks the top-k carry as device-varying over the mesh axis so
        # the while-loop carry type stays stable across ppermute steps
        run_d = jax.lax.pcast(
            jnp.full((q, k), jnp.inf, Xq.dtype), (DATA_AXIS,), to="varying"
        )
        run_i = jax.lax.pcast(
            jnp.full((q, k), -1, ids.dtype), (DATA_AXIS,), to="varying"
        )

        def body(step, carry):
            run_d, run_i, blk_x, blk_v, blk_id = carry
            d2 = _block_sqdist(Xq, blk_x)
            d2 = jnp.where(blk_v[None, :] > 0, d2, jnp.inf)
            run_d, run_i = _merge_topk(run_d, run_i, d2, blk_id[None, :], k)
            blk_x = jax.lax.ppermute(blk_x, DATA_AXIS, perm)
            blk_v = jax.lax.ppermute(blk_v, DATA_AXIS, perm)
            blk_id = jax.lax.ppermute(blk_id, DATA_AXIS, perm)
            return run_d, run_i, blk_x, blk_v, blk_id

        run_d, run_i, _, _, _ = jax.lax.fori_loop(
            0, n_shards, body, (run_d, run_i, Xi, vi, ids)
        )
        return run_d, run_i

    shard = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(DATA_AXIS), P(DATA_AXIS)),
    )
    return shard(items, item_valid, item_ids, queries)


@partial(jax.jit, static_argnames=("k",))
def knn_topk_local(items, item_valid, item_ids, queries, k: int):
    """Single-device brute force (used for num_workers=1 and by UMAP's
    local kNN-graph build).  Materializes the full (q, n) distance block —
    callers with large q*n should use `knn_topk_blocked`."""
    d2 = _block_sqdist(queries, items)
    d2 = jnp.where(item_valid[None, :] > 0, d2, jnp.inf)
    neg_d, pos = jax.lax.top_k(-d2, k)
    # invalid items surface as id -1 (the documented k > n_valid contract)
    masked_ids = jnp.where(item_valid > 0, item_ids, -1)
    return -neg_d, jnp.take(masked_ids, pos)


# default query-block rows shared by knn_topk_blocked/coltiled and the
# dispatch's tile-size model in knn_topk_single — one constant so a
# retune can't desynchronize the guard from the kernel
_QUERY_BLOCK = 1024
# one (qblock, n) blocked-kernel distance tile must leave room for the
# item matrix itself in 16 GB HBM; 2 GiB keeps the faster blocked kernel
# for everything up to ~500k items at the default query block
_BLOCKED_TILE_LIMIT_BYTES = 2 << 30


def knn_topk_single(items, item_valid, item_ids, queries, k: int):
    """Single-device brute force: the query-tiled blocked kernel while one
    (qblock, n) distance tile fits comfortably; past that, the
    double-tiled kernel (exact-equivalent, ~0.5x qps on chip but peak
    memory one (qblock, cblock) tile) — at 10M items a single blocked
    tile is 1024 x 10M x f32 = 40 GB and fails TPU compile with
    RESOURCE_EXHAUSTED (BASELINE-scale ANN run).  One owner for the
    choice — model/_search and umap_knn_graph both route through here."""
    n = int(items.shape[0])
    qb = min(_QUERY_BLOCK, max(int(queries.shape[0]), 1))
    tile_bytes = qb * n * jnp.dtype(queries.dtype).itemsize
    if tile_bytes > _BLOCKED_TILE_LIMIT_BYTES:
        return knn_topk_coltiled(items, item_valid, item_ids, queries, k=k)
    return knn_topk_blocked(items, item_valid, item_ids, queries, k=k)


@partial(jax.jit, static_argnames=("k", "block"))
def knn_topk_blocked(items, item_valid, item_ids, queries, k: int,
                     block: int = _QUERY_BLOCK):
    """Brute force with the query axis tiled: peak memory is one
    (block, n) distance tile instead of (q, n) — the single-device analog
    of the reference's batched GPU brute force (cuML handles this blocking
    inside NearestNeighborsMG; at q = n = 100k an unblocked (q, n) tile
    would be 40 GB and exceed HBM)."""
    q, d = queries.shape
    block = min(block, q)  # small batches pay for their own rows only
    nb = -(-q // block)
    qpad = nb * block
    Qp = jnp.pad(queries, ((0, qpad - q), (0, 0)))

    masked_ids = jnp.where(item_valid > 0, item_ids, -1)

    def one(b):
        # uniform int32 indices (a literal 0 traces int64 once x64 is on)
        Qb = jax.lax.dynamic_slice(
            Qp, (b * block, jnp.zeros((), jnp.int32)), (block, d)
        )
        d2 = _block_sqdist(Qb, items)
        d2 = jnp.where(item_valid[None, :] > 0, d2, jnp.inf)
        neg_d, pos = jax.lax.top_k(-d2, k)
        return -neg_d, jnp.take(masked_ids, pos)

    ds, ids = jax.lax.map(one, jnp.arange(nb, dtype=jnp.int32))
    return ds.reshape(qpad, k)[:q], ids.reshape(qpad, k)[:q]


@partial(jax.jit, static_argnames=("k", "block", "cblock"))
def knn_topk_coltiled(items, item_valid, item_ids, queries, k: int,
                      block: int = _QUERY_BLOCK, cblock: int = 8192):
    """Brute force with BOTH axes tiled: each (block, cblock) distance
    tile folds into a running (block, k) top-k via `_merge_topk`, so the
    widest sort is over cblock+k columns instead of n — the
    sort-narrowing alternative to `knn_topk_blocked`'s full-width top_k,
    and the dispatch's kernel past the blocked tile limit.
    Exact-equivalent to `knn_topk_blocked`."""
    q, d = queries.shape
    n = items.shape[0]
    block = min(block, q)
    cb = min(cblock, n)
    ncb = -(-n // cb)
    npad = ncb * cb
    Xp = jnp.pad(items, ((0, npad - n), (0, 0)))
    vp = jnp.pad(item_valid, (0, npad - n))
    ip = jnp.pad(item_ids, (0, npad - n), constant_values=-1)
    nb = -(-q // block)
    qpad = nb * block
    Qp = jnp.pad(queries, ((0, qpad - q), (0, 0)))

    def one(b):
        Qb = jax.lax.dynamic_slice(
            Qp, (b * block, jnp.zeros((), jnp.int32)), (block, d)
        )

        def fold(j, carry):
            run_d, run_i = carry
            o = jnp.asarray(j * cb, jnp.int32)
            Xb = jax.lax.dynamic_slice(
                Xp, (o, jnp.zeros((), jnp.int32)), (cb, d)
            )
            vb = jax.lax.dynamic_slice(vp, (o,), (cb,))
            ib = jax.lax.dynamic_slice(ip, (o,), (cb,))
            d2 = _block_sqdist(Qb, Xb)
            d2 = jnp.where(vb[None, :] > 0, d2, jnp.inf)
            return _merge_topk(run_d, run_i, d2, ib[None, :], k)

        run_d = jnp.full((block, k), jnp.inf, queries.dtype)
        run_i = jnp.full((block, k), -1, item_ids.dtype)
        return jax.lax.fori_loop(0, ncb, fold, (run_d, run_i))

    ds, ids = jax.lax.map(one, jnp.arange(nb, dtype=jnp.int32))
    return ds.reshape(qpad, k)[:q], ids.reshape(qpad, k)[:q]
