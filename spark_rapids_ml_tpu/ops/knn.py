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


# observability for the pallas_knn=auto measured probe (the kNN analog of
# ops/umap.py LAST_KERNEL_DECISION, read by the tests): which
# kernel the last knn_topk_single dispatch used and the probe timings
# that decided it (None timings = no probe ran)
LAST_KERNEL_DECISION: dict = {
    "kernel": None,
    "decided_by": None,
    "warm_sec_xla": None,
    "warm_sec_pallas": None,
}

# measured verdicts keyed by (backend, bucket(n), bucket(q), d, k): the
# probe costs one extra compile + two timed evaluations per kernel, paid
# once per shape bucket, the same amortization shape_bucketing gives the
# kernels themselves
_KERNEL_DECISION_CACHE: dict = {}

# backends where pallas_knn=auto runs the measured probe; elsewhere auto
# keeps the XLA path outright (off-TPU the fused kernel would run the
# Pallas INTERPRETER — hours at benchmark sizes, never competitive).
# Tests monkeypatch this to probe on the CPU mesh at tiny shapes.
_AUTO_PROBE_BACKENDS = ("tpu",)


def _timed_topk(fn, items, item_valid, item_ids, queries, k):
    """One evaluation, synced by fetching the outputs: returns (seconds,
    outputs)."""
    import time

    import numpy as np

    t0 = time.perf_counter()
    out = fn(items, item_valid, item_ids, queries, k=k)
    np.asarray(out[0]), np.asarray(out[1])
    return time.perf_counter() - t0, out


def _measured_kernel_choice(items, item_valid, item_ids, queries, k: int):
    """The umap_kernel=auto probe discipline applied to the kNN dispatch
    (blanket-enabling the fused kernel measured 0.21-0.38x XLA on chip —
    an auto mode must measure, not assume): run each kernel cold (compile)
    + 2 warm, commit to the faster, cache per shape bucket.  Large query
    sets probe on a bounded `_QUERY_BLOCK` slice (both kernels scale
    linearly in q, so the slice discriminates at a bounded cost instead
    of paying ~6 full evaluations up front); when the full query set fits
    the probe, its evaluations compute REAL results and the winner's warm
    output is returned with no work wasted.  Returns (use_pallas,
    outputs|None); outputs is None on a cache hit or a sliced probe
    (the caller dispatches the winner over the full queries)."""
    from .pallas_knn import knn_topk_fused

    key = _decision_key(items, queries, k)
    cached = _KERNEL_DECISION_CACHE.get(key)
    if cached is not None:
        LAST_KERNEL_DECISION.update(
            kernel="pallas" if cached else "xla",
            decided_by="measured-cached",
            warm_sec_xla=None, warm_sec_pallas=None,
        )
        return cached, None
    full = int(queries.shape[0]) <= _QUERY_BLOCK
    probe_q = queries if full else queries[:_QUERY_BLOCK]
    t_x0, out = _timed_topk(
        knn_topk_blocked, items, item_valid, item_ids, probe_q, k
    )  # cold (compile)
    t_x1, out = _timed_topk(
        knn_topk_blocked, items, item_valid, item_ids, probe_q, k
    )
    t_x2, out = _timed_topk(
        knn_topk_blocked, items, item_valid, item_ids, probe_q, k
    )
    t_xla = min(t_x1, t_x2)
    try:
        _, out_p = _timed_topk(
            knn_topk_fused, items, item_valid, item_ids, probe_q, k
        )  # cold (compile)
        t_p1, out_p = _timed_topk(
            knn_topk_fused, items, item_valid, item_ids, probe_q, k
        )
        t_p2, out_p = _timed_topk(
            knn_topk_fused, items, item_valid, item_ids, probe_q, k
        )
        t_pallas = min(t_p1, t_p2)
    except Exception as e:  # Mosaic lowering/compile failure: XLA wins
        from ..utils import get_logger

        get_logger("knn").warning(
            f"fused Pallas kNN probe failed ({type(e).__name__}: "
            f"{str(e)[:200]}); committing to the XLA kernel"
        )
        _KERNEL_DECISION_CACHE[key] = False
        LAST_KERNEL_DECISION.update(
            kernel="xla", decided_by="pallas-error",
            warm_sec_xla=t_xla, warm_sec_pallas=None,
        )
        return False, (out if full else None)
    if abs(t_pallas - t_xla) < 0.1 * min(t_pallas, t_xla):
        # inside noise: the platform prior (XLA — measured faster at every
        # on-chip shape so far) breaks the tie the same way for every fit
        use_pallas, decided_by = False, "measured-tie-platform-prior"
    else:
        use_pallas = t_pallas < t_xla
        decided_by = "measured"
    _KERNEL_DECISION_CACHE[key] = use_pallas
    LAST_KERNEL_DECISION.update(
        kernel="pallas" if use_pallas else "xla", decided_by=decided_by,
        warm_sec_xla=t_xla, warm_sec_pallas=t_pallas,
    )
    if not full:
        return use_pallas, None
    return use_pallas, (out_p if use_pallas else out)


def _bucket(n: int) -> int:
    from ..parallel.mesh import bucket_rows

    return bucket_rows(max(int(n), 1))


def _decision_key(items, queries, k: int) -> tuple:
    """One shape-bucket cache key for the measured verdict — shared by the
    probe and the dispatch fallback so a runtime fused failure can
    overwrite the bucket's verdict.  `distance_precision` is part of the
    key: it retraces the XLA kernel's matmul (bf16 passes vs exact f32 —
    a measured speed gap, see bench knn_100kx64_xla_bf16pass_qps), so a
    verdict measured under one precision must not pin fits under the
    other."""
    from ..config import get_config

    return (
        jax.default_backend(),
        str(get_config("distance_precision", "highest")),
        _bucket(int(items.shape[0])),
        _bucket(int(queries.shape[0])),
        int(queries.shape[1]),
        int(k),
    )


def knn_topk_single(items, item_valid, item_ids, queries, k: int):
    """Single-device brute force with automatic kernel dispatch: the fused
    Pallas distance+top-k kernel (ops/pallas_knn.py) vs the XLA blocked
    kernel.  `pallas_knn="auto"` (default) MEASURES both once per shape
    bucket on probe backends and commits to the faster — the same
    discipline as `umap_kernel=auto`, so the default can never pin a fit
    to a slower kernel; "on" forces the fused kernel, "off" forces XLA.
    One owner for the decision — model/_search and umap_knn_graph both
    route through here."""
    from ..config import get_config
    from .pallas_knn import knn_topk_fused, pallas_knn_eligible

    mode = str(get_config("pallas_knn", "auto")).lower()
    d = int(queries.shape[1])
    # the probe's XLA reference is the blocked kernel; past the tile
    # budget that kernel would itself RESOURCE_EXHAUSTED (10M items x the
    # query block = a 40 GB tile), so auto skips the probe there and the
    # coltiled dispatch below runs outright
    qb = min(_QUERY_BLOCK, max(int(queries.shape[0]), 1))
    blocked_ok = (
        qb * int(items.shape[0]) * jnp.dtype(queries.dtype).itemsize
        <= _BLOCKED_TILE_LIMIT_BYTES
    )
    use_fused = False
    decided_by = "config"  # off / ineligible / auto on a non-probe backend
    if pallas_knn_eligible(d, queries.dtype) and mode != "off":
        if (
            mode == "auto" and blocked_ok
            and jax.default_backend() in _AUTO_PROBE_BACKENDS
        ):
            use_fused, out = _measured_kernel_choice(
                items, item_valid, item_ids, queries, k
            )
            if out is not None:  # probe ran: its warm outputs ARE results
                return out
            # a fresh sliced probe / cache hit already stamped
            # LAST_KERNEL_DECISION with the measured verdict — keep it
            decided_by = None
        elif mode == "on":
            use_fused, decided_by = True, "forced"
    if use_fused:
        try:
            if decided_by is not None:
                LAST_KERNEL_DECISION.update(
                    kernel="pallas", decided_by=decided_by,
                    warm_sec_xla=None, warm_sec_pallas=None,
                )
            return knn_topk_fused(items, item_valid, item_ids, queries, k=k)
        except Exception as e:
            if mode == "on":
                # forced means forced: the caller asked for THIS kernel,
                # and an XLA result under its name would hide that Mosaic
                # refused it
                raise
            # auto: a Mosaic lowering/compile failure at an untested
            # shape degrades to the XLA kernel, not a dead fit — the
            # kernels are exact-equivalent
            from ..utils import get_logger

            get_logger("knn").warning(
                f"fused Pallas kNN kernel failed ({type(e).__name__}: "
                f"{str(e)[:200]}); falling back to the XLA blocked kernel"
            )
            decided_by = "pallas-fallback"
            if mode == "auto":
                # overwrite the bucket's verdict: a probe won on the
                # bounded slice but the full-shape dispatch cannot
                # compile — without this every later call in the bucket
                # would re-pay the failed compile before falling back
                _KERNEL_DECISION_CACHE[_decision_key(items, queries, k)] = (
                    False
                )
    if decided_by is not None:
        LAST_KERNEL_DECISION.update(
            kernel="xla", decided_by=decided_by,
            warm_sec_xla=None, warm_sec_pallas=None,
        )
    # query-tiled blocked kernel while one (qblock, n) distance tile fits
    # comfortably; past that, the double-tiled kernel (exact-equivalent,
    # ~0.5x qps on chip but peak memory one (qblock, cblock) tile) — at
    # 10M items a single blocked tile is 1024 x 10M x f32 = 40 GB and
    # fails TPU compile with RESOURCE_EXHAUSTED (BASELINE-scale ANN run)
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
    widest sort is over cblock+k columns instead of n.  XLA's full-width
    top_k was measured as the dominant cost of `knn_topk_blocked` on the
    v5e (the Pallas experiment's conclusion, ops/pallas_knn.py); this is
    the sort-narrowing alternative at the XLA level — candidate default
    pending an on-chip comparison.
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
