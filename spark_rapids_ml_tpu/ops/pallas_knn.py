#
# Fused distance + top-k Pallas kernel — the TPU-native replacement for the
# materialize-then-select brute force (`ops/knn.py knn_topk_blocked`, the
# analog of cuML's batched GPU brute force inside NearestNeighborsMG,
# reference knn.py:688-779).
#
# Why a kernel at all: XLA compiles `matmul -> top_k` as two fusions with
# the full (q_block, n) squared-distance tile round-tripping through HBM
# between them (sort-based top_k cannot fuse into the matmul).  At kNN
# scale that intermediate is the dominant HBM traffic: q*n*4 bytes written
# + read again, vs q*d + n*d useful input bytes.  This kernel tiles
# (queries x items) over a Pallas grid, keeps a running per-query top-k in
# VMEM scratch across the item-tile sweep, and writes only the final
# (q, k) result to HBM — the same streaming-selection structure cuVS's
# fusedL2Knn CUDA kernel uses, recast on the MXU/VPU:
#
#   - the -2*Q@X^T term rides the MXU (jax.lax.dot_general, f32);
#   - ||x||^2 arrives precomputed as a (1, n) row so the per-tile score is
#     one broadcast add (the per-query ||q||^2 constant does not affect
#     ranking and is added back outside the kernel);
#   - selection is k rounds of (min, first-argmin-by-iota, mask) over the
#     (BQ, k + BN) concat of [running state | tile scores] on the VPU —
#     no sort networks, no gathers, every op a lane-wise reduction;
#   - grid iteration order (last axis fastest) makes the item sweep
#     innermost, so the scratch state carries across item tiles and
#     re-initializes per query tile via pl.when(j == 0).
#
# The kernel is exact in interpret mode (the CPU tests) and is
# dispatched behind the `pallas_knn` config flag: "off" (default), "auto"
# (real TPU backends), "on" (everywhere; tests run it in interpret mode
# on CPU).
#
# MEASURED OUTCOME (v5e, 100k items x 10k queries x d=64 x k=32, f32;
# PR 21's chip run on jax 0.9.0 / libtpu 0.0.34): Mosaic compiles the
# kernel (9.1 s cold) and it runs, 0.590 s warm against 0.122 s for the
# XLA blocked kernel — 4.8x SLOWER, the third round to measure it losing.
# The premise that the (q, n) HBM round-trip dominates was wrong at these
# shapes: XLA's top_k is the bottleneck on both paths, and its sort-based
# selection on (block, n) tiles beats this kernel's k-round VPU
# min/argmin sweep.  It is also NOT exact on the chip: the in-kernel
# dot_general takes no precision argument, so it runs one bf16 pass where
# the XLA path runs `distance_precision` (exact f32) — the same run gave
# 85.1 % neighbour-id agreement with the XLA result and squared distances
# off by up to 0.21.  The XLA path stays the default; ROADMAP Design 5
# schedules this file's deletion.
#
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BIG_F32 = 3.0e38  # "+inf" stand-in that survives arithmetic (python float:
# a jnp scalar would be a captured constant inside the pallas kernel)


def _fused_kernel(k: int, bq: int, bn: int):
    def kernel(x2_ref, v_ref, q_ref, x_ref, outd_ref, outi_ref,
               rund_ref, runi_ref):
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            rund_ref[:] = jnp.full((bq, k), _BIG_F32, jnp.float32)
            runi_ref[:] = jnp.full((bq, k), -1, jnp.int32)

        Q = q_ref[:]  # (bq, d)
        X = x_ref[:]  # (bn, d)
        # score = ||x||^2 - 2 q.x  (ranking-equivalent to the squared
        # euclidean distance; ||q||^2 is added back outside)
        qx = jax.lax.dot_general(
            Q, X,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, bn)
        score = x2_ref[:] - 2.0 * qx  # (1, bn) broadcasts over rows
        score = jnp.where(v_ref[:] > 0, score, _BIG_F32)

        # union of [running top-k | this tile], then k selection rounds
        cat_d = jnp.concatenate([rund_ref[:], score], axis=1)  # (bq, k+bn)
        tile_ids = j * bn + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bn), dimension=1
        )
        cat_i = jnp.concatenate([runi_ref[:], tile_ids], axis=1)
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, k + bn), dimension=1)
        ncol = jnp.int32(k + bn)
        for t in range(k):
            m = jnp.min(cat_d, axis=1, keepdims=True)  # (bq, 1)
            hit1 = cat_d == m
            pos = jnp.min(jnp.where(hit1, col, ncol), axis=1, keepdims=True)
            hit = col == pos  # exactly one True per row (first minimum)
            # ids are >= -1, so a masked max extracts the hit id exactly
            sel = jnp.max(jnp.where(hit, cat_i, -1), axis=1, keepdims=True)
            exhausted = m >= _BIG_F32  # fewer than k valid items
            rund_ref[:, t : t + 1] = m
            runi_ref[:, t : t + 1] = jnp.where(exhausted, -1, sel)
            cat_d = jnp.where(hit, _BIG_F32, cat_d)

        outd_ref[:] = rund_ref[:]
        outi_ref[:] = runi_ref[:]

    return kernel


@partial(jax.jit, static_argnames=("k", "bq", "bn", "interpret"))
def fused_topk_sqdist(
    items: jax.Array,  # (n, d) f32
    item_valid: jax.Array,  # (n,) 1.0 real / 0.0 pad
    queries: jax.Array,  # (q, d) f32
    k: int,
    bq: int = 256,
    bn: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Exact brute-force kNN, fused in one Pallas kernel.

    Returns (squared distances (q, k), POSITIONAL item indices (q, k)),
    best first; invalid/padded items never appear (+inf distance, index
    -1 past the valid count).  Callers map positions to global ids.
    """
    q, d = queries.shape
    n = items.shape[0]
    bq = min(bq, max(8, q))
    nqt = -(-q // bq)
    nnt = -(-n // bn)
    Qp = jnp.pad(queries.astype(jnp.float32), ((0, nqt * bq - q), (0, 0)))
    Xp = jnp.pad(items.astype(jnp.float32), ((0, nnt * bn - n), (0, 0)))
    vp = jnp.pad(item_valid.astype(jnp.float32), (0, nnt * bn - n))
    x2 = (jnp.sum(Xp * Xp, axis=1) * jnp.where(vp > 0, 1.0, 0.0)).reshape(
        1, -1
    )
    v2 = vp.reshape(1, -1)

    grid = (nqt, nnt)
    outd, outi = pl.pallas_call(
        _fused_kernel(k, bq, bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),  # x2
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),  # valid
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),  # queries
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),  # items
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nqt * bq, k), jnp.float32),
            jax.ShapeDtypeStruct((nqt * bq, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, k), jnp.float32),
            pltpu.VMEM((bq, k), jnp.int32),
        ],
        interpret=interpret,
    )(x2, v2, Qp, Xp)

    # add back the per-query ||q||^2 the kernel dropped; +inf tails stay
    q2 = jnp.sum(Qp * Qp, axis=1, keepdims=True)
    d2 = jnp.where(outd >= _BIG_F32, jnp.inf, jnp.maximum(outd + q2, 0.0))
    return d2[:q], outi[:q]


def pallas_knn_eligible(d: int, dtype=None) -> bool:
    """SHAPE/DTYPE eligibility for the fused kernel, independent of the
    config mode: very wide rows fall back (the (bq + bn) x d tiles must
    fit VMEM next to the selection temps), and so do non-f32 inputs — the
    kernel computes in f32, which would silently change the f64 results
    the XLA path preserves under float32_inputs=False."""
    if d > 4096:
        return False
    return dtype is None or jnp.dtype(dtype) == jnp.float32


def knn_topk_fused(items, item_valid, item_ids, queries, k: int):
    """Drop-in for `knn_topk_blocked`: fused kernel + global-id mapping."""
    interpret = jax.default_backend() != "tpu"
    d2, pos = fused_topk_sqdist(
        items, item_valid, queries, k, interpret=interpret
    )
    ids = jnp.where(
        pos >= 0, jnp.take(item_ids, jnp.maximum(pos, 0), axis=0), -1
    )
    return d2, ids
