#
# Sparse feature kernels — the TPU answer to the reference's CSR path
# (sparse LogisticRegressionMG, reference classification.py:960-966,
# 1054-1055; cupyx CSR staging core.py:852-957).  TPU/XLA has no cusparse:
# the natural accelerator layout is ELL — every row padded to the max
# per-row nnz, giving static-shape (N, K) value/column-id arrays that
# shard over the mesh like any dense matrix:
#
#   - X @ beta     = gather beta[cols] and contract over K (vectorized,
#                    no scatter); autodiff's transpose is the scatter-add
#                    X^T r, which XLA lowers efficiently and psums across
#                    shards exactly like the dense gradient.
#   - moments      = per-column segment sums over the (N*K,) flattened
#                    entries — zeros contribute nothing, so sparse moments
#                    are exact with no densification.
#
# ELL's cost is row-skew: K = max nnz/row.  The reference's CSR handles
# skew but pays irregular access; on the MXU the padded-regular layout wins
# for the near-uniform sparsity of the reference's benchmark datasets.
#
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def ell_from_csr(csr) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side CSR -> ELL: (values (n, K) float, cols (n, K) int32),
    padded with (0.0, col 0) entries which are no-ops in every kernel."""
    csr = csr.tocsr()
    if not csr.has_canonical_format:
        csr.sum_duplicates()
    n = csr.shape[0]
    lengths = np.diff(csr.indptr)
    K = max(int(lengths.max()) if n else 1, 1)
    vals = np.zeros((n, K), csr.data.dtype)
    cols = np.zeros((n, K), np.int32)
    mask = np.arange(K)[None, :] < lengths[:, None]
    vals[mask] = csr.data
    cols[mask] = csr.indices.astype(np.int32)
    return vals, cols


def ell_matvec(vals: jax.Array, cols: jax.Array, beta: jax.Array) -> jax.Array:
    """(N,) margins: sum_k vals[i,k] * beta[cols[i,k]]."""
    return (vals * jnp.take(beta, cols)).sum(axis=1)


def ell_matmat(vals: jax.Array, cols: jax.Array, W: jax.Array) -> jax.Array:
    """(N, C) margins for multinomial W (C, d): gather W.T rows.  At
    `highest`: at default precision a float32 contraction is ONE bfloat16
    pass on a TPU."""
    # W.T: (d, C); gathered (N, K, C)
    return jnp.einsum("nk,nkc->nc", vals, jnp.take(W.T, cols, axis=0),
                      precision=jax.lax.Precision.HIGHEST)


@partial(jax.jit, static_argnames=("d",))
def ell_weighted_moments(
    vals: jax.Array, cols: jax.Array, w: jax.Array, d: int
):
    """Per-column weighted (mean, std) over the sparse matrix — exact,
    because implicit zeros contribute zero to both sums."""
    wsum = w.sum()
    wv = vals * w[:, None]
    s1 = jnp.zeros((d,), vals.dtype).at[cols].add(wv)
    s2 = jnp.zeros((d,), vals.dtype).at[cols].add(wv * vals)
    mean = s1 / wsum
    # sum w (x - mean)^2 = s2 - wsum mean^2; ddof-1 scaling and the
    # zero-std guard match ops/stats.weighted_moments exactly
    ssq = jnp.maximum(s2 - wsum * mean * mean, 0.0)
    std = jnp.sqrt(ssq / jnp.maximum(wsum - 1.0, 1.0))
    std = jnp.where(std == 0.0, 1.0, std)
    return mean, std


@jax.jit
def ell_scale_columns(vals: jax.Array, cols: jax.Array, scale: jax.Array):
    """vals[i,k] * scale[cols[i,k]] — std-only standardization (no
    centering, preserving sparsity; Spark's aggregators standardize the
    same way)."""
    return vals * jnp.take(scale, cols)
