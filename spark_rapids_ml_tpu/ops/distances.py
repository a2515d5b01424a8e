#
# Generic distance metrics for kNN-graph construction — the TPU answer to
# cuML's metric zoo (reference umap.py:203-212 lists the UMAP-supported
# metrics; cuVS brute force implements them natively).  Two kernel kinds:
#
#   - "matmul" metrics reduce to squared euclidean after a row transform
#     (normalize for cosine, center+normalize for correlation, sqrt for
#     hellinger) and ride the MXU identity `||a-b||^2 = a^2 - 2ab + b^2` —
#     these stay on the existing fast kernels (ops/knn.py).
#   - "elementwise" metrics (manhattan, chebyshev, canberra, minkowski,
#     hamming) have no matmul form; `knn_topk_metric` computes them in
#     (query_block, item_block) tiles with a running top-k merge so peak
#     memory is one tile, never (q, n, d).
#
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .precision import distance_precision

# ---------------------------------------------------------------------------
# Shared squared-euclidean forms (matmul identity), routed through
# `distance_precision()` so the rank-critical kernels (kNN/ANN/DBSCAN)
# change precision in one place.  Consolidated here from the old
# one-kernel-pair `ops/distance.py` (now a deprecation shim): ONE module
# owns every distance form.
# ---------------------------------------------------------------------------


def sqdist(
    Q: jax.Array,  # (q, d)
    X: jax.Array,  # (m, d)
    q2: Optional[jax.Array] = None,  # (q, 1) optional precomputed norms
    x2: Optional[jax.Array] = None,  # (m,)
) -> jax.Array:
    """(q, m) squared euclidean distances, clamped at 0."""
    if q2 is None:
        q2 = (Q * Q).sum(axis=1, keepdims=True)
    if x2 is None:
        x2 = (X * X).sum(axis=1)
    d2 = q2 - 2.0 * jnp.matmul(Q, X.T, precision=distance_precision()) + x2
    return jnp.maximum(d2, 0.0)


def sqdist_gathered(
    B: jax.Array,  # (r, d) one vector per row
    Xc: jax.Array,  # (r, C, d) gathered candidates per row
    b2: jax.Array,  # (r,) row-vector norms
    c2: jax.Array,  # (r, C) candidate norms
) -> jax.Array:
    """(r, C) squared euclidean distances row-vs-its-candidates, clamped
    at 0 — the gathered-candidate form used by IVF probing and the CAGRA
    build/search."""
    dot = jnp.einsum("rd,rcd->rc", B, Xc, precision=distance_precision())
    return jnp.maximum(b2[:, None] - 2.0 * dot + c2, 0.0)

MATMUL_METRICS = {
    "euclidean", "l2", "sqeuclidean", "cosine", "correlation", "hellinger",
}
ELEMENTWISE_METRICS = {
    "manhattan", "l1", "cityblock", "taxicab", "chebyshev", "linf",
    "canberra", "minkowski", "hamming", "jaccard",
}
SUPPORTED_METRICS = MATMUL_METRICS | ELEMENTWISE_METRICS


def metric_kind(metric: str) -> str:
    if metric in MATMUL_METRICS:
        return "matmul"
    if metric in ELEMENTWISE_METRICS:
        return "elementwise"
    raise ValueError(
        f"metric '{metric}' is not supported; choose from "
        + ", ".join(sorted(SUPPORTED_METRICS))
    )


def preprocess_rows(X, metric: str):
    """Host-side row transform that maps a matmul-family metric onto plain
    euclidean distance of the transformed rows."""
    import numpy as np

    X = np.asarray(X)
    if metric == "cosine":
        return X / np.maximum(np.linalg.norm(X, axis=1, keepdims=True), 1e-12)
    if metric == "correlation":
        Xc = X - X.mean(axis=1, keepdims=True)
        return Xc / np.maximum(np.linalg.norm(Xc, axis=1, keepdims=True), 1e-12)
    if metric == "hellinger":
        if (X < 0).any():
            raise ValueError("hellinger requires non-negative features")
        # ||sqrt(x)-sqrt(y)|| / sqrt(2): fold the 1/sqrt(2) into the rows
        return np.sqrt(X) / np.sqrt(2.0)
    return X


def finalize_sqdist(d2, metric: str):
    """Squared-euclidean kernel output -> the metric's reported distance.

    NOTE: cosine/correlation report 1-cos (the cuVS convention) as of
    round 3; earlier UMAP models were fitted on the chord scale
    sqrt(2·(1-cos)) — refit cosine models rather than transforming old
    ones through the new convention."""
    if metric == "sqeuclidean":
        return d2
    if metric == "cosine":
        # unit rows: 1 - cos = ||u-v||^2 / 2 (the cuVS cosine convention)
        return d2 / 2.0
    if metric == "correlation":
        return d2 / 2.0
    # euclidean / l2 / hellinger (1/sqrt(2) already folded into the rows)
    return jnp.sqrt(jnp.maximum(d2, 0.0))


def _pairwise_elementwise(Qb, Xb, metric: str, p: float):
    """(qb, mb) distances from (qb, d) x (mb, d), one broadcast tile."""
    diff = Qb[:, None, :] - Xb[None, :, :]  # (qb, mb, d)
    if metric in ("manhattan", "l1", "cityblock", "taxicab"):
        return jnp.abs(diff).sum(axis=2)
    if metric in ("chebyshev", "linf"):
        return jnp.abs(diff).max(axis=2)
    if metric == "canberra":
        denom = jnp.abs(Qb)[:, None, :] + jnp.abs(Xb)[None, :, :]
        return jnp.where(denom > 0, jnp.abs(diff) / jnp.maximum(denom, 1e-30),
                         0.0).sum(axis=2)
    if metric == "minkowski":
        s = (jnp.abs(diff) ** p).sum(axis=2)
        return s ** (1.0 / p)
    if metric == "hamming":
        return (Qb[:, None, :] != Xb[None, :, :]).mean(axis=2).astype(Qb.dtype)
    if metric == "jaccard":
        # binarized set distance 1 - |x & y| / |x | y| (the cuML metric is
        # sparse-input-only, reference umap.py:1145-1146; the tiled dense
        # kernel here serves dense AND chunk-densified sparse rows).  Two
        # all-zero rows are at distance 0, matching scipy/umap-learn.
        # One 3-D reduction: union derives from the 2-D per-row nonzero
        # counts as nnz(q) + nnz(x) - inter.
        qa = Qb != 0
        xa = Xb != 0
        inter = (qa[:, None, :] & xa[None, :, :]).sum(axis=2).astype(Qb.dtype)
        union = (
            qa.sum(axis=1).astype(Qb.dtype)[:, None]
            + xa.sum(axis=1).astype(Qb.dtype)[None, :]
            - inter
        )
        return jnp.where(union > 0, 1.0 - inter / jnp.maximum(union, 1.0),
                         0.0)
    raise ValueError(f"not an elementwise metric: {metric}")


@partial(
    jax.jit,
    static_argnames=("k", "metric", "p", "qblock", "iblock", "pcast_axis"),
)
def knn_topk_metric(
    items: jax.Array,  # (n, d)
    item_valid: jax.Array,  # (n,)
    item_ids: jax.Array,  # (n,)
    queries: jax.Array,  # (q, d)
    k: int,
    metric: str,
    p: float = 2.0,
    qblock: int = 512,
    iblock: int = 2048,
    pcast_axis: Optional[str] = None,  # set when called inside shard_map
) -> Tuple[jax.Array, jax.Array]:
    """Brute-force kNN under an elementwise metric, (query x item)-tiled:
    peak memory is one (qblock, iblock, d) broadcast tile.  Returns final
    (distances (q, k), ids (q, k)), best first; padded items never appear
    (distance +inf, tail ids -1 when k exceeds the valid count)."""
    from .knn import _merge_topk

    q, d = queries.shape
    n = items.shape[0]
    qblock = min(qblock, q)
    iblock = min(iblock, n)
    nqb = -(-q // qblock)
    nib = -(-n // iblock)
    Qp = jnp.pad(queries, ((0, nqb * qblock - q), (0, 0)))
    Xp = jnp.pad(items, ((0, nib * iblock - n), (0, 0)))
    vp = jnp.pad(item_valid, (0, nib * iblock - n))
    idp = jnp.pad(item_ids, (0, nib * iblock - n), constant_values=-1)

    def one_qblock(b):
        # uniform int32 indices (python-int literals trace int64 once a
        # prior fit enabled x64)
        qoff = (b * qblock).astype(jnp.int32)
        Qb = jax.lax.dynamic_slice(
            Qp, (qoff, jnp.zeros((), jnp.int32)), (qblock, d)
        )

        def one_iblock(i, carry):
            run_d, run_i = carry
            ioff = (i * iblock).astype(jnp.int32)
            Xb = jax.lax.dynamic_slice(
                Xp, (ioff, jnp.zeros((), jnp.int32)), (iblock, d)
            )
            vb = jax.lax.dynamic_slice(vp, (ioff,), (iblock,))
            ib = jax.lax.dynamic_slice(idp, (ioff,), (iblock,))
            dist = _pairwise_elementwise(Qb, Xb, metric, p)
            dist = jnp.where(vb[None, :] > 0, dist, jnp.inf)
            return _merge_topk(run_d, run_i, dist, ib[None, :], k)

        run_d = jnp.full((qblock, k), jnp.inf, Qp.dtype)
        run_i = jnp.full((qblock, k), -1, item_ids.dtype)
        if pcast_axis is not None:
            # under shard_map the merged carry becomes device-varying; the
            # init must match (the ops/knn.py ring does the same)
            run_d = jax.lax.pcast(run_d, (pcast_axis,), to="varying")
            run_i = jax.lax.pcast(run_i, (pcast_axis,), to="varying")
        return jax.lax.fori_loop(0, nib, one_iblock, (run_d, run_i))

    ds, ids = jax.lax.map(one_qblock, jnp.arange(nqb, dtype=jnp.int32))
    return ds.reshape(nqb * qblock, k)[:q], ids.reshape(nqb * qblock, k)[:q]


def umap_knn_graph(
    X_items,
    item_valid,
    item_ids,
    queries,
    k: int,
    metric: str,
    p: float = 2.0,
    mesh=None,
):
    """Metric-dispatching kNN used by the UMAP fit/transform: matmul-family
    metrics ride the euclidean kernels (callers pre-transform rows with
    `preprocess_rows`), elementwise metrics the tiled kernel — sharded over
    queries with replicated items when a multi-device mesh is given.
    Returns FINAL distances (not squared) + ids."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS
    from .knn import knn_ring_topk, knn_topk_single

    if metric_kind(metric) == "matmul":
        if mesh is not None and mesh.devices.size > 1:
            d2, ids = knn_ring_topk(
                X_items, item_valid, item_ids, queries, k=k, mesh=mesh
            )
        else:
            d2, ids = knn_topk_single(
                X_items, item_valid, item_ids, queries, k=k
            )
        return finalize_sqdist(d2, metric), ids
    if mesh is not None and mesh.devices.size > 1:
        kernel = jax.shard_map(
            lambda xi, vi, ii, qs: knn_topk_metric(
                xi, vi, ii, qs, k=k, metric=metric, p=p,
                pcast_axis=DATA_AXIS,
            ),
            mesh=mesh,
            in_specs=(P(None), P(None), P(None), P(DATA_AXIS)),
            out_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        )
        return kernel(X_items, item_valid, item_ids, queries)
    return knn_topk_metric(
        X_items, item_valid, item_ids, queries, k=k, metric=metric, p=p
    )
