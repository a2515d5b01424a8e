#
# IVF (inverted-file) approximate nearest neighbor kernels — the TPU-native
# replacement for the cuVS index build/search calls
# (`cuvs.neighbors.{ivf_flat,ivf_pq}` used at reference knn.py:1516-1657).
#
# Design notes (TPU-first):
#   - Build: the coarse quantizer is our own distributed k-means
#     (ops/kmeans.py) over the sharded rows; assignments come from one more
#     MXU pass.  Bucketization into the padded (nlist, max_bucket) inverted
#     file is a host-side argsort — build is host-orchestrated exactly like
#     the reference's index build, and runs once per fit.
#   - Search: queries are row-sharded over the mesh (inference data
#     parallelism); the inverted file is replicated.  Per query block the
#     nprobe nearest lists are gathered into a dense (q, nprobe·max_bucket)
#     candidate matrix — a static-shape gather + one batched matmul, which
#     is exactly the memory/compute trade XLA tiles well onto the MXU.
#     (The reference shards the index and broadcasts queries,
#     knn.py:1448-1470; with a single controller the inverse layout avoids
#     the global top-k merge entirely while keeping the same IVF recall
#     semantics.)
#   - IVF-PQ: product-quantization codebooks trained per subspace with the
#     same k-means kernel; search uses asymmetric distance computation
#     (per-query lookup tables, one gather + segment sum per candidate).
#
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .distances import sqdist, sqdist_gathered
from .precision import distance_precision
import numpy as np


class IVFFlatIndex(NamedTuple):
    """Inverted file with oversized lists split into capped SUB-LISTS:
    `centers` stays the (nlist, d) coarse parents a query probes;
    `sub_table[p]` names the sub-lists storing parent p's rows (-1 pad).
    Padding is bounded at ~cap x nsub ~= 1.25x the data instead of
    nlist x max_count (one hot list made the padded file ~15 GB at
    10M x 128 on a 16 GB chip)."""

    centers: np.ndarray  # (nlist, d) coarse PARENT centroids
    buckets: np.ndarray  # (nsub, cap, d) capped sub-list vectors
    bucket_ids: np.ndarray  # (nsub, cap) int32 positional item ids, -1 pad
    bucket_valid: np.ndarray  # (nsub, cap) 1.0 real / 0.0 pad
    sub_table: np.ndarray  # (nlist, max_sub) int32 sub-list ids, -1 pad


def _quantizer_train_rows(n: int, nlist: int) -> int:
    """Coarse-quantizer training-set size: bounded like cuVS ivf_flat's
    sampled trainset (its kmeans_trainset_fraction default trains on a
    fraction, not all rows) — full data at small n, 256 rows/list capped
    at n for BASELINE-scale builds where kmeans over all rows would
    materialize an (n, nlist) distance block (40 GB at 10M x 1024)."""
    return min(n, max(nlist * 256, 16384))


def _assign_chunked(X: np.ndarray, centers) -> np.ndarray:
    """kmeans_predict over bounded row chunks: the per-chunk device
    footprint is chunk x (k + d) f32 — the (chunk, k) distance block PLUS
    the staged (chunk, d) rows themselves — bounded to ~1 GiB, and the
    per-chunk host->device transfer additionally capped at the single-put
    ceiling (mesh._MAX_PUT_BYTES)."""
    from ..parallel.mesh import _MAX_PUT_BYTES
    from .kmeans import kmeans_predict

    n = X.shape[0]
    k = int(centers.shape[0])
    d = int(X.shape[1])
    itemsize = 4  # rows stage f32
    chunk = int(max(8192, min(
        n,
        (1 << 28) // max(k + d, 1),
        _MAX_PUT_BYTES // max(d * itemsize, 1),
    )))
    out = np.empty((n,), np.int32)
    for at in range(0, n, chunk):
        out[at : at + chunk] = np.asarray(
            kmeans_predict(jnp.asarray(X[at : at + chunk]), centers)
        )
    return out


def _train_kmeans_budgeted(Xtr, k: int, seed: int, max_iter: int,
                           init: str = "k-means++"):
    """Quantizer/codebook kmeans through the shared fused-vs-stepwise
    dispatch gate (ops/kmeans.py kmeans_fit_auto — one cost model with
    the KMeans model, so the per-program FLOP budget cannot diverge
    between the two training paths)."""
    from .kmeans import kmeans_fit_auto

    w = jnp.ones((int(Xtr.shape[0]),), jnp.float32)
    centers, _, _, _ = kmeans_fit_auto(
        Xtr, w, k=k, seed=seed, max_iter=max_iter, tol=1e-4, init=init
    )
    return centers


def build_ivfflat(
    X: np.ndarray, nlist: int, seed: int = 42, kmeans_iters: int = 20
) -> IVFFlatIndex:
    """Train the coarse quantizer and assemble the padded inverted file."""
    X = np.ascontiguousarray(X, dtype=np.float32)
    n = X.shape[0]
    from ..parallel.mesh import _chunked_device_put

    n_train = _quantizer_train_rows(n, nlist)
    if n_train < n:
        sel = np.random.default_rng(seed).choice(n, size=n_train,
                                                 replace=False)
        Xtr = _chunked_device_put(np.ascontiguousarray(X[sel]))
    else:
        Xtr = _chunked_device_put(X)
    centers = _train_kmeans_budgeted(Xtr, nlist, seed, kmeans_iters)
    assign = _assign_chunked(X, centers)
    centers = np.asarray(centers)
    order = np.argsort(assign, kind="stable")
    counts = np.bincount(assign, minlength=nlist)
    # oversized lists split into capped sub-lists (see IVFFlatIndex):
    # probing stays over the nlist PARENT centers, and the search
    # expands each probed parent to its sub-lists via sub_table — the
    # probe top-k therefore still covers nprobe DISTINCT coarse cells
    # (duplicated sub-centers in the probe would let one hot cell crowd
    # every other cell out of the top-k on exactly the skewed data the
    # split targets)
    d = X.shape[1]
    n_mean = max(int(np.ceil(n / max(nlist, 1))), 1)
    cap = max(32, int(np.ceil(1.25 * n_mean)))
    # empty coarse lists get NO sub-list (an all -1 sub_table row, which
    # the search fold masks) — at high nlist with skew, a zero sub-list
    # per empty cell would waste cap x d x 4 bytes each
    sub_of = [
        (lst, at) for lst in range(nlist)
        for at in range(0, int(counts[lst]), cap)
    ]
    nsub = max(len(sub_of), 1)
    max_sub = max(int((-(-counts // cap)).max()), 1) if nlist else 1
    sub_table = np.full((nlist, max_sub), -1, np.int32)
    buckets = np.zeros((nsub, cap, d), np.float32)
    bucket_ids = np.full((nsub, cap), -1, np.int32)
    bucket_valid = np.zeros((nsub, cap), np.float32)
    starts = np.concatenate([[0], np.cumsum(counts)])
    fill = np.zeros((nlist,), np.int64)
    for s, (lst, at) in enumerate(sub_of):
        sub_table[lst, fill[lst]] = s
        fill[lst] += 1
        c = min(cap, int(counts[lst]) - at)
        if c <= 0:
            continue
        idx = order[starts[lst] + at : starts[lst] + at + c]
        buckets[s, :c] = X[idx]
        bucket_ids[s, :c] = idx.astype(np.int32)
        bucket_valid[s, :c] = 1.0
    return IVFFlatIndex(centers, buckets, bucket_ids, bucket_valid, sub_table)


@partial(jax.jit, static_argnames=("nprobe", "k"))
def search_ivfflat(
    queries: jax.Array,  # (q, d)
    centers: jax.Array,  # (nlist, d) parent centroids
    buckets: jax.Array,  # (nsub, cap, d) sub-list vectors
    bucket_ids: jax.Array,  # (nsub, cap)
    bucket_valid: jax.Array,  # (nsub, cap)
    sub_table: jax.Array,  # (nlist, max_sub) sub-list ids, -1 pad
    nprobe: int,
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Probe the nprobe nearest PARENT cells per query (distinct coarse
    cells, as in the unsplit inverted file), expand each to its
    sub-lists via `sub_table`, and fold ONE sub-list per step into a
    running top-k: peak memory is a single (q, cap, d) gather instead
    of (q, nprobe, mb, d).  The all-at-once gather is tens of GB at
    BASELINE scale (10M items -> mb ~ 10-20k, nprobe 64) and does not
    compile at 10M items; the fold visits the
    same candidates with identical distances.  Returns
    (sq_distances (q,k), ids (q,k), -1 = none)."""
    qn = queries.shape[0]
    cap = buckets.shape[1]
    max_sub = sub_table.shape[1]
    q2 = (queries * queries).sum(axis=1, keepdims=True)
    dc = sqdist(queries, centers, q2=q2)  # (q, nlist)
    _, probe = jax.lax.top_k(-dc, nprobe)  # (q, nprobe) parent ids
    # (q, nprobe*max_sub) sub-list ids, front-packed DESCENDING so the
    # -1 padding sinks to the tail; the fold then runs only to the
    # batch-max count of real sub-lists instead of nprobe*max_sub — on
    # skewed data most fixed steps would gather fully-masked padding
    nsteps = nprobe * max_sub
    expanded = -jnp.sort(
        -jnp.take(sub_table, probe, axis=0).reshape(qn, -1), axis=1
    )
    n_live = jnp.max(jnp.sum(expanded >= 0, axis=1))

    kk = min(k, nsteps * cap)

    def fold(r, carry):
        run_d, run_i = carry
        lists = expanded[:, r]  # (q,) sub-list ids, may be -1
        safe = jnp.maximum(lists, 0)
        cx = jnp.take(buckets, safe, axis=0)  # (q, cap, d)
        cid = jnp.take(bucket_ids, safe, axis=0)  # (q, cap)
        cv = jnp.take(bucket_valid, safe, axis=0)  # (q, cap)
        cv = cv * (lists >= 0)[:, None]
        x2 = (cx * cx).sum(axis=2)
        d2 = sqdist_gathered(queries, cx, q2[:, 0], x2)  # (q, cap)
        d2 = jnp.where(cv > 0, d2, jnp.inf)
        cat_d = jnp.concatenate([run_d, d2], axis=1)
        cat_i = jnp.concatenate([run_i, cid], axis=1)
        neg_d, pos = jax.lax.top_k(-cat_d, kk)
        return -neg_d, jnp.take_along_axis(cat_i, pos, axis=1)

    run_d = jnp.full((qn, kk), jnp.inf, queries.dtype)
    run_i = jnp.full((qn, kk), -1, bucket_ids.dtype)
    # traced upper bound: lowers to a while_loop running exactly the
    # batch's live steps
    dist, ids = jax.lax.fori_loop(0, n_live, fold, (run_d, run_i))
    if kk < k:  # fewer candidates than k: pad with inf/-1
        pad = k - kk
        dist = jnp.concatenate(
            [dist, jnp.full((qn, pad), jnp.inf, dist.dtype)], axis=1
        )
        ids = jnp.concatenate([ids, jnp.full((qn, pad), -1, ids.dtype)], axis=1)
    # mark unreachable slots (inf distance) as id -1
    ids = jnp.where(jnp.isinf(dist), -1, ids)
    return dist, ids


class IVFPQIndex(NamedTuple):
    centers: np.ndarray  # (nlist, d) coarse PARENT centroids
    codebooks: np.ndarray  # (M, ksub, dsub) per-subspace codebooks
    codes: np.ndarray  # (nsub, cap, M) uint8 PQ codes of residuals
    bucket_ids: np.ndarray  # (nsub, cap) int32
    bucket_valid: np.ndarray  # (nsub, cap)
    sub_table: np.ndarray  # (nlist, max_sub) int32 sub-list ids, -1 pad


def build_ivfpq(
    X: np.ndarray,
    nlist: int,
    M: int = 8,
    n_bits: int = 8,
    seed: int = 42,
    kmeans_iters: int = 20,
) -> IVFPQIndex:
    """IVF-PQ build: coarse quantizer + per-subspace residual codebooks
    (the cuVS ivf_pq analog, reference knn.py:1581-1612)."""
    X = np.ascontiguousarray(X, dtype=np.float32)
    n, d = X.shape
    if d % M != 0:
        raise ValueError(f"feature dim {d} not divisible by pq M={M}")
    dsub = d // M
    ksub = min(2**n_bits, max(n // 4, 2))
    flat = build_ivfflat(X, nlist, seed=seed, kmeans_iters=kmeans_iters)
    nsub = flat.buckets.shape[0]  # sub-lists after oversize splitting
    assign = np.full((n,), 0, np.int64)  # sub-list id per row
    for lst in range(nsub):
        ids = flat.bucket_ids[lst][flat.bucket_valid[lst] > 0]
        assign[ids] = lst
    # map each sub-list back to its parent cell: residuals (and the
    # search's LUTs) are against the PARENT coarse center
    parent_of = np.zeros((nsub,), np.int64)
    for p in range(flat.sub_table.shape[0]):
        for s in flat.sub_table[p]:
            if s >= 0:
                parent_of[s] = p
    resid = X - flat.centers[parent_of[assign]]
    # codebooks train on the same bounded sample policy as the coarse
    # quantizer; codes assign in bounded chunks (an (n, ksub) block is
    # 10 GB at 10M x 256)
    n_train = _quantizer_train_rows(n, ksub)
    tr = (np.random.default_rng(seed + 7).choice(n, size=n_train,
                                                 replace=False)
          if n_train < n else slice(None))
    codebooks = np.zeros((M, ksub, dsub), np.float32)
    codes = np.zeros((n, M), np.uint8)
    from ..parallel.mesh import _chunked_device_put

    for m in range(M):
        sub = resid[:, m * dsub : (m + 1) * dsub]
        cb = _train_kmeans_budgeted(
            _chunked_device_put(np.ascontiguousarray(sub[tr])),
            ksub, seed + m + 1, kmeans_iters,
        )
        codebooks[m] = np.asarray(cb)
        codes[:, m] = _assign_chunked(
            np.ascontiguousarray(sub), jnp.asarray(codebooks[m])
        ).astype(np.uint8)
    mb = flat.bucket_ids.shape[1]
    bucket_codes = np.zeros((nsub, mb, M), np.uint8)
    for lst in range(nsub):
        mask = flat.bucket_valid[lst] > 0
        bucket_codes[lst, mask] = codes[flat.bucket_ids[lst][mask]]
    return IVFPQIndex(flat.centers, codebooks, bucket_codes, flat.bucket_ids,
                      flat.bucket_valid, flat.sub_table)


@partial(jax.jit, static_argnames=("nprobe", "k"))
def search_ivfpq(
    queries: jax.Array,  # (q, d)
    centers: jax.Array,  # (nlist, d) parent centroids
    codebooks: jax.Array,  # (M, ksub, dsub)
    codes: jax.Array,  # (nsub, cap, M) uint8
    bucket_ids: jax.Array,  # (nsub, cap)
    bucket_valid: jax.Array,  # (nsub, cap)
    sub_table: jax.Array,  # (nlist, max_sub)
    nprobe: int,
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """ADC search: per (query, probed cell) distance lookup tables over
    the residual codebooks, summed across subspaces per candidate code.
    Probes parent cells and folds ONE sub-list per step (same rationale
    and structure as `search_ivfflat`): peak memory one (q, cap, M)
    code gather + the precomputed (q, nprobe, M, ksub) LUT block instead
    of the nprobe-times-larger all-at-once candidate forms.

    The ADC LUT depends only on the (query, probed PARENT) pair, and a
    parent contributes up to `max_sub` fold steps — so the LUTs are
    computed ONCE per probed parent up front and each step just indexes
    its parent's slice by the parent's probe RANK (carried through the
    front-packing permutation), instead of re-running the
    (q, M, dsub) x (M, ksub, dsub) einsum every step."""
    M, ksub, dsub = codebooks.shape
    qn, d = queries.shape
    max_sub = sub_table.shape[1]
    q2 = (queries * queries).sum(axis=1, keepdims=True)
    dc = sqdist(queries, centers, q2=q2)  # (q, nlist)
    _, probe = jax.lax.top_k(-dc, nprobe)  # (q, nprobe) parent ids
    expanded = jnp.take(sub_table, probe, axis=0).reshape(qn, -1)
    # each step needs its parent's LUT slice: the parent probe RANK
    # (0..nprobe-1), aligned with `expanded` before the permutation
    ranks = jnp.broadcast_to(
        jnp.repeat(jnp.arange(nprobe, dtype=jnp.int32), max_sub)[None, :],
        (qn, nprobe * max_sub),
    )
    nsteps = nprobe * max_sub
    # front-pack real sub-lists (same rationale as search_ivfflat),
    # carrying the aligned parent ranks through the same permutation
    ordr = jnp.argsort(-expanded, axis=1)
    expanded = jnp.take_along_axis(expanded, ordr, axis=1)
    ranks = jnp.take_along_axis(ranks, ordr, axis=1)
    n_live = jnp.max(jnp.sum(expanded >= 0, axis=1))

    cb2 = (codebooks * codebooks).sum(axis=2)  # (M, ksub)
    cap = codes.shape[1]
    kk = min(k, nsteps * cap)

    # per-parent residuals and LUTs, once for the whole fold loop:
    # ||r_m - c_{m,j}||^2 for each probed parent and subspace code j
    resid_all = (
        queries[:, None, :] - jnp.take(centers, probe, axis=0)
    )  # (q, nprobe, d)
    resid_sub_all = resid_all.reshape(qn, nprobe, M, dsub)
    dot_all = jnp.einsum(
        "qpmd,mjd->qpmj", resid_sub_all, codebooks,
        precision=distance_precision(),
    )
    r2_all = (resid_sub_all * resid_sub_all).sum(axis=3, keepdims=True)
    luts_all = r2_all + cb2[None, None] - 2.0 * dot_all  # (q, nprobe, M, ksub)

    def fold(r, carry):
        run_d, run_i = carry
        lists = expanded[:, r]  # (q,) sub-list ids, may be -1
        safe = jnp.maximum(lists, 0)
        # this step's parent LUT, indexed by probe rank
        luts = jnp.take_along_axis(
            luts_all, ranks[:, r][:, None, None, None], axis=1
        ).squeeze(1)  # (q, M, ksub)
        cand_codes = jnp.take(codes, safe, axis=0).astype(jnp.int32)
        # ADC: sum the per-subspace table entries selected by each code
        d2 = jnp.take_along_axis(
            luts[:, None, :, :],  # (q, 1, M, ksub)
            cand_codes[..., None],  # (q, cap, M, 1)
            axis=3,
        ).squeeze(3).sum(axis=2)  # (q, cap)
        cv = jnp.take(bucket_valid, safe, axis=0)
        cv = cv * (lists >= 0)[:, None]
        cid = jnp.take(bucket_ids, safe, axis=0)
        d2 = jnp.where(cv > 0, jnp.maximum(d2, 0.0), jnp.inf)
        cat_d = jnp.concatenate([run_d, d2], axis=1)
        cat_i = jnp.concatenate([run_i, cid], axis=1)
        neg_d, pos = jax.lax.top_k(-cat_d, kk)
        return -neg_d, jnp.take_along_axis(cat_i, pos, axis=1)

    run_d = jnp.full((qn, kk), jnp.inf, queries.dtype)
    run_i = jnp.full((qn, kk), -1, bucket_ids.dtype)
    dist, ids = jax.lax.fori_loop(0, n_live, fold, (run_d, run_i))
    if kk < k:
        pad = k - kk
        dist = jnp.concatenate(
            [dist, jnp.full((qn, pad), jnp.inf, dist.dtype)], axis=1
        )
        ids = jnp.concatenate([ids, jnp.full((qn, pad), -1, ids.dtype)], axis=1)
    ids = jnp.where(jnp.isinf(dist), -1, ids)
    return dist, ids
