#
# Random-forest kernels — the TPU-native replacement for the single-GPU
# `cuml.RandomForestClassifier/Regressor` fits the reference dispatches per
# worker (reference tree.py:383-447; ensemble parallelism: each worker fits
# n_estimators/num_workers trees on its local rows, tree.py:330-341).
#
# There is no cuML to call into — this is a from-scratch histogram
# (XGBoost-style binned) tree builder designed for XLA:
#   - Quantile bin edges are computed per worker from the local shard (one
#     sort per feature); rows are digitized once into int32 bin ids.
#   - Trees grow LEVEL-WISE over a bounded ACTIVE-NODE frontier: each level
#     processes at most `max_active` nodes (a fixed-shape batch), one
#     scatter-add builds the (active-slot, bin, feature, stat) histogram,
#     cumulative sums over bins give every candidate split's left/right
#     statistics, and an argmax picks the best (feature, bin) per slot.
#     Children are allocated in an explicit node TABLE (`left_child`
#     pointers) whose size is 1 + sum_l 2*min(2^l, max_active) — linear in
#     depth, NOT the 2^depth heap that capped the depth-6 compiler ceiling.
#     When a level has more splittable children than `max_active`, the
#     largest (by weighted count) keep growing and the rest become leaves
#     (best-first growth under a width budget, LightGBM-style); with
#     max_active >= 2^level the build is exact level-wise growth.
#     No recursion, no dynamic shapes, no host round-trips.
#   - Per-node feature subsets (featureSubsetStrategy) use the Gumbel
#     top-K trick; bootstrap resampling uses Poisson(rate) weights (the
#     standard large-n approximation of multinomial bootstrap, also used
#     by cuML's GPU forest).
#   - A whole device's worth of trees builds under one vmap; across the
#     mesh, trees are embarrassingly parallel (shard_map with no
#     collectives — the analog of reference tree.py's barrier-allGather-
#     only pattern).
#
# Samples that reach a node that does not split simply keep that node id;
# deeper levels ignore them (their id falls outside the active range), and
# the final leaf-statistics scatter reads each sample's resting node.
#
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import DATA_AXIS

GINI, ENTROPY, VARIANCE = 0, 1, 2  # split criteria


def compute_bin_edges(
    X: jax.Array, n_bins: int, valid: jax.Array | None = None
) -> jax.Array:
    """(n_bins-1, d) interior quantile boundaries from the local rows.

    Zero-padding and zero-weight rows are pushed past the last quantile
    (+inf before the sort) so they cannot skew the edges toward 0; the
    quantile positions index over the *valid* row count."""
    m, d = X.shape
    if valid is not None:
        ok = valid > 0
        X = jnp.where(ok[:, None], X, jnp.inf)
        n_eff = ok.sum().astype(jnp.int32)
    else:
        n_eff = jnp.int32(m)
    Xs = jnp.sort(X, axis=0)
    # edge j at quantile (j+1)/n_bins of the valid rows
    qidx = jnp.clip(
        ((jnp.arange(1, n_bins) * n_eff) // n_bins).astype(jnp.int32), 0, m - 1
    )
    edges = Xs[qidx, :]  # (n_bins-1, d)
    # guard against inf edges when a shard is mostly padding
    return jnp.where(jnp.isfinite(edges), edges, jnp.finfo(X.dtype).max)


def digitize(X: jax.Array, edges: jax.Array) -> jax.Array:
    """Bin ids in [0, n_bins): number of interior edges strictly below x."""
    # (m, d) vs (B-1, d) -> count over edges
    return (X[:, None, :] > edges[None, :, :]).sum(axis=1).astype(jnp.int32)


def _impurity(stats: jax.Array, criterion: int) -> jax.Array:
    """Node impurity from per-channel statistics.

    Classification (gini/entropy): stats[..., :C] are class counts.
    Regression (variance): stats[..., 0:3] = (weight, sum y, sum y^2).
    Returns (impurity, total_count) with impurity 0 for empty nodes.
    """
    if criterion == VARIANCE:
        n = stats[..., 0]
        safe_n = jnp.maximum(n, 1e-12)
        mean = stats[..., 1] / safe_n
        var = jnp.maximum(stats[..., 2] / safe_n - mean * mean, 0.0)
        return jnp.where(n > 0, var, 0.0), n
    n = stats.sum(axis=-1)
    safe_n = jnp.maximum(n, 1e-12)
    p = stats / safe_n[..., None]
    if criterion == GINI:
        imp = 1.0 - (p * p).sum(axis=-1)
    else:  # entropy (Spark uses log2? MLlib uses natural log; sklearn ln)
        imp = -(jnp.where(p > 0, p * jnp.log(p), 0.0)).sum(axis=-1)
    return jnp.where(n > 0, imp, 0.0), n


class TreeArrays(NamedTuple):
    feature: jax.Array  # (T, n_nodes) int32 split feature, -1 = leaf
    threshold: jax.Array  # (T, n_nodes) f32 raw-value threshold (go left if <=)
    leaf_stats: jax.Array  # (T, n_nodes, S) per-leaf statistics
    gain: jax.Array  # (T, n_nodes) impurity decrease of each split (0 = leaf)
    count: jax.Array  # (T, n_nodes) weighted sample count reaching the node
    left_child: jax.Array  # (T, n_nodes) int32 node-table id of the left
    # child (right child = left + 1); -1 for leaves


def table_nodes(max_depth: int, max_active: int) -> int:
    """Node-table size for a (max_depth, max_active) build: root + two
    child slots per possible active node per level."""
    return 1 + sum(2 * min(2**lv, max_active) for lv in range(max_depth))


def _grow_one_tree(
    key,
    Xb: jax.Array,  # (m, d) int32 bin ids
    edges: jax.Array,  # (B-1, d) raw edge values
    stats: jax.Array,  # (m, S) per-sample statistic channels (pre-weighted)
    valid: jax.Array,  # (m,) row validity * user weight
    max_depth: int,
    n_bins: int,
    criterion: int,
    max_features: int,  # features considered per node (Gumbel top-K)
    min_instances: float,
    min_info_gain: float,
    bootstrap: bool,
    subsample: float,
    max_active: int,
):
    m, d = Xb.shape
    S = stats.shape[1]
    n_nodes = table_nodes(max_depth, max_active)

    kb, kf = jax.random.split(key)
    # pcast marks the rate as device-varying to match the varying key inside
    # jax.random's internal control flow under shard_map
    rate = jax.lax.pcast(
        jnp.asarray(subsample, jnp.float32), (DATA_AXIS,), to="varying"
    )
    if bootstrap:
        w = jax.random.poisson(kb, rate, (m,)).astype(stats.dtype)
    elif subsample < 1.0:
        w = jax.random.bernoulli(kb, rate, (m,)).astype(stats.dtype)
    else:
        w = jnp.ones((m,), stats.dtype)
    w = w * valid
    wstats = stats * w[:, None]  # (m, S)

    # node-table arrays carry ONE trash row at index n_nodes: writes for
    # empty frontier slots land there instead of corrupting real nodes
    # (negative scatter ids would wrap in JAX)
    feature = jnp.full((n_nodes + 1,), -1, jnp.int32)
    threshold = jnp.zeros((n_nodes + 1,), edges.dtype)
    gain_arr = jnp.zeros((n_nodes + 1,), stats.dtype)
    count_arr = jnp.zeros((n_nodes + 1,), stats.dtype)
    left_arr = jnp.full((n_nodes + 1,), -1, jnp.int32)

    node = jnp.zeros((m,), jnp.int32)  # table id where each sample rests
    # frontier slot of each sample; A_l (the level width) means inactive
    slot = jnp.where(w > 0, 0, 1).astype(jnp.int32)
    frontier = jnp.zeros((1,), jnp.int32)  # table ids of active nodes
    base = jnp.int32(1)  # next unallocated table id

    # Program-size structure: levels where the frontier is still widening
    # (A_l < max_active) have level-specific shapes and unroll; once the
    # frontier saturates at max_active every remaining level has IDENTICAL
    # shapes, so all of them but the last share ONE lax.fori_loop body —
    # compiled program size is O(log2(max_active)), independent of
    # max_depth.  (The fully-unrolled deep build overwhelmed the TPU
    # compile helper at depth 16, BENCH r03.)  `level` may be traced (the
    # fori index): it only feeds fold_in.
    def level_step(level, A_l, state, last):
        (feature, threshold, gain_arr, count_arr, left_arr,
         node, slot, frontier, base) = state
        active = slot < A_l
        slot_c = jnp.clip(slot, 0, A_l - 1)

        # histogram: (A_l * B, d, S) via one batched scatter-add
        idx = slot_c[:, None] * n_bins + Xb  # (m, d)
        upd = jnp.where(active[:, None, None], wstats[:, None, :], 0.0)
        upd = jnp.broadcast_to(upd, (m, d, S))
        hist = jnp.zeros((A_l * n_bins, d, S), stats.dtype)
        hist = hist.at[idx, jnp.arange(d)[None, :], :].add(upd)
        hist = hist.reshape(A_l, n_bins, d, S).transpose(0, 2, 1, 3)
        # (A_l, d, B, S)

        cum = jnp.cumsum(hist, axis=2)
        total = cum[:, :, -1, :]  # (A_l, d, S) same for every feature
        left = cum[:, :, : n_bins - 1, :]  # (A_l, d, B-1, S)
        right = total[:, :, None, :] - left

        imp_parent, n_parent = _impurity(total[:, 0, :], criterion)  # (A_l,)
        imp_l, n_left = _impurity(left, criterion)  # (A_l, d, B-1)
        imp_r, n_right = _impurity(right, criterion)
        safe_np = jnp.maximum(n_parent, 1e-12)[:, None, None]
        gain = (
            imp_parent[:, None, None]
            - (n_left * imp_l + n_right * imp_r) / safe_np
        )
        ok = (n_left >= min_instances) & (n_right >= min_instances)
        gain = jnp.where(ok, gain, -jnp.inf)

        if max_features < d:
            # per-node feature subset: Gumbel top-K mask over features
            g = jax.random.gumbel(
                jax.random.fold_in(kf, level), (A_l, d), stats.dtype
            )
            kth = jnp.sort(g, axis=1)[:, d - max_features]
            fmask = g >= kth[:, None]  # exactly K True per node
            gain = jnp.where(fmask[:, :, None], gain, -jnp.inf)

        flat = gain.reshape(A_l, -1)
        best = jnp.argmax(flat, axis=1)
        best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
        bf = (best // (n_bins - 1)).astype(jnp.int32)  # (A_l,)
        bb = (best % (n_bins - 1)).astype(jnp.int32)
        real = frontier >= 0
        can_split = jnp.isfinite(best_gain) & (best_gain > min_info_gain) & real

        sids = jnp.where(real, frontier, n_nodes)  # dead slots -> trash row
        left_ids = base + 2 * jnp.arange(A_l, dtype=jnp.int32)
        feature = feature.at[sids].set(jnp.where(can_split, bf, -1))
        threshold = threshold.at[sids].set(
            jnp.where(can_split, edges[bb, bf], 0.0)
        )
        gain_arr = gain_arr.at[sids].set(
            jnp.where(can_split, best_gain, 0.0)
        )
        count_arr = count_arr.at[sids].set(n_parent)
        left_arr = left_arr.at[sids].set(jnp.where(can_split, left_ids, -1))

        # route samples: left child if bin id <= split bin
        samp_f = bf[slot_c]
        samp_b = bb[slot_c]
        go_left = (
            jnp.take_along_axis(Xb, samp_f[:, None], axis=1)[:, 0] <= samp_b
        )
        splits = active & can_split[slot_c]
        child_node = left_ids[slot_c] + jnp.where(go_left, 0, 1)
        node = jnp.where(splits, child_node, node)

        if not last:
            # next frontier: the up-to-A_next largest children (weighted
            # count) that could still split; the rest rest as leaves
            A_next = min(2 * A_l, max_active)
            flat2 = n_left.reshape(A_l, -1)
            nl_b = jnp.take_along_axis(flat2, best[:, None], axis=1)[:, 0]
            nr_b = n_parent - nl_b
            cand_counts = jnp.stack([nl_b, nr_b], axis=1).reshape(-1)
            cand_valid = jnp.repeat(can_split, 2)
            growable = cand_counts >= jnp.maximum(2.0 * min_instances, 1e-12)
            score = jnp.where(cand_valid & growable, cand_counts, -jnp.inf)
            if 2 * A_l <= max_active:
                keep_vals = score
                keep_idx = jnp.arange(2 * A_l, dtype=jnp.int32)
            else:
                keep_vals, keep_idx = jax.lax.top_k(score, A_next)
                keep_idx = keep_idx.astype(jnp.int32)
            kept = keep_vals > -jnp.inf
            frontier = jnp.where(kept, base + keep_idx, -1)
            # inverse map: candidate child -> next-level slot (A_next = none)
            inv = jnp.full((2 * A_l,), A_next, jnp.int32).at[keep_idx].set(
                jnp.where(kept, jnp.arange(A_next, dtype=jnp.int32), A_next)
            )
            cand_of_sample = 2 * slot_c + jnp.where(go_left, 0, 1)
            slot = jnp.where(splits, inv[cand_of_sample], A_next)
        base = base + 2 * A_l
        return (feature, threshold, gain_arr, count_arr, left_arr,
                node, slot, frontier, base)

    state = (feature, threshold, gain_arr, count_arr, left_arr,
             node, slot, frontier, base)
    # first level whose frontier width reaches max_active
    sat = 0
    while (1 << sat) < max_active and sat < max_depth:
        sat += 1
    for lv in range(min(sat, max_depth)):
        state = level_step(
            lv, min(1 << lv, max_active), state, last=(lv == max_depth - 1)
        )
    if sat < max_depth:
        if max_depth - 1 > sat:
            state = jax.lax.fori_loop(
                sat,
                max_depth - 1,
                lambda lv, st: level_step(lv, max_active, st, last=False),
                state,
            )
        # final level: no next-frontier bookkeeping (nothing grows past it)
        state = level_step(max_depth - 1, max_active, state, last=True)
    (feature, threshold, gain_arr, count_arr, left_arr,
     node, slot, frontier, base) = state

    leaf_stats = jnp.zeros((n_nodes + 1, S), stats.dtype).at[node].add(wstats)
    return TreeArrays(
        feature[:n_nodes],
        threshold[:n_nodes],
        leaf_stats[:n_nodes],
        gain_arr[:n_nodes],
        count_arr[:n_nodes],
        left_arr[:n_nodes],
    )


@partial(
    jax.jit,
    static_argnames=("n_bins", "criterion", "n_classes", "mesh"),
)
def _forest_prep(X, y, valid, n_bins: int, criterion: int, n_classes: int,
                 mesh=None):
    """One pass shared by every tree chunk: per-device bin edges (sorted
    local quantiles), digitized rows, and histogram statistic channels."""

    def kernel(Xl, yl, validl):
        if criterion == VARIANCE:
            yf = yl.astype(Xl.dtype)
            statsl = jnp.stack([jnp.ones_like(yf), yf, yf * yf], axis=1)
        else:
            statsl = (
                yl.astype(jnp.int32)[:, None] == jnp.arange(n_classes)[None, :]
            ).astype(Xl.dtype)
        edges = compute_bin_edges(Xl, n_bins, valid=validl)
        Xb = digitize(Xl, edges)
        return Xb, edges, statsl

    shard = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
    )
    return shard(X, y, valid)


@partial(
    jax.jit,
    static_argnames=(
        "count", "trees_per_worker", "max_depth", "n_bins", "criterion",
        "max_features", "bootstrap", "subsample", "max_active", "mesh",
    ),
)
def _forest_fit_chunk(
    Xb, edges, stats, valid, seed, lo,
    count: int,
    trees_per_worker: int,
    max_depth: int,
    n_bins: int,
    criterion: int,
    max_features: int,
    min_instances: float,
    min_info_gain: float,
    bootstrap: bool,
    subsample: float,
    max_active: int,
    mesh=None,
):
    """Grow trees [lo, lo+count) of each device's `trees_per_worker`
    allocation.  `lo` is traced, so every full chunk shares one
    compilation; per-tree PRNG keys come from one split of the full
    allocation, so the forest is identical for any chunking."""

    def kernel(Xbl, edgesl, statsl, validl, lo_):
        widx = jax.lax.axis_index(DATA_AXIS)
        base = jax.random.fold_in(jax.random.PRNGKey(seed), widx)
        keys = jax.lax.dynamic_slice_in_dim(
            jax.random.split(base, trees_per_worker), lo_, count, axis=0
        )
        grow = partial(
            _grow_one_tree,
            Xb=Xbl,
            edges=edgesl,
            stats=statsl,
            valid=validl,
            max_depth=max_depth,
            n_bins=n_bins,
            criterion=criterion,
            max_features=max_features,
            min_instances=min_instances,
            min_info_gain=min_info_gain,
            bootstrap=bootstrap,
            subsample=subsample,
            max_active=max_active,
        )
        return jax.vmap(lambda k: grow(k))(keys)

    shard = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                  P()),
        out_specs=TreeArrays(*([P(DATA_AXIS)] * 6)),
    )
    return shard(Xb, edges, stats, valid, jnp.asarray(lo, jnp.int32))


def forest_fit(
    X: jax.Array,  # (N_pad, d) rows sharded over DATA_AXIS
    y: jax.Array,  # (N_pad,) labels, sharded
    valid: jax.Array,  # (N_pad,) validity * sample weight, sharded
    seed,
    trees_per_worker: int,
    max_depth: int,
    n_bins: int,
    criterion: int,
    n_classes: int,  # 0 for regression
    max_features: int,
    min_instances: float,
    min_info_gain: float,
    bootstrap: bool,
    subsample: float,
    max_active: int = 256,
    mesh=None,
    chunk_trees: int | None = None,  # test hook: fixed chunk size
):
    """Fit the whole forest: each device grows `trees_per_worker` trees on
    its local rows (reference `_estimators_per_worker` tree.py:330-341).
    Returns HOST TreeArrays with a leading (trees_per_worker * n_devices)
    axis.

    Trees are dispatched from the host in adaptively-sized chunks that
    each target `_TARGET_DISPATCH_S` of device time (a 100-tree depth-16
    build on 1M rows is minutes).  The bound was sized for a development
    link that no longer exists; it is kept until re-justified on the chip
    or deleted (ROADMAP Design 2).  Trees are embarrassingly parallel, so
    chunking changes nothing but dispatch count; per-chunk host fetches
    double as the sync points."""
    import time as _time

    import numpy as np

    from ..parallel.mesh import fetch_replicated

    prep = _forest_prep(
        X, y, valid, n_bins=n_bins, criterion=criterion,
        n_classes=n_classes, mesh=mesh,
    )

    def run(lo: int, count: int):
        t0 = _time.perf_counter()
        chunk = _forest_fit_chunk(
            *prep, valid, seed, lo,
            count=count,
            trees_per_worker=trees_per_worker,
            max_depth=max_depth,
            n_bins=n_bins,
            criterion=criterion,
            max_features=max_features,
            min_instances=min_instances,
            min_info_gain=min_info_gain,
            bootstrap=bootstrap,
            subsample=subsample,
            max_active=max_active,
            mesh=mesh,
        )
        host = TreeArrays(
            *(np.asarray(fetch_replicated(t, mesh)) for t in chunk)
        )  # the fetch is the sync
        return host, _time.perf_counter() - t0

    # estimated histogram work per device: levels x rows x features
    # scatter-adds per tree.  Small builds run as ONE dispatch (far from
    # the deadline; probing would just add compiles), big builds probe a
    # single tree and size chunks from its warm time.
    m_local = int(X.shape[0]) // max(int(mesh.devices.size), 1)
    est_ops = trees_per_worker * max_depth * m_local * int(X.shape[1])
    chunks = []
    done = 0
    if chunk_trees is not None:
        size = max(1, min(chunk_trees, trees_per_worker))
    elif trees_per_worker > 1 and est_ops > 2e8:
        c0, _ = run(0, 1)  # cold: includes compile
        c1, warm = run(1, 1)  # warm: honest per-tree device time
        chunks += [c0, c1]
        done = 2
        # ~20 s of device work per dispatch, floor 1
        size = int(min(max(20.0 / max(warm, 1e-3), 1), trees_per_worker - done))
    else:
        size = trees_per_worker
    while trees_per_worker - done >= size and size > 0:
        chunks.append(run(done, size)[0])
        done += size
    if trees_per_worker - done:
        chunks.append(run(done, trees_per_worker - done)[0])

    # reassemble DEVICE-MAJOR: each chunk is (ndev*count, ...) device-major
    # over its own count; naive chunk concat would interleave devices and
    # make the caller's [:n_trees] padding trim timing-dependent (chunk
    # sizes come from a wall-clock probe)
    ndev = int(mesh.devices.size)

    def reassemble(field):
        parts = [
            getattr(c, field).reshape(
                (ndev, -1) + getattr(c, field).shape[1:]
            )
            for c in chunks
        ]
        cat = np.concatenate(parts, axis=1)  # (ndev, trees_per_worker, ...)
        return cat.reshape((ndev * trees_per_worker,) + cat.shape[2:])

    return TreeArrays(*(reassemble(f) for f in TreeArrays._fields))


@partial(jax.jit, static_argnames=("max_depth",))
def forest_apply(
    X: jax.Array,  # (n, d) query rows
    feature: jax.Array,  # (T, n_nodes)
    threshold: jax.Array,  # (T, n_nodes)
    left_child: jax.Array,  # (T, n_nodes)
    max_depth: int,
) -> jax.Array:
    """Leaf node-table index per (tree, row): vectorized pointer traversal —
    `max_depth` rounds of gather + select, all trees at once."""

    def one_tree(feat, thr, lc):
        node = jnp.zeros((X.shape[0],), jnp.int32)
        for _ in range(max_depth):
            f = feat[node]  # (n,)
            is_leaf = f < 0
            x = jnp.take_along_axis(
                X, jnp.maximum(f, 0)[:, None], axis=1
            )[:, 0]
            child = lc[node] + jnp.where(x <= thr[node], 0, 1)
            node = jnp.where(is_leaf, node, child)
        return node

    return jax.vmap(one_tree)(feature, threshold, left_child)  # (T, n)
