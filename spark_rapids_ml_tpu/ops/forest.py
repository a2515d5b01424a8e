#
# Random-forest kernels — the TPU-native replacement for the single-GPU
# `cuml.RandomForestClassifier/Regressor` fits the reference dispatches per
# worker (reference tree.py:383-447; ensemble parallelism: each worker fits
# n_estimators/num_workers trees on its local rows, tree.py:330-341).
#
# There is no cuML to call into — this is a from-scratch histogram
# (XGBoost-style binned) tree builder designed for XLA:
#   - BINS.  Quantile bin edges come from a seeded stratified SAMPLE of each
#     worker's rows (`edge_sample_rows`: Spark's own findSplits samples
#     max(maxBins^2, 10,000) rows), read out of the resident rows in row
#     blocks, sorted per feature.  The rows are digitized once, block by
#     block, into 8-bit bin ids packed four to an int32 word
#     (`pack_words`): nothing of the rows' size is held twice.  A bin id is
#     the count of edges strictly below x, so `x <= edges[b]` on the raw
#     value routes exactly as `bin <= b` does during the build.
#   - A LEVEL STEP READS EACH NODE'S OWN FEATURES ONLY.  Every tree keeps
#     its rows in a TILED, NODE-SORTED layout: (tiles, T) row ids, every
#     tile the rows of ONE frontier node (a node's rows padded to whole
#     tiles with weightless pads).  Per level: the tiles' packed rows are
#     gathered, the node's K features selected out of them (a few: by a
#     one-hot (features x K) matmul, bin ids being exact in bfloat16; from
#     a quarter of a packed row's words on: the words laid rows-minor and
#     each feature's word taken as a row, `selects_by_take`), the (node, K,
#     bin, stat) histogram accumulated by a one-hot (rows x K*bins) matmul
#     against the rows' statistics split into three exact bfloat16 parts
#     (integer counts stay exact, real sums f32), cumulative sums over bins
#     give every candidate split's left/right statistics, an argmax picks
#     the best (feature, bin) per node, and ONE stable sort by next-level
#     node (with a pool of pads that rounds every node up to whole tiles)
#     is the next level's layout.  Rows of nodes that stop leave the
#     layout; a leaf's statistics are its parent's histogram at the chosen
#     split.  No per-row scatter, no per-row table gather.
#   - PANELS.  A selection wider than `feature_panel` features (8,192
#     one-hot columns: 64 features at 128 bins) is multiplied a panel of
#     features at a time inside each scan step, the ids padded to whole
#     panels with the node's last feature (its columns dropped): no array
#     of rows x K x bins is alive at once, and a column's sum is the same
#     dot over a tile's rows whatever the panel's width.  The classifier's
#     floor(sqrt(d)) features are one panel, the product taken whole.
#   - REAL-VALUED STATISTICS (variance).  A regression tree's channels are
#     (w, w y', w y'^2) with y' = y - c, c ONE constant per worker (THE
#     DRAWS, below): every f32 sum of the build is of labels less the
#     shift.  A split's gain is (S_l - n_l S/n)^2 / (n_l n_r)
#     (`_variance_gain`): the variance decrease with nothing of the size of
#     mean^2 cancelling, and no part for sum y^2, which is carried for the
#     leaves alone.  A child's statistics are added up from its own bins of
#     the split's feature, never the node's less its sibling's.  The sums
#     run in a fixed order (a tile's rows in one dot, tiles in scan order),
#     so a forest is bit-identical from fit to fit and for any chunking.
#     The node table's leaves are put back at the end of `_grow_one_tree`,
#     in float32: (n, S1' + c n, S2' + c (2 S1' + c n)), the model's
#     contract (w, sum y, sum y^2) of the labels as given.
#   - Children are allocated in an explicit node TABLE (`left_child`
#     pointers) whose size is 1 + sum_l 2*min(2^l, max_active).  The
#     default `max_active` is the worker's row count, which no frontier can
#     pass, so growth is EXACT level-wise and, while 2^level <= rows, the
#     table is the heap.  A caller's smaller cap turns the levels above it
#     into best-first growth under a width budget (LightGBM-style): the
#     largest children (by weighted count) keep growing, the rest rest.
#     No recursion, no dynamic shapes, no host round-trips.
#   - Per-node feature subsets (featureSubsetStrategy) use the Gumbel
#     top-K trick; bootstrap resampling uses Poisson(rate) weights (the
#     standard large-n approximation of multinomial bootstrap, also used
#     by cuML's GPU forest).
#   - Trees are dispatched in equal chunks sized from the shapes and the
#     memory the device has left (`chunk_trees_for`), a chunk's trees under
#     one vmap (one after another where a level is panelled: the panels
#     count on the chip's fast memory, which a vmap over trees multiplies
#     out of it); across the mesh, trees are embarrassingly parallel
#     (shard_map with no collectives — the analog of reference tree.py's
#     barrier-allGather-only pattern).
#
# THE DRAWS, stated so that a reference can re-derive them with jax.random
# alone (chipbench/estimators/rfc.py and rfr.py do).  Worker `i` (its position on
# the mesh's data axis) holds m rows (padding included) and
# base = fold_in(PRNGKey(seed), i).
#   edges   q = max(1, m // edge_sample_rows(n_bins)), S = m // q; sample
#           row j is j*q + randint(fold_in(base, EDGE_STREAM), (S,), 0, q)[j];
#           a sampled row of weight 0 counts as +inf; with n_eff sampled
#           rows of positive weight, edge e (1..n_bins-1) of a feature is
#           its sorted sample's element (e * n_eff) // n_bins.
#   tree t  key = split(base, trees_per_worker)[t]; kb, kf = split(key);
#           bootstrap weights poisson(kb, rate, (m,)) (bernoulli without
#           bootstrap and rate < 1, else ones), times the row's weight.
#   node    of frontier slot s at level l: the K features with the largest
#           gumbel(fold_in(kf, l), (A_l, d), the rows' dtype)[s], A_l =
#           min(2^l, max_active).  Uncapped, slot s of level l is table node
#           2^l - 1 + s.
#   shift   (regression) c = float32(sum_i v_i y_i / sum_i v_i) over the
#           worker's rows, v the row's validity times its weight
#           (`label_shift`, one small program beside the bins).  Any value
#           near the mean does: gains and leaves do not depend on it in
#           exact arithmetic, so a reference needs none.
#
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import DATA_AXIS

GINI, ENTROPY, VARIANCE = 0, 1, 2  # split criteria

EDGE_STREAM = 0x0ED6E5  # fold_in tag of the edge sample's offsets
EDGE_RULE = "stratified sample of max(n_bins^2, 10000) rows per worker"

# rows to one program of the bin phase, and to one step of a level's scans
_BIN_BLOCK_ROWS = 32_768
_SCAN_ROWS = 8_192
_MAX_TILE_ROWS = 512
# one-hot columns (features x bins) to one product of a scan step: wider
# selections are multiplied a panel of features at a time
_PANEL_COLUMNS = 8_192


def edge_sample_rows(n_bins: int) -> int:
    """Rows the bin edges are read from at the least (Spark's findSplits:
    max(maxBins^2, 10,000)); fewer local rows are all taken."""
    return max(n_bins * n_bins, 10_000)


def _edge_stride(m: int, n_bins: int) -> int:
    """q: the edge sample takes one row out of every q of a worker's m."""
    return max(1, m // edge_sample_rows(n_bins))


def edge_sample(key, m: int, n_bins: int) -> jax.Array:
    """Local positions of the worker's edge sample: one row out of each
    run of q rows (`_edge_stride`), at a seeded offset."""
    q = _edge_stride(m, n_bins)
    n = m // q
    off = jax.random.randint(jax.random.fold_in(key, EDGE_STREAM), (n,), 0, q)
    return jnp.arange(n, dtype=jnp.int32) * q + off.astype(jnp.int32)


def compute_bin_edges(
    X: jax.Array, n_bins: int, valid: jax.Array | None = None
) -> jax.Array:
    """(n_bins-1, d) interior quantile boundaries of the rows `X` (the edge
    sample, or all of a small worker's rows).

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
    """uint8 bin ids in [0, n_bins): number of interior edges strictly
    below x (n_bins <= 256)."""
    # (m, d) vs (B-1, d) -> count over edges
    return (X[:, None, :] > edges[None, :, :]).sum(axis=1).astype(jnp.uint8)


def pack_words(d: int) -> int:
    """int32 words to a packed row of d bin ids: four ids to a word, whole
    lane tiles once a row is longer than one (a (rows, words) array whose
    minor dimension is no multiple of 128 lies column-major on a TPU, and
    a row gather from it would copy it)."""
    words = -(-d // 4)
    return words if words <= 128 else -(-words // 128) * 128


def pack_bins(Xb: jax.Array) -> jax.Array:
    """(m, d) uint8 bin ids -> (m, W) int32, byte b of word w holding
    feature b*W + w (plane-major, so unpacking is four aligned planes side
    by side and no interleave)."""
    m, d = Xb.shape
    W = pack_words(d)
    planes = jnp.pad(Xb, ((0, 0), (0, 4 * W - d))).reshape(m, 4, W).astype(jnp.int32)
    return (planes[:, 0] | (planes[:, 1] << 8) | (planes[:, 2] << 16)
            | (planes[:, 3] << 24))


def _unpack_planes(words: jax.Array, dtype=jnp.bfloat16):
    """The four (…, W) byte planes of packed rows, as `dtype` (a bin id is
    at most 255: exact in bfloat16)."""
    return [((words >> (8 * b)) & 0xFF).astype(dtype) for b in range(4)]


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
        # sum_c p_c (1 - p_c), not 1 - sum_c p_c^2: no cancellation, so the
        # rounding is a few ulp OF THE IMPURITY and a nearly pure node ranks
        # its candidate splits as well as an even one (n - count is exact)
        imp = (p * ((n[..., None] - stats) / safe_n[..., None])).sum(axis=-1)
    else:  # entropy (Spark uses log2? MLlib uses natural log; sklearn ln)
        imp = -(jnp.where(p > 0, p * jnp.log(p), 0.0)).sum(axis=-1)
    return jnp.where(n > 0, imp, 0.0), n


def _variance_gain(node, left, right):
    """Variance decrease of every candidate split from (weight, sum y,
    sum y^2) statistics: node (A, 3), left and right (A, K, B-1, 3).
    Returns (gain, n, n_left, n_right).

    var - (n_l var_l + n_r var_r) / n is (S_l - n_l S/n)^2 / (n_l n_r):
    what cancels is a child's sum against its share of the node's, a
    difference whose square IS the gain, and never sum y^2 / n against
    mean^2 (6e-8 of mean^2, which passes the gains' own size once the mean
    is a few hundred standard deviations).  sum y^2 takes no part: it is
    carried for the leaves alone."""
    n, n_left, n_right = node[:, 0], left[..., 0], right[..., 0]
    mean = (node[:, 1] / jnp.maximum(n, 1e-12))[:, None, None]
    dev = left[..., 1] - n_left * mean
    return dev * dev / jnp.maximum(n_left * n_right, 1e-12), n, n_left, n_right


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


def _level_shape(m: int, A: int):
    """(rows to a tile, tiles, tiles to a scan step) of a level whose
    frontier holds A nodes over m rows: tiles of about half a node's mean
    rows, room for every row plus one ragged tile a node, whole steps."""
    T = 8
    while 2 * T <= min(_MAX_TILE_ROWS, m // (2 * A)):
        T *= 2
    tiles = -(-m // T) + A
    step = max(1, min(_SCAN_ROWS // T, tiles))
    return T, -(-tiles // step) * step, step


def feature_panel(max_features: int, n_bins: int) -> int:
    """Features to one panel of a level's histogram: as many as keep a
    panel's one-hot within `_PANEL_COLUMNS` columns (64 at 128 bins).  A
    selection no wider than that is one panel, the product taken whole."""
    return max(1, min(max_features, _PANEL_COLUMNS // n_bins))


def selects_by_take(max_features: int, d: int) -> bool:
    """Whether a node's features are taken out of the gathered rows as
    columns (a transpose of the packed words, then one row gather a
    feature) or by the one-hot (features x words) product.  The product's
    cost grows with the features a node, the take's hardly: on a v5e at
    3,000 columns the take is 9.0 ms a level of 336,000 rows at 1,000
    features where the product is 17.2 (PERF.md, PR 39), and the product
    wins under a few hundred.  By the shapes: the take from the width at
    which a node's features fill a quarter of a packed row's words."""
    return 4 * max_features >= pack_words(d)


def _layout(key, rowid, w, y, counts, m: int, A: int, T: int, tiles: int):
    """The tiled, node-sorted layout of a level: rows (`key` their frontier
    slot, A for a row that rests; `rowid` < m, -1 for a pad of the level
    before) sorted by slot together with a pool of pads that rounds every
    slot's `counts` rows up to whole tiles.  Returns (slot of each tile (A
    where empty), rowid (tiles, T) with -1 for pads, w, y).

    Slot and row id share ONE uint32 sort key where their bits fit (4,096
    slots over 500,000 rows do): every key but a pad's is unique, so the
    order is the rows' own whatever sorts them, and the sort carries two
    operands less than a stable one by slot would."""
    lane = jnp.arange(T, dtype=jnp.int32)[None, :]
    slots = jnp.arange(A, dtype=jnp.int32)[:, None]
    pool_key = jnp.where(lane < (-counts[:, None]) % T, slots, A).reshape(-1)
    pad = jnp.zeros((A * T,), w.dtype)
    key = jnp.concatenate([key, pool_key])
    rowid = jnp.concatenate([rowid, jnp.full((A * T,), -1, jnp.int32)])
    w, y = jnp.concatenate([w, pad]), jnp.concatenate([y, pad])
    row_bits = m.bit_length()  # 2^row_bits - 1 >= m marks a pad
    if row_bits + A.bit_length() <= 32:
        mark = jnp.uint32((1 << row_bits) - 1)
        code = (key.astype(jnp.uint32) << row_bits) | jnp.where(
            rowid < 0, mark, rowid.astype(jnp.uint32))
        code, w, y = jax.lax.sort((code, w, y), num_keys=1, is_stable=False)
        key = (code >> row_bits).astype(jnp.int32)
        rowid = jnp.where(code & mark == mark, -1, (code & mark).astype(jnp.int32))
    else:
        key, rowid, w, y = jax.lax.sort((key, rowid, w, y), num_keys=1, is_stable=True)
    n = tiles * T

    def tiled(a, fill):  # the first n sorted elements; a level may be smaller than its room
        short = max(0, n - a.shape[0])
        return jnp.concatenate([a[:n], jnp.full((short,), fill, a.dtype)]).reshape(tiles, T)

    return tiled(key, A)[:, 0], tiled(rowid, -1), tiled(w, 0), tiled(y, 0)


def _row_stats(w, y, criterion: int, n_stats: int):
    """(…, S, T) statistic channels of rows (…, T) of weight w and label
    y, the rows on the minor axis."""
    if criterion == VARIANCE:
        return jnp.stack([w, w * y, w * y * y], axis=-2)
    return w[..., None, :] * (
        y.astype(jnp.int32)[..., None, :] == jnp.arange(n_stats)[:, None]
    ).astype(w.dtype)


def _three_parts(x, dtype):
    """f32 (…, S, T) -> (…, 3S, T): three parts of `dtype` (bfloat16 on a
    TPU) that add up to it (to its last bit or two: 24 significand bits in
    three of 8), so a one-hot product with them is as exact as f32.  Each
    part is cut by `reduce_precision`, not by a cast there and back: XLA
    may elide that pair on a TPU (xla_allow_excess_precision) and leave no
    remainder, which made every real-valued sum a one-part bfloat16 sum
    (2e-3 of a leaf's sum y on the chip, PERF.md PR 39; counts never
    showed it: they are exact in the first part)."""
    info = jnp.finfo(dtype)
    parts, rest = [], x
    for _ in range(3):
        part = jax.lax.reduce_precision(rest, exponent_bits=info.nexp, mantissa_bits=info.nmant)
        parts.append(part.astype(dtype))
        rest = rest - part
    return jnp.concatenate(parts, axis=-2)


def _grow_one_tree(
    key,
    packed: jax.Array,  # (m, W) int32 packed bin ids
    edges: jax.Array,  # (B-1, d) raw edge values
    y: jax.Array,  # (m,) labels
    valid: jax.Array,  # (m,) row validity * user weight
    shift,  # () the worker's label shift (`label_shift`); 0 for classes
    max_depth: int,
    n_bins: int,
    criterion: int,
    n_stats: int,  # statistic channels: classes, or 3 for regression
    max_features: int,  # features considered per node (Gumbel top-K)
    min_instances: float,
    min_info_gain: float,
    bootstrap: bool,
    subsample: float,
    max_active: int,
    room: int,  # rows a level's layout has room for (`rows_room`)
    operand,  # dtype of the one-hot products' operands: bfloat16 on a TPU
):
    m, W = packed.shape
    d = edges.shape[1]
    S, K, B = n_stats, max_features, n_bins
    panel = feature_panel(K, B)
    panels = -(-K // panel)
    K_sel = panels * panel  # features selected a node: K, padded to whole panels
    dtype = edges.dtype
    n_nodes = table_nodes(max_depth, max_active)

    kb, kf = jax.random.split(key)
    if bootstrap:
        w = jax.random.poisson(kb, subsample, (m,)).astype(dtype)
    elif subsample < 1.0:
        w = jax.random.bernoulli(kb, subsample, (m,)).astype(dtype)
    else:
        w = jnp.ones((m,), dtype)
    w = w * valid.astype(dtype)
    if criterion == VARIANCE:
        # every sum of the build is of labels less the shift; the node
        # table's leaves are put back below
        y = y.astype(dtype) - shift

    # node-table arrays carry ONE trash row at index n_nodes: writes for
    # empty frontier slots land there instead of corrupting real nodes
    # (negative scatter ids would wrap in JAX)
    feature = jnp.full((n_nodes + 1,), -1, jnp.int32)
    threshold = jnp.zeros((n_nodes + 1,), dtype)
    gain_arr = jnp.zeros((n_nodes + 1,), dtype)
    count_arr = jnp.zeros((n_nodes + 1,), dtype)
    left_arr = jnp.full((n_nodes + 1,), -1, jnp.int32)
    leaf_stats = jnp.zeros((n_nodes + 1, S), dtype)

    def level_step(level, A_l, state, layout, last):
        (feature, threshold, gain_arr, count_arr, left_arr, leaf_stats,
         frontier, base) = state
        tile_slot, rowid, wt, yt = layout
        T, tiles, step = _level_shape(room, A_l)
        live = tile_slot < A_l
        slot_c = jnp.minimum(tile_slot, A_l - 1)

        # each node's K features, ascending (ties in gain go to the lowest)
        if K < d:
            g = jax.random.gumbel(jax.random.fold_in(kf, level), (A_l, d), dtype)
            feats = jnp.sort(jax.lax.top_k(g, K)[1].astype(jnp.int32), axis=1)
        else:
            feats = jnp.broadcast_to(jnp.arange(d, dtype=jnp.int32), (A_l, d))

        # whole panels: the last feature again, its columns dropped
        feats_sel = jnp.pad(feats, ((0, 0), (0, K_sel - K)), mode="edge")

        def chunks(a):
            return a.reshape((tiles // step, step) + a.shape[1:])

        # the rows' bin ids of their node's features, rows minor: (tiles, K, T)
        def select(args):
            rows, node_feats = args  # (step, T), (step, K_sel)
            words = jnp.take(packed, jnp.maximum(rows, 0), axis=0)  # (step, T, W)
            if K == d:
                ids = jnp.concatenate(_unpack_planes(words, operand), axis=-1)[..., :d]
                ids = jnp.pad(ids, ((0, 0), (0, 0), (0, K_sel - K)))
                return jnp.swapaxes(ids, 1, 2).astype(jnp.uint8)
            if selects_by_take(K, d):
                # the words laid rows-minor, then each feature's word taken
                # as one row of T and its byte shifted out: no product
                lanes = jnp.swapaxes(words, 1, 2).reshape(step * W, T)
                at = jnp.arange(step, dtype=jnp.int32)[:, None] * W + node_feats % W
                col = jnp.take(lanes, at.reshape(-1), axis=0).reshape(step, K_sel, T)
                return ((col >> (8 * (node_feats // W))[:, :, None]) & 0xFF).astype(jnp.uint8)
            planes = _unpack_planes(words, operand)
            words = jnp.arange(W, dtype=jnp.int32)
            sel = sum(
                jnp.einsum(
                    "ckw,ctw->ckt",
                    (b * W + words == node_feats[:, :, None]).astype(operand), planes[b],
                    preferred_element_type=jnp.float32)
                for b in range(4))
            return sel.astype(jnp.uint8)

        with jax.named_scope("forest_hist"):
            sel = jax.lax.map(select, (chunks(rowid), chunks(feats_sel[slot_c])))
            sel = sel.reshape(tiles, K_sel, T)
            stats3 = _three_parts(_row_stats(wt, yt, criterion, S), operand)  # (tiles, 3S, T)

            def three_in_one(part):  # (step, 3S, ...) -> (step, S, ...)
                return (part[:, :S] + part[:, S:2 * S] + part[:, 2 * S:]).astype(dtype)

            # (A_l + 1, S, K*B): a tile's one-hot product, added to its node
            def accumulate(hist, args):
                s, st, node = args  # (step, K, T), (step, 3S, T), (step,)
                bins = jnp.arange(B, dtype=jnp.uint8)[:, None]
                if panels == 1:
                    onehot = (s[:, :, None, :] == bins).astype(operand).reshape(step, K * B, T)
                    part = jnp.einsum("cst,cqt->csq", st, onehot,
                                      preferred_element_type=jnp.float32)
                    return hist.at[node].add(three_in_one(part)), None

                # a panel's product keeps features and bins apart: merged into
                # one axis, the ids' broadcast along the bins is an array of
                # its own (a third of a level on a v5e); apart, XLA makes it
                # inside the product (41 ms a level for 100, PERF.md PR 39)
                def one_panel(p, hist):  # (panels, A_l + 1, S, panel, B)
                    sp = jax.lax.dynamic_slice_in_dim(s, p * panel, panel, axis=1)
                    onehot = (sp[:, :, None, :] == bins).astype(operand)
                    part = jnp.einsum("cst,ckbt->cskb", st, onehot,
                                      preferred_element_type=jnp.float32)
                    return hist.at[p, node].add(three_in_one(part))

                return jax.lax.fori_loop(0, panels, one_panel, hist), None

            hist, _ = jax.lax.scan(
                accumulate,
                jnp.zeros((A_l + 1, S, K * B) if panels == 1
                          else (panels, A_l + 1, S, panel, B), dtype),
                (chunks(sel), chunks(stats3), chunks(jnp.where(live, tile_slot, A_l))))
            if panels == 1:
                hist = hist[:A_l].reshape(A_l, S, K, B).transpose(0, 2, 3, 1)  # (A,K,B,S)
            else:
                hist = hist[:, :A_l].transpose(1, 0, 3, 4, 2).reshape(A_l, K_sel, B, S)[:, :K]

        with jax.named_scope("forest_split"):
            cum = jnp.cumsum(hist, axis=2)
            total = cum[:, :, -1, :]  # (A_l, K, S) same for every feature
            left = cum[:, :, : B - 1, :]  # (A_l, K, B-1, S)
            right = total[:, :, None, :] - left

            if criterion == VARIANCE:
                gain, n_parent, n_left, n_right = _variance_gain(total[:, 0, :], left, right)
            else:
                imp_parent, n_parent = _impurity(total[:, 0, :], criterion)  # (A_l,)
                imp_l, n_left = _impurity(left, criterion)  # (A_l, K, B-1)
                imp_r, n_right = _impurity(right, criterion)
                safe_np = jnp.maximum(n_parent, 1e-12)[:, None, None]
                gain = (
                    imp_parent[:, None, None]
                    - (n_left * imp_l + n_right * imp_r) / safe_np
                )
            ok = (n_left >= min_instances) & (n_right >= min_instances)
            gain = jnp.where(ok, gain, -jnp.inf)

            flat = gain.reshape(A_l, -1)
            best = jnp.argmax(flat, axis=1)
            best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
            bj = (best // (B - 1)).astype(jnp.int32)  # (A_l,) index into feats
            bb = (best % (B - 1)).astype(jnp.int32)
            bf = jnp.take_along_axis(feats, bj[:, None], axis=1)[:, 0]
            real = frontier >= 0
            can_split = jnp.isfinite(best_gain) & (best_gain > min_info_gain) & real
            left_stats = jnp.take_along_axis(
                left.reshape(A_l, K * (B - 1), S), best[:, None, None], axis=1)[:, 0]
            node_stats = total[:, 0, :]

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
            # a frontier node that does not split is a leaf with its rows
            leaf_stats = leaf_stats.at[sids].set(
                jnp.where(can_split[:, None], 0.0, node_stats))

            # the children, as candidates 2*slot (left) and 2*slot + 1
            if criterion == VARIANCE:
                # real-valued sums: each child's added up from its own bins
                # of the split's feature; the node's less the left child's
                # would leave a small right child the node's rounding
                picked = jnp.take_along_axis(hist, bj[:, None, None, None], axis=1)[:, 0]
                to_left = (jnp.arange(B, dtype=jnp.int32) <= bb[:, None])[:, :, None]
                left_stats = jnp.where(to_left, picked, 0.0).sum(axis=1)
                right_stats = jnp.where(to_left, 0.0, picked).sum(axis=1)
            else:
                right_stats = node_stats - left_stats
            child_stats = jnp.stack([left_stats, right_stats], axis=1).reshape(2 * A_l, S)
            cand_counts = _impurity(child_stats, criterion)[1]
            cand_valid = jnp.repeat(can_split, 2)
            cand_ids = base + jnp.arange(2 * A_l, dtype=jnp.int32)
            if last:
                kept_cand = jnp.zeros((2 * A_l,), bool)
            else:
                # next frontier: the up-to-A_next largest children (weighted
                # count) that could still split; the rest rest as leaves
                A_next = min(2 * A_l, max_active)
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
                kept_cand = inv < A_next
            # a child that leaves the frontier is a leaf with its statistics
            rest = jnp.where(cand_valid & ~kept_cand, cand_ids, n_nodes)
            leaf_stats = leaf_stats.at[rest].set(child_stats)
            count_arr = count_arr.at[rest].set(cand_counts)

        if not last:
            with jax.named_scope("forest_route"):
                # left child if bin id <= split bin, read off the selected ids
                pick = jnp.arange(K_sel, dtype=jnp.int32)[:, None] == bj[slot_c][:, None, None]
                row_bin = jnp.where(pick, sel, 0).max(axis=1)  # (tiles, T)
                go_left = row_bin <= bb[slot_c][:, None].astype(jnp.uint8)
                held = live[:, None] & (rowid >= 0)
                side = jnp.stack([inv[2 * slot_c], inv[2 * slot_c + 1]], axis=1)
                key_next = jnp.where(
                    held, jnp.where(go_left, side[:, :1], side[:, 1:]), A_next)
                # rows to each next slot, from the tiles' own counts
                n_l = (held & go_left).sum(axis=1, dtype=jnp.int32)
                n_r = (held & ~go_left).sum(axis=1, dtype=jnp.int32)
                counts = jnp.zeros((A_next + 1,), jnp.int32).at[side].add(
                    jnp.stack([n_l, n_r], axis=1))[:A_next]
                layout = _layout(
                    key_next.reshape(-1), rowid.reshape(-1), wt.reshape(-1),
                    yt.reshape(-1), counts, m, A_next, *_level_shape(room, A_next)[:2])
        base = base + 2 * A_l
        return (feature, threshold, gain_arr, count_arr, left_arr, leaf_stats,
                frontier, base), layout

    state = (feature, threshold, gain_arr, count_arr, left_arr, leaf_stats,
             jnp.zeros((1,), jnp.int32), jnp.int32(1))
    active = w > 0
    n_active = active.sum().astype(jnp.int32)
    layout = _layout(
        jnp.where(active, 0, 1).astype(jnp.int32), jnp.arange(m, dtype=jnp.int32),
        w, y.astype(dtype), n_active[None], m, 1, *_level_shape(room, 1)[:2])

    # Program-size structure: levels where the frontier is still widening
    # (A_l < max_active) have level-specific shapes and unroll; once the
    # frontier saturates at max_active every remaining level has IDENTICAL
    # shapes, so all of them but the last share ONE lax.fori_loop body.
    # `level` may be traced (the fori index): it only feeds fold_in.
    sat = 0
    while (1 << sat) < max_active and sat < max_depth:
        sat += 1
    for lv in range(min(sat, max_depth)):
        state, layout = level_step(
            lv, min(1 << lv, max_active), state, layout, last=(lv == max_depth - 1)
        )
    if sat < max_depth:
        if max_depth - 1 > sat:
            state, layout = jax.lax.fori_loop(
                sat,
                max_depth - 1,
                lambda lv, sl: level_step(lv, max_active, *sl, last=False),
                (state, layout),
            )
        # final level: no next-frontier bookkeeping (nothing grows past it)
        state, layout = level_step(max_depth - 1, max_active, state, layout, last=True)
    feature, threshold, gain_arr, count_arr, left_arr, leaf_stats = state[:6]
    if criterion == VARIANCE:
        # the model's contract is (w, sum y, sum y^2) of the labels as given:
        # sum (y' + c) = S1' + c n, sum (y' + c)^2 = S2' + 2 c S1' + c^2 n
        n, s1, s2 = leaf_stats[:, 0], leaf_stats[:, 1], leaf_stats[:, 2]
        leaf_stats = jnp.stack(
            [n, s1 + shift * n, s2 + shift * (2.0 * s1 + shift * n)], axis=1)
    return TreeArrays(
        feature[:n_nodes],
        threshold[:n_nodes],
        leaf_stats[:n_nodes],
        gain_arr[:n_nodes],
        count_arr[:n_nodes],
        left_arr[:n_nodes],
    ), n_active > room


def _local(kernel, mesh, n_sharded: int, n_replicated: int = 0, out_specs=P(DATA_AXIS)):
    """`kernel` over each device's own rows: the first arguments sharded by
    rows, the rest replicated, no collective."""
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(P(DATA_AXIS),) * n_sharded + (P(),) * n_replicated,
        out_specs=out_specs, check_vma=False,
    )


def _block_of(a, k, rows: int):
    """Rows [start, start + rows) of the device's `a`, read in place; the
    last block starts early enough to be whole, so blocks may overlap."""
    start = jnp.minimum(k * rows, a.shape[0] - rows)
    return start, jax.lax.dynamic_slice_in_dim(a, start, rows, 0)


@partial(jax.jit, static_argnames=("n_bins", "rows", "mesh"), donate_argnums=(0, 1))
def _forest_sample_block(sample, sample_valid, X, valid, seed, k, n_bins: int,
                         rows: int, mesh=None):
    """Writes into `sample` the rows of the worker's edge sample that lie
    in row block k of its shard (and into `sample_valid` their weights).
    The sample is in row order, so a block's part of it is one window."""

    def kernel(sl, svl, Xl, validl, k_):
        m, n = Xl.shape[0], sl.shape[0]
        base = jax.random.fold_in(jax.random.PRNGKey(seed), jax.lax.axis_index(DATA_AXIS))
        q = _edge_stride(m, n_bins)
        window = min(n, rows // q + 2)
        first = jnp.minimum((k_ * rows) // q, n - window)
        at = jax.lax.dynamic_slice_in_dim(edge_sample(base, m, n_bins), first, window)
        start, Xb = _block_of(Xl, k_, rows)
        _, vb = _block_of(validl, k_, rows)
        mine = (at >= k_ * rows) & (at < (k_ + 1) * rows)
        rel = jnp.clip(at - start, 0, rows - 1)

        def put(a, new):  # the block's own samples into their window of `a`
            held = jax.lax.dynamic_slice_in_dim(a, first, window)
            mask = mine.reshape((-1,) + (1,) * (a.ndim - 1))
            return jax.lax.dynamic_update_slice_in_dim(a, jnp.where(mask, new, held), first, 0)

        return put(sl, jnp.take(Xb, rel, axis=0)), put(svl, jnp.take(vb, rel))

    return _local(kernel, mesh, 4, 1, out_specs=(P(DATA_AXIS), P(DATA_AXIS)))(
        sample, sample_valid, X, valid, jnp.asarray(k, jnp.int32))


@partial(jax.jit, static_argnames=("n_bins", "mesh"))
def _forest_edges(sample, sample_valid, n_bins: int, mesh=None):
    """Per-device bin edges from its edge sample."""
    return _local(lambda s, v: compute_bin_edges(s, n_bins, valid=v), mesh, 2)(
        sample, sample_valid)


@partial(jax.jit, static_argnames=("rows", "mesh"), donate_argnums=(0,))
def _forest_bin_block(packed, X, edges, k, rows: int, mesh=None):
    """Writes row block k of each device's packed bin ids."""

    def kernel(pl, Xl, edgesl, k_):
        start, Xb = _block_of(Xl, k_, rows)
        return jax.lax.dynamic_update_slice_in_dim(
            pl, pack_bins(digitize(Xb, edgesl)), start, 0)

    return _local(kernel, mesh, 3, 1)(packed, X, edges, jnp.asarray(k, jnp.int32))


@partial(jax.jit, static_argnames=("mesh",))
def _forest_label_shift(y, valid, mesh=None):
    """(n_dev,) each worker's weighted mean label, float32."""

    def kernel(yl, validl):
        v = validl.astype(jnp.float32)
        return (jnp.sum(v * yl.astype(jnp.float32)) / jnp.maximum(jnp.sum(v), 1e-12))[None]

    return _local(kernel, mesh, 2)(y, valid)


def label_shift(y, valid, criterion: int, mesh):
    """The constant each worker takes off its labels before a regression
    tree's sums are formed (THE DRAWS, above): its weighted mean label.
    Zeros, and no program, for a classifier's classes."""
    from jax.sharding import NamedSharding

    if criterion != VARIANCE:
        return jnp.zeros((int(mesh.devices.size),), jnp.float32,
                         device=NamedSharding(mesh, P(DATA_AXIS)))
    return _forest_label_shift(y, valid, mesh=mesh)


def forest_bins(X, valid, seed, n_bins: int, mesh):
    """(packed bin ids (N_pad, W) int32, edges (n_dev * (n_bins-1), d)),
    both sharded like the rows: the edge sample gathered and the rows
    digitized in row blocks read in place, one program a block."""
    from jax.sharding import NamedSharding

    if not 2 <= n_bins <= 256:
        raise ValueError(f"maxBins must lie in [2, 256] (8-bit bin ids), got {n_bins}")
    n_dev = int(mesh.devices.size)
    m, d = int(X.shape[0]) // n_dev, int(X.shape[1])
    rows = min(m, _BIN_BLOCK_ROWS)
    blocks = -(-m // rows)
    n_sample = m // _edge_stride(m, n_bins)
    by_rows = NamedSharding(mesh, P(DATA_AXIS))
    sample = jnp.zeros((n_dev * n_sample, d), X.dtype, device=by_rows)
    sample_valid = jnp.zeros((n_dev * n_sample,), valid.dtype, device=by_rows)
    for k in range(blocks):
        sample, sample_valid = _forest_sample_block(
            sample, sample_valid, X, valid, seed, k, n_bins=n_bins, rows=rows, mesh=mesh)
    edges = _forest_edges(sample, sample_valid, n_bins=n_bins, mesh=mesh)
    packed = jnp.zeros((n_dev * m, pack_words(d)), jnp.int32, device=by_rows)
    for k in range(blocks):
        packed = _forest_bin_block(packed, X, edges, k, rows=rows, mesh=mesh)
    return packed, edges


@partial(
    jax.jit,
    static_argnames=(
        "count", "trees_per_worker", "max_depth", "n_bins", "criterion",
        "n_stats", "max_features", "bootstrap", "subsample", "max_active", "room",
        "mesh",
    ),
)
def _forest_fit_chunk(
    packed, edges, y, valid, shift, seed, lo,
    count: int,
    trees_per_worker: int,
    max_depth: int,
    n_bins: int,
    criterion: int,
    n_stats: int,
    max_features: int,
    min_instances: float,
    min_info_gain: float,
    bootstrap: bool,
    subsample: float,
    max_active: int,
    room: int,
    mesh=None,
):
    """Grow trees [lo, lo+count) of each device's `trees_per_worker`
    allocation.  `lo` is traced, so every chunk shares one compilation;
    per-tree PRNG keys come from one split of the full allocation, so the
    forest is identical for any chunking.  Returns (TreeArrays, per tree
    whether its rows of positive weight passed `room`)."""

    def kernel(packedl, edgesl, yl, validl, shiftl, lo_):
        widx = jax.lax.axis_index(DATA_AXIS)
        base = jax.random.fold_in(jax.random.PRNGKey(seed), widx)
        keys = jax.lax.dynamic_slice_in_dim(
            jax.random.split(base, trees_per_worker), lo_, count, axis=0
        )
        grow = partial(
            _grow_one_tree,
            packed=packedl,
            edges=edgesl,
            y=yl,
            valid=validl,
            shift=shiftl[0],
            max_depth=max_depth,
            n_bins=n_bins,
            criterion=criterion,
            n_stats=n_stats,
            max_features=max_features,
            min_instances=min_instances,
            min_info_gain=min_info_gain,
            bootstrap=bootstrap,
            subsample=subsample,
            max_active=max_active,
            room=room,
            # the CPU backend has no bfloat16 dot; f32 operands are as exact
            operand=(jnp.bfloat16 if mesh.devices.flat[0].platform == "tpu"
                     else jnp.float32),
        )
        if feature_panel(max_features, n_bins) < max_features:
            # one tree after another: a panelled level counts on a scan
            # step's one-hot lying in the chip's fast memory, which a vmap
            # over the chunk's trees multiplies out of it (1.08 s a tree for
            # 0.67 at five trees a dispatch on a v5e, PERF.md PR 39)
            return jax.lax.map(grow, keys)
        return jax.vmap(lambda k: grow(k))(keys)

    return _local(
        kernel, mesh, 5, 1, out_specs=(TreeArrays(*([P(DATA_AXIS)] * 6)), P(DATA_AXIS))
    )(packed, edges, y, valid, shift, jnp.asarray(lo, jnp.int32))


def rows_room(m: int, bootstrap: bool, subsample: float) -> int:
    """Rows a tree's level layouts have room for.  A row of weight 0 is in
    no layout; under Poisson(rate) bootstrap a row has weight 0 with
    probability e^-rate (37 % at rate 1), without it and a rate under 1
    with probability 1 - rate.  Room for the mean count of the others and
    eight standard deviations (passed once in 10^15 trees; `forest_fit`
    grows such a tree again with room for every row)."""
    import math

    if bootstrap:
        p = 1.0 - math.exp(-subsample)
    elif subsample < 1.0:
        p = subsample
    else:
        return m
    return min(m, math.ceil(m * p + 8.0 * math.sqrt(m * p * (1.0 - p))) + 1)


def tree_bytes(room: int, d: int, max_depth: int, n_bins: int, n_stats: int,
               max_features: int, max_active: int) -> int:
    """Device bytes one tree's build holds at its widest level, from the
    shapes alone: the histogram, its cumulative sums and the gains derived
    from it (three arrays of its size at a time), the selected bin ids, the
    layout and its sort, a scan step's gathered rows and the one-hot of ONE
    panel of features (`feature_panel`), and the node table.  For the
    classifier's benchmark tree this says 1.3 GB where a v5e's compiler
    asks 0.75; for the regressor's (1,000 features a node in 16 panels of
    64) 0.79 GB where it asks 0.51, and where the one-hot taken whole would
    be 2.1 GB alone."""
    A = min(2 ** (max_depth - 1), max_active)
    T, tiles, step = _level_shape(room, A)
    panel = feature_panel(max_features, n_bins)
    selected = -(-max_features // panel) * panel
    hist = 4 * A * n_stats * selected * n_bins
    rows = tiles * T
    scan = step * T * (4 * pack_words(d) * 3 + 2 * panel * n_bins)
    if max_features < d:
        if selects_by_take(max_features, d):  # the words rows-minor, the taken words
            scan += 4 * step * T * (pack_words(d) + selected)
        else:  # the selection's one-hots
            scan += 2 * step * 4 * pack_words(d) * selected
        hist += 8 * A * d  # the Gumbel draws and their top-k
    table = 4 * table_nodes(max_depth, max_active) * (5 + n_stats)
    return 3 * hist + rows * (selected + 4 * 4 * 3 + 6 * n_stats) + scan + table


def chunk_trees_for(trees: int, per_tree: int, free: int) -> int:
    """Trees to one dispatch: the largest divisor of `trees` (equal chunks,
    one compilation) whose builds fit in half of `free` bytes."""
    want = max(1, min(trees, free // 2 // max(per_tree, 1)))
    return next(c for c in range(want, 0, -1) if trees % c == 0)


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
    max_active: int | None = None,  # None: exact growth
    mesh=None,
    chunk_trees: int | None = None,  # test hook: fixed chunk size
):
    """Fit the whole forest: each device grows `trees_per_worker` trees on
    its local rows (reference `_estimators_per_worker` tree.py:330-341).
    Returns HOST TreeArrays with a leading (trees_per_worker * n_devices)
    axis.

    Trees are dispatched from the host in equal chunks sized from the
    shapes and the memory a device has left beside its rows and their bins
    (`chunk_trees_for`).  Trees are embarrassingly parallel and every
    count is exact, so chunking changes nothing but the dispatch count:
    the forest is bit-identical for any chunking and from fit to fit.

    Spans (docs/observability.md): `forest_bin` (edges and bins), one
    `forest_grow` per dispatched chunk (ended when the chunk is built),
    `forest_fetch`, `forest_assemble`; one `fact[forest]` a fit."""
    import numpy as np

    from ..parallel.device_cache import bytes_beside
    from ..parallel.mesh import fetch_replicated
    from ..tracing import fact, trace

    ndev = int(mesh.devices.size)
    m_local, d = int(X.shape[0]) // ndev, int(X.shape[1])
    # no frontier holds more nodes than the worker has rows
    width = m_local if max_active is None else max(1, min(int(max_active), m_local))
    n_stats = 3 if criterion == VARIANCE else int(n_classes)

    with trace("forest_bin"):
        packed, edges = forest_bins(X, valid, seed, n_bins, mesh)
        shift = label_shift(y, valid, criterion, mesh)
        jax.block_until_ready((packed, shift))

    room = rows_room(m_local, bootstrap, subsample)
    panel = feature_panel(max_features, n_bins)
    per_tree = tree_bytes(room, d, max_depth, n_bins, n_stats, max_features, width)
    if chunk_trees is not None:
        size = max(1, min(chunk_trees, trees_per_worker))
    else:
        free = bytes_beside(X) - packed.addressable_shards[0].data.nbytes
        size = chunk_trees_for(trees_per_worker, per_tree, max(free, 0))

    def grow(lo, room):
        return _forest_fit_chunk(
            packed, edges, y, valid, shift, seed, lo,
            count=min(size, trees_per_worker - lo),
            trees_per_worker=trees_per_worker,
            max_depth=max_depth,
            n_bins=n_bins,
            criterion=criterion,
            n_stats=n_stats,
            max_features=max_features,
            min_instances=min_instances,
            min_info_gain=min_info_gain,
            bootstrap=bootstrap,
            subsample=subsample,
            max_active=width,
            room=room,
            mesh=mesh,
        )

    chunks = []
    for lo in range(0, trees_per_worker, size):
        with trace("forest_grow"):
            trees, over = jax.block_until_ready(grow(lo, room))
            if np.asarray(fetch_replicated(over, mesh)).any():
                trees, _ = jax.block_until_ready(grow(lo, m_local))
            chunks.append(trees)
    with trace("forest_fetch"):
        chunks = [
            TreeArrays(*(np.asarray(fetch_replicated(t, mesh)) for t in chunk))
            for chunk in chunks
        ]

    # reassemble DEVICE-MAJOR: each chunk is (ndev*count, ...) device-major
    # over its own count; naive chunk concat would interleave devices, and
    # the caller's [:n_trees] trim of the padding counts on the order
    def reassemble(field):
        parts = [
            getattr(c, field).reshape(
                (ndev, -1) + getattr(c, field).shape[1:]
            )
            for c in chunks
        ]
        cat = np.concatenate(parts, axis=1)  # (ndev, trees_per_worker, ...)
        return cat.reshape((ndev * trees_per_worker,) + cat.shape[2:])

    # the host at work on the fetched tables: their order, and the walk of
    # every tree that the fact's `depth_reached` costs
    with trace("forest_assemble", detail="work"):
        trees = TreeArrays(*(reassemble(f) for f in TreeArrays._fields))
        internal = trees.feature >= 0
        fact(
            "forest",
            trees=int(trees.feature.shape[0]),
            internal_nodes=int(internal.sum()),
            depth_reached=_depth_reached(trees.left_child, internal),
            widest_frontier=int(min(2 ** (max_depth - 1), width)),
            bins=int(n_bins),
            features_per_node=int(max_features),
            criterion=("gini", "entropy", "variance")[criterion],
            feature_panel=int(panel),
            panels_per_level=int(-(-max_features // panel)),
            chunk_trees=int(size),
            tree_bytes=int(per_tree),
            edge_rule=EDGE_RULE,
        )
    return trees


def _depth_reached(left_child, internal) -> int:
    """Levels of splits in the deepest tree (host arrays)."""
    import numpy as np

    level = np.full(left_child.shape, -1, np.int32)
    level[:, 0] = 0
    reached = 0
    while True:
        t, i = np.nonzero((level == reached) & internal)
        if not len(t):
            return reached
        reached += 1
        level[t, left_child[t, i]] = level[t, left_child[t, i] + 1] = reached


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
