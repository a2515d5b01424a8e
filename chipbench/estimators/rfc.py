#
# chipbench/estimators/rfc.py: RandomForestClassifier, grown exactly level
# by level as the reference project's random_forest_classifier benchmark
# row runs it (gini, Poisson(1) bootstrap weights, floor(sqrt(cols))
# features drawn per node, quantile bins, `x <= threshold` goes left).
#
# The plain reference, independent of spark_rapids_ml_tpu/ops/forest.py.  It
# re-derives, with jax.random and numpy alone, what the estimator's stated
# rules draw: the worker's edge sample and its order statistics (the bin
# edges), every tree's bootstrap weights, every node's feature subset.  A
# forest is held to them by an AUDIT (`compare`), not by growing a second
# forest and matching nodes (one exact tie flips a whole subtree):
#   edges_off       every threshold is one of the reference's edges of its
#                   feature, bit for bit (the largest gap to the nearest)
#   leaf_count_off  every leaf's statistics against the exact weighted class
#                   counts of the rows the forest's OWN tree routes there by
#                   raw value (`x <= threshold`)
#   split_regret    per internal node, (best float64 gini gain among the
#                   node's allowed (feature, bin) candidates - the gain of
#                   the split chosen) over the parent's impurity, from the
#                   exact integer histogram of the rows routed to the node
#   stopped_early   leaves above maxDepth where an allowed split gains more
#                   than minInfoGain by over REGRET_TOL of the impurity
#                   (these two on the first, the middle and the last tree:
#                   every tree's histograms take the host minutes at 500,000
#                   x 3,000; the other four numbers are of every tree)
#   fits_differ     arrays of a fit not bit-identical to the first fit's
#                   since `build`
#   trees_off       |trees - numTrees|, plus one per tree that is deeper than
#                   maxDepth or whose pointers are not the heap's
# The control (`lowered`): the reference's own build with features compared
# to edges in bfloat16; audited by raw float32 value its leaves miscount.
#
# Rules restated (one worker; the program's are in ops/forest.py's header):
# base = fold_in(PRNGKey(seed), 0).  Edges: q = max(1, m // max(B^2, 10000)),
# S = m // q, sample row j = j*q + randint(fold_in(base, EDGE_STREAM), (S,),
# 0, q)[j], edge e of a feature = its sorted sample's element (e*S) // B.
# Tree t: key = split(base, numTrees)[t]; kb, kf = split(key); weights
# poisson(kb, rate, (m,)).  Node 2^l - 1 + s (level l, slot s): the K
# features of largest gumbel(fold_in(kf, l), (min(2^l, m), d), f32)[s].
#
from __future__ import annotations

import hashlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

LABELS = "sign"
# XLA module names of the programs that make the bins and grow the trees
PROGRAMS = {
    "forest_bin": ("_forest_sample_block", "_forest_edges", "_forest_bin_block"),
    "forest_grow": ("_forest_fit_chunk",),
}
EDGE_STREAM = 0x0ED6E5
REGRET_TOL = 1e-6  # a float32 gain from exact counts; the limit of split_regret
BROKEN = float(np.finfo(np.float64).max)  # a number that cannot be read: over any limit, finite in JSON
_STATE = ("threshold", "leaf_stats")  # what the fault test may alter, in this order
_first_fit: dict = {}


def build(params: dict, chips: int):
    """The estimator.  Until the model is made, its fit kernel's attributes
    carry the fitted floats as ONE vector under `coef_`, the thresholds
    first and then the leaf statistics: chipbench/tests' fault test alters
    `attrs["coef_"]` of whatever family it is given (PERF.md §7: an adapter
    should name its state, then this goes)."""
    from spark_rapids_ml_tpu.classification import RandomForestClassifier

    est = RandomForestClassifier(num_workers=chips, **params)
    fit_array, create_model = est._fit_array, est._create_model

    def fit_array_as_coef(fit_input):
        attrs = fit_array(fit_input)
        parts = [np.asarray(attrs.pop(k)) for k in _STATE]
        attrs["coef_shapes"] = [p.shape for p in parts]
        attrs["coef_"] = np.concatenate([p.ravel() for p in parts])
        return attrs

    def create_model_from_coef(attrs):
        state, at = attrs.pop("coef_"), 0
        for key, shape in zip(_STATE, attrs.pop("coef_shapes")):
            n = int(np.prod(shape))
            attrs[key] = state[at:at + n].reshape(shape)
            at += n
        return create_model(attrs)

    est._fit_array, est._create_model = fit_array_as_coef, create_model_from_coef
    _first_fit.clear()
    return est


def answer(model) -> dict:
    """What a fit returned, as host arrays, with the run's `fact[forest]`
    (None from a program that records none) and how many of the arrays
    differ from the first fit's since `build`."""
    ans = {
        "feature": np.asarray(model.feature),
        "threshold": np.asarray(model.threshold),
        "leaf_stats": np.asarray(model.leaf_stats),
        "left_child": np.asarray(model.left_child),
    }
    if not _first_fit:
        _first_fit.update(ans)
    first = _first_fit
    ans["fits_differ"] = sum(
        1 for k in ("feature", "threshold", "leaf_stats", "left_child")
        if first[k].shape != ans[k].shape or not np.array_equal(first[k], ans[k]))
    ans["fact"] = (model.fit_report() or {}).get("forest")
    return ans


def features_per_node(cols: int, params: dict) -> int:
    if params.get("featureSubsetStrategy", "auto") not in ("auto", "sqrt"):
        raise ValueError("the reference re-derives featureSubsetStrategy auto/sqrt alone")
    return max(1, int(np.sqrt(cols)))


def work(rows: int, cols: int, chips: int, params: dict) -> dict:
    """Least work per chip, from the shapes alone.  A level of one tree
    reads, per row, the bin ids of its node's K features and 12 bytes of
    row state (id, weight, label), and adds one count per feature; whatever
    implements the step does that much.  The bins: one read of the f32 rows
    and one write of 8-bit ids.  A fit is the bins once and maxDepth levels
    of each of the chip's trees."""
    share, k = rows / chips, features_per_node(cols, params)
    level = {"flops": share * k, "bytes": share * (k + 12.0)}
    bins = {"flops": 0.0, "bytes": share * cols * 5.0}
    trees = -(-int(params["numTrees"]) // chips)
    return {
        "kernels": {"forest_level": level, "forest_bin": bins},
        "levels": trees * int(params["maxDepth"]),
        "fit": [dict(level, count=trees * int(params["maxDepth"])), dict(bins, count=1)],
    }


# -- the draws, re-derived ----------------------------------------------------

def _base_key(params: dict):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(int(params["seed"])), 0)


def edge_sample_positions(params: dict, m: int) -> np.ndarray:
    import jax

    b = int(params["maxBins"])
    q = max(1, m // max(b * b, 10_000))
    n = m // q
    off = jax.random.randint(jax.random.fold_in(_base_key(params), EDGE_STREAM), (n,), 0, q)
    return np.arange(n, dtype=np.int64) * q + np.asarray(off, np.int64)


def tree_keys(params: dict, t: int):
    """(bootstrap key, feature key) of tree t."""
    import jax

    kb, kf = jax.random.split(jax.random.split(_base_key(params), int(params["numTrees"]))[t])
    return kb, kf


def bootstrap_weights(params: dict, t: int, m: int) -> np.ndarray:
    import jax

    if not params.get("bootstrap", True):
        if float(params.get("subsamplingRate", 1.0)) < 1.0:
            raise ValueError("the reference re-derives bootstrap or whole rows alone")
        return np.ones(m)
    rate = float(params.get("subsamplingRate", 1.0))
    return np.asarray(jax.random.poisson(tree_keys(params, t)[0], rate, (m,)), np.float64)


def node_features(params: dict, t: int, level: int, m: int, cols: int) -> np.ndarray:
    """(slots of the level, K) feature ids, ascending, of tree t's nodes."""
    import jax
    import jax.numpy as jnp

    k, width = features_per_node(cols, params), min(2 ** level, m)
    g = jax.random.gumbel(
        jax.random.fold_in(tree_keys(params, t)[1], level), (width, cols), jnp.float32)
    return np.sort(np.asarray(jax.lax.top_k(g, k)[1]), axis=1)


# -- passes over the device rows, a row block at a time --------------------------

def _rows_at(X, y, at: np.ndarray, block_rows: int) -> np.ndarray:
    """Rows `at` (ascending positions) of X as float32 host rows."""
    import jax
    import jax.numpy as jnp

    from chipbench import blocks

    def pick(Xb, yb, at, first):
        rel = at - first
        mine = (rel >= 0) & (rel < Xb.shape[0])
        rows = jnp.take(Xb, jnp.clip(rel, 0, Xb.shape[0] - 1), axis=0)
        return (jnp.where(mine[:, None], rows, 0.0),)

    call = blocks.block_caller(pick, X.sharding.mesh, block_rows, n_args=2)
    out = np.zeros((len(at), X.shape[1]), np.float32)
    for b in range(X.shape[0] // block_rows):
        lo, hi = np.searchsorted(at, [b * block_rows, (b + 1) * block_rows])
        if hi > lo:  # a window of the sample to a block: it is in row order
            (part,) = jax.device_get(call(
                X, y, np.int32(b), jnp.asarray(at[lo:hi], jnp.int32),
                np.int32(b * block_rows)))
            out[lo:hi] = part[0]
    return out


def _bin_rows(X, y, edges: np.ndarray, block_rows: int, lowered: bool) -> np.ndarray:
    """(rows, cols) uint8 on the host: edges strictly below each value,
    compared in float32 or, `lowered`, in bfloat16."""
    import jax
    import jax.numpy as jnp

    from chipbench import blocks

    def bins(Xb, yb, e):
        if lowered:
            Xb, e = Xb.astype(jnp.bfloat16), e.astype(jnp.bfloat16)
        return ((Xb[:, None, :] > e[None, :, :]).sum(axis=1).astype(jnp.uint8),)

    call = blocks.block_caller(bins, X.sharding.mesh, block_rows, n_args=1)
    out = np.empty(X.shape, np.uint8)
    e = jnp.asarray(edges)
    for b in range(X.shape[0] // block_rows):
        (part,) = jax.device_get(call(X, y, np.int32(b), e))
        out[b * block_rows:(b + 1) * block_rows] = part[0]
    return out


def _leaves(X, y, ans: dict, depth: int, block_rows: int) -> np.ndarray:
    """(trees, rows) int32: the node where each row comes to rest when the
    forest routes it by raw value, `x <= threshold` to the left child."""
    import jax
    import jax.numpy as jnp

    from chipbench import blocks

    n_nodes = ans["feature"].shape[1]

    def route(Xb, yb, feature, threshold, left_child):
        def one(feat, thr, lc):
            node = jnp.zeros((Xb.shape[0],), jnp.int32)
            for _ in range(depth):
                f = feat[node]
                x = jnp.take_along_axis(Xb, jnp.maximum(f, 0)[:, None], axis=1)[:, 0]
                child = jnp.clip(lc[node] + jnp.where(x <= thr[node], 0, 1), 0, n_nodes - 1)
                node = jnp.where(f < 0, node, child)
            return node

        return (jax.vmap(one)(feature, threshold, left_child),)

    call = blocks.block_caller(route, X.sharding.mesh, block_rows, n_args=3)
    trees = [jnp.asarray(ans[k]) for k in ("feature", "threshold", "left_child")]
    parts = [jax.device_get(call(X, y, np.int32(b), *trees))[0][0]
             for b in range(X.shape[0] // block_rows)]
    return np.concatenate(parts, axis=1)


# -- exact histograms and float64 gains -------------------------------------------

def _gini_gains(hist: np.ndarray, min_instances: float):
    """hist (nodes, K, B, C) exact counts -> (gain (nodes, K, B-1) float64
    with -inf where a side is under min_instances, impurity (nodes,))."""
    cum = np.cumsum(hist, axis=2)
    total = cum[:, :1, -1:, :]  # the same for every feature

    def gini(s):
        n = s.sum(axis=-1)
        p = s / np.maximum(n, 1e-300)[..., None]
        return np.where(n > 0, 1.0 - (p * p).sum(axis=-1), 0.0), n

    left = cum[:, :, :-1, :]
    imp_l, n_l = gini(left)
    imp_r, n_r = gini(total - left)
    imp_p, n_p = gini(total[:, 0, 0, :])
    gain = imp_p[:, None, None] - (n_l * imp_l + n_r * imp_r) / np.maximum(n_p, 1e-300)[:, None, None]
    allowed = (n_l >= min_instances) & (n_r >= min_instances)
    return np.where(allowed, gain, -np.inf), imp_p


_SLOTS_AT_ONCE = 256  # nodes to one histogram: 28 MB of float64 at 54 x 128 x 2


def _level_search(binned, rows, slot, feats, w, cls, n_bins: int, n_classes: int,
                  min_instances: float, chosen=None):
    """Per frontier slot of a level, from the exact (K, B, C) histogram of
    its rows (`rows` in slot `slot`, weights w, classes cls) over its own K
    features `feats[slot]`: (best float64 gain, its flat (feature index,
    bin) position, impurity) and, with `chosen` = (feature index, bin) per
    slot, the gain there."""
    n_slots, k = feats.shape
    best, at = np.full(n_slots, -np.inf), np.zeros(n_slots, np.int64)
    imp, got = np.zeros(n_slots), np.full(n_slots, -np.inf)
    order = np.argsort(slot, kind="stable")
    starts = np.arange(0, n_slots + _SLOTS_AT_ONCE, _SLOTS_AT_ONCE)
    cuts = np.searchsorted(slot[order], starts)
    for s0, lo, hi in zip(starts, cuts, cuts[1:]):
        s1 = min(s0 + _SLOTS_AT_ONCE, n_slots)
        part = order[lo:hi]
        local = slot[part] - s0
        b = binned[rows[part][:, None], feats[s0:s1][local]].astype(np.int64)  # (n, K)
        flat = ((local[:, None] * k + np.arange(k)) * n_bins + b) * n_classes + cls[part][:, None]
        hist = np.bincount(flat.ravel(), weights=np.repeat(w[part], k),
                           minlength=(s1 - s0) * k * n_bins * n_classes)
        gain, imp[s0:s1] = _gini_gains(hist.reshape(s1 - s0, k, n_bins, n_classes),
                                       min_instances)
        flat_gain = gain.reshape(s1 - s0, -1)
        at[s0:s1] = flat_gain.argmax(axis=1)
        best[s0:s1] = flat_gain[np.arange(s1 - s0), at[s0:s1]]
        if chosen is not None:
            got[s0:s1] = gain[np.arange(s1 - s0), chosen[0][s0:s1], chosen[1][s0:s1]]
    return best, at, imp, got


def _threads() -> int:
    return max(1, min(8, (os.cpu_count() or 2) - 1))


# -- the reference -----------------------------------------------------------------

def reference(X, y, params: dict, lowered: bool = False) -> dict:
    """What `compare` audits a forest against: the bin edges re-derived
    from the edge sample, the rows binned by them on the host, the labels.
    `lowered`: instead, the ANSWER of the reference's own build over rows
    binned in bfloat16, to be audited like a fit's."""
    from chipbench import blocks

    if X.sharding.mesh.devices.size != 1:
        raise ValueError("the forest reference audits one worker's trees")
    if params.get("impurity", "gini") != "gini":
        raise ValueError("the reference computes gini gains alone")
    m, n_bins = X.shape[0], int(params["maxBins"])
    block_rows = blocks.block_rows_of(X)
    sample = np.sort(_rows_at(X, y, edge_sample_positions(params, m), block_rows), axis=0)
    edges = sample[(np.arange(1, n_bins) * len(sample)) // n_bins]  # (B-1, cols)
    ref = {
        "X": X, "y": y, "edges": edges, "params": dict(params),
        "labels": np.asarray(y).astype(np.int64),
        "binned": _bin_rows(X, y, edges, block_rows, lowered),
        "block_rows": block_rows, "audits": {},
    }
    return grow(ref) if lowered else ref


def grow(ref: dict) -> dict:
    """The reference's own forest over `ref`'s binned rows, as an answer:
    level by level, every node's exact histogram over its K features, the
    first best float64 gain, rows routed by bin id.  Heap node table."""
    params, binned, cls = ref["params"], ref["binned"], ref["labels"]
    m, cols = binned.shape
    depth, n_bins = int(params["maxDepth"]), int(params["maxBins"])
    n_classes, n_trees = int(cls.max()) + 1, int(params["numTrees"])
    min_inst, min_gain = float(params["minInstancesPerNode"]), float(params["minInfoGain"])
    if 2 ** (depth - 1) > m:
        raise ValueError("the reference numbers nodes as a heap: 2^(maxDepth-1) <= rows")
    n_nodes = 2 ** (depth + 1) - 1

    def one_tree(t):
        w = bootstrap_weights(params, t, m)
        feature = np.full(n_nodes, -1, np.int32)
        threshold = np.zeros(n_nodes, np.float32)
        stats = np.zeros((n_nodes, n_classes), np.float32)
        rows = np.nonzero(w > 0)[0]
        node = np.zeros(len(rows), np.int64)
        for level in range(depth + 1):
            first = 2 ** level - 1
            counts = np.bincount((node - first) * n_classes + cls[rows], weights=w[rows],
                                 minlength=2 ** level * n_classes).reshape(-1, n_classes)
            if level == depth or not len(rows):
                stats[first:first + 2 ** level] = counts
                break
            feats = node_features(params, t, level, m, cols)
            gain, best, _, _ = _level_search(binned, rows, node - first, feats, w[rows],
                                             cls[rows], n_bins, n_classes, min_inst)
            splits = gain > min_gain
            bj, bb = best // (n_bins - 1), best % (n_bins - 1)
            bf = feats[np.arange(len(feats)), bj]
            at = first + np.nonzero(splits)[0]
            feature[at], threshold[at] = bf[splits], ref["edges"][bb[splits], bf[splits]]
            stats[first:first + 2 ** level] = np.where(splits[:, None], 0.0, counts)
            slot = node - first
            moving = splits[slot]
            rows, slot = rows[moving], slot[moving]
            node = 2 * (first + slot) + 1 + (binned[rows, bf[slot]] > bb[slot])
        left = np.where(feature >= 0, 2 * np.arange(n_nodes) + 1, -1).astype(np.int32)
        return feature, threshold, stats, left

    with ThreadPoolExecutor(_threads()) as pool:
        trees = list(pool.map(one_tree, range(n_trees)))
    keys = ("feature", "threshold", "leaf_stats", "left_child")
    ans = {k: np.stack([t[i] for t in trees]) for i, k in enumerate(keys)}
    ans.update(fits_differ=0, fact=None)
    return ans


def _heap_levels(feature: np.ndarray, left_child: np.ndarray, depth: int):
    """Level of every node the tree reaches (-1 elsewhere), or None when the
    pointers are not the heap's, a node is out of the table or a split lies
    at maxDepth."""
    n_nodes = len(feature)
    level = np.full(n_nodes, -1, np.int32)
    level[0] = 0
    ids = np.arange(n_nodes)
    for lv in range(depth + 1):
        at = ids[(level == lv) & (feature >= 0)]
        if not len(at):
            return level
        if lv == depth or (left_child[at] != 2 * at + 1).any() or 2 * at.max() + 2 >= n_nodes:
            return None
        level[2 * at + 1] = level[2 * at + 2] = lv + 1
    return level


def audit(ans: dict, ref: dict) -> dict:
    """The six numbers of `compare` for one forest (see the file's head)."""
    params, binned, cls, edges = ref["params"], ref["binned"], ref["labels"], ref["edges"]
    m, cols = binned.shape
    depth, n_bins = int(params["maxDepth"]), int(params["maxBins"])
    min_inst, min_gain = float(params["minInstancesPerNode"]), float(params["minInfoGain"])
    feature, threshold = ans["feature"], ans["threshold"]
    n_trees, n_nodes = feature.shape
    n_classes = ans["leaf_stats"].shape[-1]
    levels = [_heap_levels(feature[t], ans["left_child"][t], depth) for t in range(n_trees)]
    out = {
        "trees_off": float(abs(n_trees - int(params["numTrees"]))
                           + sum(lv is None for lv in levels)),
        "fits_differ": float(ans["fits_differ"]),
    }
    # thresholds against the edges of their features
    t_at, n_at = np.nonzero(feature >= 0)
    f_at = np.clip(feature[t_at, n_at], 0, cols - 1)
    gaps = np.abs(edges[:, f_at].astype(np.float64) - threshold[t_at, n_at]).min(axis=0)
    out["edges_off"] = float(gaps.max()) if len(gaps) else 0.0
    if int(cls.max()) + 1 > n_classes:
        return dict(out, leaf_count_off=BROKEN, split_regret=BROKEN, stopped_early=BROKEN)
    leaves = _leaves(ref["X"], ref["y"], ans, depth, ref["block_rows"])

    def one_tree(t):
        w = bootstrap_weights(params, t, m)
        counts = np.bincount(leaves[t].astype(np.int64) * n_classes + cls, weights=w,
                             minlength=n_nodes * n_classes).reshape(n_nodes, n_classes)
        off = float(np.abs(ans["leaf_stats"][t] - counts).max())
        level = levels[t]
        if level is None:
            return off, BROKEN, BROKEN
        regret, early = 0.0, 0
        if t not in (0, n_trees // 2, n_trees - 1):
            return off, regret, early
        rows = np.nonzero(w > 0)[0]
        cur = leaves[t][rows].astype(np.int64)
        for lv in range(min(depth, int(level.max())), -1, -1):
            here = level[cur] == lv  # rows whose path holds a node of this level
            if lv < depth:
                first = 2 ** lv - 1
                feats = node_features(params, t, lv, m, cols)
                node = first + np.arange(2 ** lv)
                split = (level[node] == lv) & (feature[t, node] >= 0)
                # the chosen (feature, bin) among the node's own candidates
                f, thr = feature[t, node], threshold[t, node]
                j = (feats == f[:, None]).argmax(axis=1)
                b = (edges[:, np.clip(f, 0, cols - 1)] == thr[None, :]).argmax(axis=0)
                found = (feats[np.arange(len(j)), j] == f) & (
                    edges[b, np.clip(f, 0, cols - 1)] == thr)
                r = rows[here]
                best, _, imp, chosen = _level_search(
                    binned, r, cur[here] - first, feats, w[r], cls[r], n_bins, n_classes,
                    min_inst, chosen=(j, b))
                chosen = np.where(found, chosen, -np.inf)
                scale = np.where(imp > 0, imp, 1.0)
                with np.errstate(invalid="ignore"):
                    lost = np.where(split, (best - chosen) / scale, 0.0)
                regret = max(regret, float(np.nan_to_num(lost, nan=BROKEN, posinf=BROKEN).max()))
                leaf = (level[node] == lv) & (feature[t, node] < 0)
                early += int((leaf & (best > min_gain + REGRET_TOL * scale)).sum())
            cur = np.where(here, (cur - 1) // 2, cur)
        return off, regret, early

    with ThreadPoolExecutor(_threads()) as pool:
        per_tree = list(pool.map(one_tree, range(n_trees)))
    out["leaf_count_off"] = max((p[0] for p in per_tree), default=0.0)
    out["split_regret"] = max((p[1] for p in per_tree), default=0.0)
    out["stopped_early"] = float(sum(p[2] for p in per_tree))
    return out


def compare(ans: dict, ref: dict) -> dict:
    """The numbers held to the configuration's `limits`.  A window's fits
    are bit-identical, so a forest already audited is not audited again."""
    digest = hashlib.sha1()
    for k in ("feature", "threshold", "leaf_stats", "left_child"):
        digest.update(np.ascontiguousarray(ans[k]).tobytes())
    key = (digest.hexdigest(), ans["fits_differ"])
    if key not in ref["audits"]:
        ref["audits"][key] = audit(ans, ref)
    return dict(ref["audits"][key])
