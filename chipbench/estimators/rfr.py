#
# chipbench/estimators/rfr.py: RandomForestRegressor, grown exactly level by
# level as the reference project's random_forest_regressor benchmark row runs
# it (variance, Poisson(1) bootstrap weights, cols // 3 features drawn per
# node, quantile bins, `x <= threshold` goes left).
#
# The plain reference, independent of spark_rapids_ml_tpu/ops/forest.py: as
# chipbench/estimators/rfc.py (whose draws and routing helpers it imports and
# does not edit), an AUDIT of the program's own trees with jax.random and
# numpy alone, float64 throughout (`compare`):
#   edges_off        every threshold is one of the reference's edges of its
#                    feature, bit for bit (the largest gap to the nearest)
#   leaf_weight_off  a leaf's weight against the exact Poisson-weighted count
#                    of the rows its OWN tree routes there by raw value
#                    (`x <= threshold`): integers
#   leaf_stat_gap    largest gap of a leaf's sum y and sum y^2 from their
#                    float64 sums over those rows, over the leaf's sum |w y|
#                    and sum w y^2
#   split_regret     per internal node, (best float64 variance gain among the
#                    node's allowed (feature, bin) candidates - the gain of
#                    the split chosen) over the node's variance, from the
#                    float64 histogram of the rows routed to the node
#   stopped_early    leaves above maxDepth where an allowed split gains more
#                    than minInfoGain by over REGRET_TOL of the variance
#                    (these two on the first REGRET_TREES trees, every node of
#                    each: a tree's histograms over cols // 3 features a node
#                    take the host a quarter of a minute at 500,000 x 3,000;
#                    the other numbers are of every tree)
#   fits_differ      arrays of a fit not bit-identical to the first fit's
#                    since `build`
#   trees_off        |trees - numTrees|, plus one per tree that is deeper than
#                    maxDepth or whose pointers are not the heap's
# The gain of a split is (S_l - n_l S/n)^2 / (n_l n_r), S the sums of w y and
# n those of w: the variance decrease, written so that nothing of the size of
# mean^2 cancels; float64 over labels less their float64 mean.
# The control (`lowered`): the reference's own build with each row's w y and
# w y^2 rounded to ONE bfloat16 part before they are added, and features
# compared to edges in bfloat16 (the classifier's control, kept).
#
# Rules restated (one worker; the program's are in ops/forest.py's header):
# the edges, a tree's keys and its Poisson weights as rfc.py restates them;
# node 2^l - 1 + s (level l, slot s): the K = cols // 3 features of largest
# gumbel(fold_in(kf, l), (min(2^l, m), cols), f32)[s].  The program takes a
# constant off the labels before it forms its sums (the worker's weighted
# mean, float32) and puts it back on the leaves: gains do not depend on it,
# so the reference needs none and audits the leaves as the model reads them.
#
from __future__ import annotations

import hashlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench import manifest as mf

_rfc = mf.adapter("rfc")  # the draws, the passes over the rows, the heap walk

LABELS = "linear"
# XLA module names of the programs that make the bins (and the label shift)
# and grow the trees
PROGRAMS = {
    "forest_bin": _rfc.PROGRAMS["forest_bin"] + ("_forest_label_shift",),
    "forest_grow": _rfc.PROGRAMS["forest_grow"],
}
REGRET_TOL = 1e-6  # what `stopped_early` lets a gain pass minInfoGain by
REGRET_TREES = 5  # trees whose every node's split is ranked again in float64
BROKEN = _rfc.BROKEN
_STATE = ("threshold", "leaf_stats")  # what the fault test may alter, in this order
_KEYS = ("feature", "threshold", "leaf_stats", "left_child")


def build(params: dict, chips: int):
    """The estimator.  As rfc.py's: until the model is made, its fit
    kernel's attributes carry the fitted floats as ONE vector under `coef_`,
    the thresholds first and then the leaf statistics, for chipbench/tests'
    fault test."""
    from spark_rapids_ml_tpu.regression import RandomForestRegressor

    est = RandomForestRegressor(num_workers=chips, **params)
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
    _rfc._first_fit.clear()
    return est


# what a fit returned, with the run's `fact[forest]` and `fits_differ`
answer = _rfc.answer


def features_per_node(cols: int, params: dict) -> int:
    if params.get("featureSubsetStrategy", "auto") not in ("auto", "onethird"):
        raise ValueError("the reference re-derives featureSubsetStrategy auto/onethird alone")
    return max(1, cols // 3)


def work(rows: int, cols: int, chips: int, params: dict) -> dict:
    """Least work per chip, from the shapes alone, as `rfc.work` with K =
    cols // 3: a level of one tree reads, per row, the bin ids of its node's
    K features and 12 bytes of row state, and adds three statistics per
    feature.  The bins: one read of the f32 rows, one write of 8-bit ids."""
    share, k = rows / chips, features_per_node(cols, params)
    level = {"flops": share * k * 3.0, "bytes": share * (k + 12.0)}
    bins = {"flops": 0.0, "bytes": share * cols * 5.0}
    trees = -(-int(params["numTrees"]) // chips)
    return {
        "kernels": {"forest_level": level, "forest_bin": bins},
        "levels": trees * int(params["maxDepth"]),
        "fit": [dict(level, count=trees * int(params["maxDepth"])), dict(bins, count=1)],
    }


def node_features(params: dict, t: int, level: int, m: int, cols: int) -> np.ndarray:
    """(slots of the level, K) feature ids, ascending, of tree t's nodes."""
    import jax
    import jax.numpy as jnp

    k, width = features_per_node(cols, params), min(2 ** level, m)
    g = jax.random.gumbel(
        jax.random.fold_in(_rfc.tree_keys(params, t)[1], level), (width, cols), jnp.float32)
    return np.sort(np.asarray(jax.lax.top_k(g, k)[1]), axis=1)


# -- float64 histograms and gains ------------------------------------------------

def _bf16(a: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return a.astype(np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)


def _products(ref: dict, t: int):
    """(w, w y, w y^2) of tree t's rows, float64, y less its float64 mean;
    `lowered`: the two products each rounded to one bfloat16 part."""
    w = _rfc.bootstrap_weights(ref["params"], t, len(ref["centred"]))
    p1, p2 = w * ref["centred"], w * ref["centred"] ** 2
    return (w, _bf16(p1), _bf16(p2)) if ref["lowered"] else (w, p1, p2)


def _node_search(binned_t: np.ndarray, rows: np.ndarray, feats: np.ndarray, w, p1,
                 n_bins: int, min_instances: float, chosen=None):
    """One node, from the float64 (K, B) histograms of its `rows` (weights
    w, products p1 = w y) over its own features `feats`: (best gain, its
    flat (feature index, bin) position, the gain at `chosen` = (feature
    index, bin) or -inf)."""
    if len(rows) * 8 > binned_t.shape[1]:
        ids = np.take(binned_t[feats], rows, axis=1)  # (K, n)
    else:
        ids = binned_t[np.ix_(feats, rows)]
    n_b, s_b = np.empty((len(feats), n_bins)), np.empty((len(feats), n_bins))
    for k in range(len(feats)):
        n_b[k] = np.bincount(ids[k], weights=w, minlength=n_bins)
        s_b[k] = np.bincount(ids[k], weights=p1, minlength=n_bins)
    n, s = n_b[0].sum(), s_b[0].sum()
    n_l, s_l = np.cumsum(n_b, axis=1)[:, :-1], np.cumsum(s_b, axis=1)[:, :-1]
    n_r = n - n_l
    dev = s_l - n_l * (s / max(n, 1e-300))
    gain = dev * dev / np.maximum(n_l * n_r, 1e-300)
    gain = np.where((n_l >= min_instances) & (n_r >= min_instances), gain, -np.inf)
    at = int(gain.argmax())
    got = -np.inf if chosen is None else float(gain[chosen])
    return float(gain.ravel()[at]), at, got


def _rows_by_slot(rows: np.ndarray, slot: np.ndarray, width: int):
    """(slot, its rows) for every slot of a level that holds rows."""
    order = np.argsort(slot, kind="stable")
    cuts = np.searchsorted(slot[order], np.arange(width + 1))
    for s in range(width):
        if cuts[s + 1] > cuts[s]:
            yield s, rows[order[cuts[s]:cuts[s + 1]]]


def _variance(w, p1, p2) -> float:
    n = w.sum()
    return float(max(p2.sum() / n - (p1.sum() / n) ** 2, 0.0)) if n > 0 else 0.0


# -- the reference ---------------------------------------------------------------

def reference(X, y, params: dict, lowered: bool = False) -> dict:
    """What `compare` audits a forest against: the bin edges re-derived from
    the edge sample, the rows binned by them (features by rows, on the
    host), the labels in float64.  `lowered`: instead, the ANSWER of the
    reference's own build with one-part bfloat16 products over rows binned
    in bfloat16, to be audited like a fit's."""
    from chipbench import blocks

    if X.sharding.mesh.devices.size != 1:
        raise ValueError("the forest reference audits one worker's trees")
    if params.get("impurity", "variance") != "variance":
        raise ValueError("the reference computes variance gains alone")
    features_per_node(X.shape[1], params)
    m, n_bins = X.shape[0], int(params["maxBins"])
    block_rows = blocks.block_rows_of(X)
    sample = np.sort(
        _rfc._rows_at(X, y, _rfc.edge_sample_positions(params, m), block_rows), axis=0)
    edges = sample[(np.arange(1, n_bins) * len(sample)) // n_bins]  # (B-1, cols)
    labels = np.asarray(y).astype(np.float64)
    ref = {
        "X": X, "y": y, "edges": edges, "params": dict(params), "lowered": lowered,
        "labels": labels, "centred": labels - labels.mean(),
        "binned_t": np.ascontiguousarray(_rfc._bin_rows(X, y, edges, block_rows, lowered).T),
        "block_rows": block_rows, "audits": {},
    }
    return grow(ref) if lowered else ref


def grow(ref: dict) -> dict:
    """The reference's own forest over `ref`'s binned rows, as an answer:
    level by level, every node's float64 histogram over its K features, the
    first best gain, rows routed by bin id.  Heap node table; a leaf holds
    (w, sum y, sum y^2) of the labels as given."""
    params, binned_t, edges = ref["params"], ref["binned_t"], ref["edges"]
    cols, m = binned_t.shape
    depth, n_bins = int(params["maxDepth"]), int(params["maxBins"])
    n_trees = int(params["numTrees"])
    min_inst, min_gain = float(params["minInstancesPerNode"]), float(params["minInfoGain"])
    if 2 ** (depth - 1) > m:
        raise ValueError("the reference numbers nodes as a heap: 2^(maxDepth-1) <= rows")
    n_nodes, mean = 2 ** (depth + 1) - 1, float(ref["labels"].mean())

    def one_tree(t):
        w, p1, p2 = _products(ref, t)
        feature = np.full(n_nodes, -1, np.int32)
        threshold = np.zeros(n_nodes, np.float32)
        stats = np.zeros((n_nodes, 3), np.float64)
        rows = np.nonzero(w > 0)[0]
        node = np.zeros(len(rows), np.int64)
        for level in range(depth + 1):
            first, width = 2 ** level - 1, 2 ** level
            sums = np.stack([np.bincount(node - first, weights=v[rows], minlength=width)
                             for v in (w, p1, p2)], axis=1)
            if level == depth or not len(rows):
                stats[first:first + width] = sums
                break
            feats = node_features(params, t, level, m, cols)
            splits = np.zeros(width, bool)
            bf, bb = np.zeros(width, np.int64), np.zeros(width, np.int64)
            for s, mine in _rows_by_slot(rows, node - first, width):
                gain, at, _ = _node_search(
                    binned_t, mine, feats[s], w[mine], p1[mine], n_bins, min_inst)
                splits[s] = gain > min_gain
                bf[s], bb[s] = feats[s, at // (n_bins - 1)], at % (n_bins - 1)
            at = first + np.nonzero(splits)[0]
            feature[at], threshold[at] = bf[splits], edges[bb[splits], bf[splits]]
            stats[first:first + width] = np.where(splits[:, None], 0.0, sums)
            slot = node - first
            moving = splits[slot]
            rows, slot = rows[moving], slot[moving]
            node = 2 * (first + slot) + 1 + (binned_t[bf[slot], rows] > bb[slot])
        # sums of the labels as given, from those of the labels less their mean
        n, s1, s2 = stats.T
        stats = np.stack([n, s1 + mean * n, s2 + 2 * mean * s1 + mean * mean * n], axis=1)
        left = np.where(feature >= 0, 2 * np.arange(n_nodes) + 1, -1).astype(np.int32)
        return feature, threshold, stats.astype(np.float32), left

    with ThreadPoolExecutor(_rfc._threads()) as pool:
        trees = list(pool.map(one_tree, range(n_trees)))
    ans = {k: np.stack([t[i] for t in trees]) for i, k in enumerate(_KEYS)}
    ans.update(fits_differ=0, fact=None)
    return ans


def _tree_regret(ans: dict, ref: dict, t: int, level, leaves_t: np.ndarray):
    """(split_regret, stopped_early) of tree t, every node of it."""
    params, binned_t, edges = ref["params"], ref["binned_t"], ref["edges"]
    cols, m = binned_t.shape
    depth, n_bins = int(params["maxDepth"]), int(params["maxBins"])
    min_inst, min_gain = float(params["minInstancesPerNode"]), float(params["minInfoGain"])
    feature, threshold = ans["feature"][t], ans["threshold"][t]
    # float64 throughout, whatever `ref` was made for
    w, p1, p2 = _products(dict(ref, lowered=False), t)
    rows = np.nonzero(w > 0)[0]
    cur = leaves_t[rows].astype(np.int64)
    regret, early = 0.0, 0
    for lv in range(min(depth, int(level.max())), -1, -1):
        here = level[cur] == lv  # rows whose path holds a node of this level
        if lv < depth:
            first = 2 ** lv - 1
            feats = node_features(params, t, lv, m, cols)
            for s, mine in _rows_by_slot(rows[here], cur[here] - first, 2 ** lv):
                node = first + s
                if level[node] != lv:
                    continue
                f, thr = int(feature[node]), threshold[node]
                chosen = None
                if f >= 0:  # the chosen (feature, bin) among the node's own candidates
                    j = int(np.searchsorted(feats[s], f))
                    if j < len(feats[s]) and feats[s, j] == f:  # so f is a column
                        b = int((edges[:, f] == thr).argmax())
                        chosen = (j, b) if edges[b, f] == thr else None
                best, _, got = _node_search(
                    binned_t, mine, feats[s], w[mine], p1[mine], n_bins, min_inst, chosen)
                var = _variance(w[mine], p1[mine], p2[mine])
                scale = var if var > 0 else 1.0
                if f >= 0:
                    lost = (best - got) / scale
                    regret = max(regret, lost if np.isfinite(lost) else BROKEN)
                elif best > min_gain + REGRET_TOL * scale:
                    early += 1
        cur = np.where(here, (cur - 1) // 2, cur)
    return regret, early


def audit(ans: dict, ref: dict) -> dict:
    """The seven numbers of `compare` for one forest (see the file's head)."""
    params, edges, labels = ref["params"], ref["edges"], ref["labels"]
    cols, m = ref["binned_t"].shape
    depth = int(params["maxDepth"])
    feature, threshold = ans["feature"], ans["threshold"]
    n_trees, n_nodes = feature.shape
    levels = [_rfc._heap_levels(feature[t], ans["left_child"][t], depth) for t in range(n_trees)]
    out = {
        "trees_off": float(abs(n_trees - int(params["numTrees"]))
                           + sum(lv is None for lv in levels)),
        "fits_differ": float(ans["fits_differ"]),
    }
    t_at, n_at = np.nonzero(feature >= 0)
    f_at = np.clip(feature[t_at, n_at], 0, cols - 1)
    gaps = np.abs(edges[:, f_at].astype(np.float64) - threshold[t_at, n_at]).min(axis=0)
    out["edges_off"] = float(gaps.max()) if len(gaps) else 0.0
    if ans["leaf_stats"].shape[-1] != 3:
        return dict(out, leaf_weight_off=BROKEN, leaf_stat_gap=BROKEN,
                    split_regret=BROKEN, stopped_early=BROKEN)
    leaves = _rfc._leaves(ref["X"], ref["y"], ans, depth, ref["block_rows"])

    def one_tree(t):
        w = _rfc.bootstrap_weights(params, t, m)
        at = leaves[t].astype(np.int64)
        exact = [np.bincount(at, weights=v, minlength=n_nodes)
                 for v in (w, w * labels, w * labels ** 2, w * np.abs(labels))]
        held = ans["leaf_stats"][t].astype(np.float64)
        off = float(np.abs(held[:, 0] - exact[0]).max())
        gap = 0.0
        for got, want, scale in ((held[:, 1], exact[1], exact[3]), (held[:, 2], exact[2], exact[2])):
            miss = np.abs(got - want)
            with np.errstate(divide="ignore", invalid="ignore"):
                rel = np.where(scale > 0, miss / scale, np.where(miss > 0, BROKEN, 0.0))
            gap = max(gap, float(np.nan_to_num(rel, nan=BROKEN, posinf=BROKEN).max()))
        if levels[t] is None:
            return off, gap, BROKEN, BROKEN
        if t >= REGRET_TREES:
            return off, gap, 0.0, 0
        return (off, gap) + _tree_regret(ans, ref, t, levels[t], leaves[t])

    with ThreadPoolExecutor(_rfc._threads()) as pool:
        per_tree = list(pool.map(one_tree, range(n_trees)))
    out["leaf_weight_off"] = max((p[0] for p in per_tree), default=0.0)
    out["leaf_stat_gap"] = max((p[1] for p in per_tree), default=0.0)
    out["split_regret"] = max((p[2] for p in per_tree), default=0.0)
    out["stopped_early"] = float(sum(p[3] for p in per_tree))
    return out


def compare(ans: dict, ref: dict) -> dict:
    """The numbers held to the configuration's `limits`.  A window's fits
    are bit-identical, so a forest already audited is not audited again."""
    digest = hashlib.sha1()
    for k in _KEYS:
        digest.update(np.ascontiguousarray(ans[k]).tobytes())
    key = (digest.hexdigest(), ans["fits_differ"])
    if key not in ref["audits"]:
        ref["audits"][key] = audit(ans, ref)
    return dict(ref["audits"][key])
