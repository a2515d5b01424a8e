#
# What the reference's random_forest_classifier benchmark row (one worker's
# 500,000 x 3,000, depth 13; chipbench's `rfc_fit_cached`) asks of the
# program, at sizes the CPU runs: the estimator against the benchmark's
# plain reference (every number of its audit at its limit), the bfloat16
# control over it, the bins and the edge rule, a forest that is the same
# whatever the chunking, and exact growth where the old default capped.
#
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import blocks, datagen
from chipbench import manifest as mf
from spark_rapids_ml_tpu.classification import RandomForestClassifier
from spark_rapids_ml_tpu.data import DeviceDataset
from spark_rapids_ml_tpu.ops import forest as forest_ops
from spark_rapids_ml_tpu.parallel import get_mesh

ROWS, COLS = 4096, 48
PARAMS = {
    "numTrees": 3, "maxDepth": 13, "maxBins": 128, "impurity": "gini",
    "featureSubsetStrategy": "auto", "bootstrap": True, "subsamplingRate": 1.0,
    "minInstancesPerNode": 1, "minInfoGain": 0.0, "seed": 1,
}
SIGN = {"model": "hidden_direction", "labels": "sign"}
LIMITS = mf.cell(mf.load_manifest(), "rfc_fit_cached")["config_file"]["limits"]


@pytest.fixture(scope="module")
def adapter():
    return mf.adapter("rfc")


def _rows(seed):
    mesh = get_mesh(1)
    X, y, w = datagen.make_rows(mesh, ROWS, COLS, seed, SIGN, 256)
    return mesh, X, y, w


def _fit(adapter, params, seed):
    mesh, X, y, w = _rows(seed)
    model = adapter.build(params, 1).fit(DeviceDataset(mesh, X, ROWS, y=y, weight=w))
    return model, X, y


# -- the estimator against the plain reference ---------------------------------

@pytest.mark.parametrize("bootstrap", [True, False])
@pytest.mark.parametrize("seed", [2**31 + 7, 11])
def test_estimator_meets_every_limit_of_the_plain_reference(adapter, seed, bootstrap,
                                                            monkeypatch):
    monkeypatch.setattr(blocks, "BLOCK_ROWS", 256)
    params = dict(PARAMS, bootstrap=bootstrap)
    model, X, y = _fit(adapter, params, seed)
    got = adapter.compare(adapter.answer(model), adapter.reference(X, y, params))
    assert set(got) == set(LIMITS)
    assert all(got[k] <= LIMITS[k] for k in LIMITS), got
    # the tree is deep and wide: past the old default's 256 nodes a level
    fact = model.fit_report()["forest"]
    assert fact["depth_reached"] == 13 and fact["trees"] == 3
    assert fact["internal_nodes"] == int((model.feature >= 0).sum()) > 3 * 256
    assert fact["features_per_node"] == 6 and fact["bins"] == 128


@pytest.mark.parametrize("seed", [2**31 + 7, 11])
def test_bfloat16_binning_control_is_over_the_limit(adapter, seed, monkeypatch):
    monkeypatch.setattr(blocks, "BLOCK_ROWS", 256)
    _, X, y, _ = _rows(seed)
    ref = adapter.reference(X, y, PARAMS)
    own = adapter.compare(adapter.grow(ref), ref)
    assert all(own[k] <= LIMITS[k] for k in LIMITS), own  # the reference passes its audit
    low = adapter.compare(adapter.reference(X, y, PARAMS, lowered=True), ref)
    assert low["leaf_count_off"] > LIMITS["leaf_count_off"], low


@pytest.mark.parametrize("fault,number", [
    ("threshold_1pct", "edges_off"), ("leaf_stats_zero", "leaf_count_off"),
    ("one_tree_short", "trees_off"), ("pointer_off_heap", "trees_off"),
    ("worse_split", "split_regret"), ("leaf_for_split", "stopped_early"),
])
def test_audit_catches(adapter, fault, number, monkeypatch):
    monkeypatch.setattr(blocks, "BLOCK_ROWS", 256)
    _, X, y, _ = _rows(5)
    ref = adapter.reference(X, y, PARAMS)
    ans = {k: np.array(v, copy=True) if isinstance(v, np.ndarray) else v
           for k, v in adapter.grow(ref).items()}
    if fault == "threshold_1pct":
        ans["threshold"][0, 0] *= 1.01
    elif fault == "leaf_stats_zero":
        ans["leaf_stats"][...] = 0.0
    elif fault == "one_tree_short":
        ans = {k: v[:-1] if isinstance(v, np.ndarray) else v for k, v in ans.items()}
    elif fault == "pointer_off_heap":
        ans["left_child"][1, 0] = 3
    elif fault == "worse_split":
        # the root's split moved to another edge of the same feature; the
        # leaves recounted, so only the regret can tell
        f = ans["feature"][0, 0]
        at = int(np.nonzero(ref["edges"][:, f] == ans["threshold"][0, 0])[0][0])
        ans["threshold"][0, 0] = ref["edges"][(at + 40) % 127, f]
    elif fault == "leaf_for_split":
        node = int(np.nonzero(ans["feature"][0] >= 0)[0][-1])  # a deepest split
        ans["feature"][0, node], ans["left_child"][0, node] = -1, -1
    got = adapter.compare(ans, ref)
    assert got[number] > LIMITS[number], got


# -- bins and edges ---------------------------------------------------------------

def test_uint8_bins_equal_the_int32_bins_and_pack_round_trips(rng):
    X = jnp.asarray(rng.normal(size=(500, 13)).astype(np.float32))
    edges = forest_ops.compute_bin_edges(X, 128)
    bins = forest_ops.digitize(X, edges)
    assert bins.dtype == jnp.uint8
    old = (X[:, None, :] > edges[None, :, :]).sum(axis=1).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(bins, np.int32), np.asarray(old))
    # a threshold routes the raw value as its bin id routed during the build
    e, b = np.asarray(edges), np.asarray(bins)
    for cut in (0, 63, 126):
        np.testing.assert_array_equal(np.asarray(X) <= e[cut], b <= cut)
    planes = forest_ops._unpack_planes(forest_ops.pack_bins(bins), jnp.float32)
    back = np.concatenate([np.asarray(p, np.float32) for p in planes], axis=1)[:, :13]
    np.testing.assert_array_equal(back, b)
    assert forest_ops.pack_words(3000) == 768 and forest_ops.pack_words(48) == 12


@pytest.mark.parametrize("rows,bins", [(ROWS, 32), (40_000, 128)])
def test_edge_rule_is_numpy_order_statistics_of_the_stated_sample(adapter, rows, bins):
    mesh = get_mesh(1)
    X, y, w = datagen.make_rows(mesh, rows, 5, 3, SIGN, 256)
    _, edges = forest_ops.forest_bins(X, w, 9, bins, mesh)
    at = adapter.edge_sample_positions({"seed": 9, "maxBins": bins}, rows)
    want = max(bins * bins, 10_000)
    assert len(at) == rows // max(1, rows // want) and (np.diff(at) > 0).all()
    sample = np.sort(np.asarray(X)[at], axis=0)
    np.testing.assert_array_equal(
        np.asarray(edges), sample[(np.arange(1, bins) * len(at)) // bins])


def test_more_than_256_bins_is_refused():
    mesh, X, y, w = _rows(1)
    with pytest.raises(ValueError, match="maxBins"):
        RandomForestClassifier(numTrees=1, maxDepth=2, maxBins=257).fit(
            DeviceDataset(mesh, X, ROWS, y=y, weight=w))


# -- chunks, and growth without a cap ------------------------------------------------

@pytest.mark.parametrize("chunk", [1, 3, None])
def test_forest_is_bit_identical_for_any_chunking(chunk, monkeypatch):
    mesh, X, y, w = _rows(21)
    real = forest_ops.forest_fit
    sizes = []

    def fit(chunk_trees):
        monkeypatch.setattr(
            forest_ops, "forest_fit",
            lambda *a, **kw: real(*a, **dict(kw, chunk_trees=chunk_trees)))
        model = RandomForestClassifier(numTrees=6, maxDepth=7, maxBins=32, seed=5).fit(
            DeviceDataset(mesh, X, ROWS, y=y, weight=w))
        sizes.append(model.fit_report()["forest"]["chunk_trees"])
        return model

    one, other = fit(6), fit(chunk)
    assert sizes == [6, chunk or 6]  # sized by memory, all six fit beside toy rows
    for attr in forest_ops.TreeArrays._fields:
        np.testing.assert_array_equal(getattr(one, attr), getattr(other, attr), err_msg=attr)


def test_chunks_are_sized_from_shapes_and_memory_not_a_clock():
    room = forest_ops.rows_room(500_000, True, 1.0)
    assert 0.632 * 500_000 < room < 0.64 * 500_000  # the rows of positive weight
    assert forest_ops.rows_room(500_000, False, 1.0) == 500_000
    per_tree = forest_ops.tree_bytes(room, 3000, 13, 128, 2, 54, 500_000)
    assert 0.5e9 < per_tree < 3e9
    assert forest_ops.chunk_trees_for(25, per_tree, 2 * per_tree) == 1
    assert forest_ops.chunk_trees_for(25, per_tree, 12 * per_tree) == 5  # a divisor
    assert forest_ops.chunk_trees_for(25, per_tree, 10**15) == 25
    assert forest_ops.chunk_trees_for(7, per_tree, 0) == 1
    assert not hasattr(forest_ops, "_time") and "perf_counter" not in open(
        forest_ops.__file__).read()


def test_a_tree_whose_rows_pass_the_room_is_grown_again_with_room_for_all(monkeypatch):
    mesh, X, y, w = _rows(21)
    ds = DeviceDataset(mesh, X, ROWS, y=y, weight=w)

    def fit():
        return RandomForestClassifier(numTrees=2, maxDepth=5, maxBins=32, seed=5).fit(ds)

    roomy = fit()
    monkeypatch.setattr(forest_ops, "rows_room", lambda m, bootstrap, subsample: 64)
    cramped = fit()
    for attr in forest_ops.TreeArrays._fields:
        np.testing.assert_array_equal(getattr(roomy, attr), getattr(cramped, attr), err_msg=attr)


def test_default_grows_exactly_where_the_old_default_capped(rng):
    # a noisy monotone signal: splits fall near the medians and no node
    # turns pure, so the tree stays as wide as its levels allow
    X = rng.normal(size=(32_768, 6)).astype(np.float32)
    y = (X.sum(axis=1) + 3.0 * rng.normal(size=len(X)) > 0).astype(np.float64)

    def fit(cap):
        est = RandomForestClassifier(numTrees=1, maxDepth=10, maxBins=16, seed=2,
                                     bootstrap=False, featureSubsetStrategy="all",
                                     num_workers=1)
        assert est._tpu_params["max_active_nodes"] is None
        if cap:
            est._tpu_params["max_active_nodes"] = cap
        return est.fit((X, y))

    exact, capped = fit(None), fit(256)
    level9 = slice(2**9 - 1, 2**10 - 1)  # the heap's tenth level
    assert int((exact.feature[0, level9] >= 0).sum()) > 256
    assert exact.feature.shape[1] == 2**11 - 1  # the node table is the heap
    assert int((capped.feature[0] >= 0).sum()) < int((exact.feature[0] >= 0).sum())
    # every split of the capped tree's first eight levels is the exact tree's
    np.testing.assert_array_equal(exact.feature[0, :255], capped.feature[0, :255])


def test_the_two_sorts_of_a_layout_give_the_same_layout(rng):
    # slot and row id share one sort key where their bits fit; where they do
    # not (here: told of 2^30 rows) a stable sort by slot orders the same
    n, A, T, tiles = 1000, 8, 16, 80
    key = jnp.asarray(rng.integers(0, A + 1, n), jnp.int32)
    rowid = jnp.arange(n, dtype=jnp.int32)
    w = jnp.asarray(rng.integers(1, 4, n), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    counts = jnp.bincount(key, length=A + 1)[:A].astype(jnp.int32)
    packed_key = forest_ops._layout(key, rowid, w, y, counts, n, A, T, tiles)
    stable = forest_ops._layout(key, rowid, w, y, counts, 2**30, A, T, tiles)
    for a, b in zip(packed_key, stable):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    slot, rows = np.asarray(packed_key[0]), np.asarray(packed_key[1])
    for s in range(A):  # whole tiles a slot, its rows in order, pads last
        mine = rows[slot == s].ravel()
        assert (mine >= 0).sum() == int(counts[s]) and len(mine) % T == 0
        np.testing.assert_array_equal(mine[mine >= 0], np.nonzero(np.asarray(key) == s)[0])
