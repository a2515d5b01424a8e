#
# What the reference's random_forest_regressor benchmark row (one worker's
# 500,000 x 3,000, depth 6, a third of the columns a node; chipbench's
# `rfr_fit_cached`) asks of the program, at sizes the CPU runs: the estimator
# against the benchmark's plain reference (every number of its audit at its
# limit), the one-part bfloat16 control over it, each audited fault caught by
# its own number, the label shift, a forest that is the same whatever the
# panels and the chunking, the classifier's trees as the parent grew them,
# and the worker's share.
#
import hashlib

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
from spark_rapids_ml_tpu.regression import RandomForestRegressor

ROWS, COLS = 4096, 48
CONFIG = mf.cell(mf.load_manifest(), "rfr_fit_cached")["config_file"]
PARAMS = dict(CONFIG["params"], numTrees=3)  # depth 6, 128 bins, 48 // 3 = 16 features a node
LIMITS = CONFIG["limits"]
LINEAR = {"model": "hidden_direction", "labels": "linear"}
FIELDS = forest_ops.TreeArrays._fields


@pytest.fixture(scope="module")
def adapter():
    return mf.adapter("rfr")


def _rows(seed, n_dev=1, rows=ROWS):
    mesh = get_mesh(n_dev)
    X, y, w = datagen.make_rows(mesh, rows, COLS, seed, LINEAR, 256)
    return mesh, X, y, w


def _fit(adapter, params, seed):
    mesh, X, y, w = _rows(seed)
    model = adapter.build(params, 1).fit(DeviceDataset(mesh, X, ROWS, y=y, weight=w))
    return model, X, y


def _same_forest(one, other):
    for attr in FIELDS:
        np.testing.assert_array_equal(getattr(one, attr), getattr(other, attr), err_msg=attr)


# -- the estimator against the plain reference ---------------------------------

@pytest.mark.parametrize("bootstrap", [True, False])
@pytest.mark.parametrize("seed", [2**31 + 7, 11])
def test_regressor_meets_every_limit_of_the_plain_reference(adapter, seed, bootstrap,
                                                            monkeypatch):
    monkeypatch.setattr(blocks, "BLOCK_ROWS", 256)
    params = dict(PARAMS, bootstrap=bootstrap)
    model, X, y = _fit(adapter, params, seed)
    got = adapter.compare(adapter.answer(model), adapter.reference(X, y, params))
    assert set(got) == set(LIMITS)
    assert all(got[k] <= LIMITS[k] for k in LIMITS), got
    fact = model.fit_report()["forest"]
    assert fact["depth_reached"] == 6 and fact["trees"] == 3 and fact["bins"] == 128
    assert fact["features_per_node"] == 16 and fact["criterion"] == "variance"
    assert fact["feature_panel"] == 16 and fact["panels_per_level"] == 1
    assert fact["internal_nodes"] == int((model.feature >= 0).sum()) > 3 * 40
    # the model's contract: a leaf holds (w, sum y, sum y^2) of the labels as given
    leaf = (model.feature < 0) & (model.leaf_stats[..., 0] > 0)
    mean = model.leaf_stats[..., 1][leaf] / model.leaf_stats[..., 0][leaf]
    assert np.asarray(y).min() <= mean.min() and mean.max() <= np.asarray(y).max()


@pytest.mark.parametrize("seed", [2**31 + 7, 11])
def test_one_part_bfloat16_control_is_over_the_limits(adapter, seed, monkeypatch):
    monkeypatch.setattr(blocks, "BLOCK_ROWS", 256)
    _, X, y, _ = _rows(seed)
    ref = adapter.reference(X, y, PARAMS)
    own = adapter.compare(adapter.grow(ref), ref)
    assert all(own[k] <= LIMITS[k] for k in LIMITS), own  # the reference passes its audit
    low = adapter.compare(adapter.reference(X, y, PARAMS, lowered=True), ref)
    assert low["leaf_weight_off"] > LIMITS["leaf_weight_off"], low
    assert low["leaf_stat_gap"] > LIMITS["leaf_stat_gap"], low
    assert low["split_regret"] > LIMITS["split_regret"], low


@pytest.mark.parametrize("fault,number", [
    ("threshold_1pct", "edges_off"), ("leaf_sum_1pct", "leaf_stat_gap"),
    ("leaf_weight_plus_one", "leaf_weight_off"), ("one_tree_short", "trees_off"),
    ("pointer_off_heap", "trees_off"), ("second_best_split", "split_regret"),
    ("leaf_for_split", "stopped_early"),
])
def test_audit_catches(adapter, fault, number, monkeypatch):
    monkeypatch.setattr(blocks, "BLOCK_ROWS", 256)
    _, X, y, _ = _rows(5)
    ref = adapter.reference(X, y, PARAMS)
    ans = {k: np.array(v, copy=True) if isinstance(v, np.ndarray) else v
           for k, v in adapter.grow(ref).items()}
    clean = adapter.compare(ans, ref)
    if fault == "threshold_1pct":
        ans["threshold"][0, 0] *= 1.01
    elif fault == "leaf_sum_1pct":
        leaf = int(np.nonzero(ans["leaf_stats"][0, :, 0] > 0)[0][0])
        ans["leaf_stats"][0, leaf, 1] *= 1.01
    elif fault == "leaf_weight_plus_one":
        leaf = int(np.nonzero(ans["leaf_stats"][2, :, 0] > 0)[0][-1])
        ans["leaf_stats"][2, leaf, 0] += 1.0
    elif fault == "one_tree_short":
        ans = {k: v[:-1] if isinstance(v, np.ndarray) else v for k, v in ans.items()}
    elif fault == "pointer_off_heap":
        ans["left_child"][1, 0] = 3
    elif fault == "second_best_split":
        # the root's split moved to the next edge of the same feature, the
        # leaves left as they were: only the regret can tell (and the leaves)
        f = ans["feature"][0, 0]
        at = int(np.nonzero(ref["edges"][:, f] == ans["threshold"][0, 0])[0][0])
        ans["threshold"][0, 0] = ref["edges"][(at + 40) % 127, f]
    elif fault == "leaf_for_split":
        node = int(np.nonzero(ans["feature"][0] >= 0)[0][-1])  # a deepest split
        ans["feature"][0, node], ans["left_child"][0, node] = -1, -1
    got = adapter.compare(ans, ref)
    assert clean[number] <= LIMITS[number] < got[number], (clean, got)


# -- the label shift: what keeps the ranking float64's where the mean is large ----

def _offset_fit(adapter, seed, offset, monkeypatch, shifted: bool):
    """Labels of mean `offset` and standard deviation 1."""
    monkeypatch.setattr(blocks, "BLOCK_ROWS", 256)
    mesh, X, y, w = _rows(seed)
    y = (y - y.mean()) / y.std() + offset
    if not shifted:
        monkeypatch.setattr(
            forest_ops, "label_shift",
            lambda y, valid, criterion, mesh: jnp.zeros((1,), jnp.float32))
    model = adapter.build(PARAMS, 1).fit(DeviceDataset(mesh, X, ROWS, y=y, weight=w))
    return adapter.compare(adapter.answer(model), adapter.reference(X, y, PARAMS))


@pytest.mark.parametrize("offset", [1e3, 1e4])
@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_labels_with_a_large_offset_keep_every_limit(adapter, seed, offset, monkeypatch):
    got = _offset_fit(adapter, seed, offset, monkeypatch, shifted=True)
    assert all(got[k] <= LIMITS[k] for k in LIMITS), got


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_the_shift_is_what_keeps_the_ranking_where_the_mean_is_large(adapter, seed, monkeypatch):
    """At a mean of 1,000 standard deviations the gain's own form (a
    child's sum against its share of the node's) still ranks as float64
    does; at 10,000 the float32 sums of w y themselves no longer hold the
    differences, and only labels less the shift do (2.7e-4 to 4.3e-3 of the
    node's variance lost without it, 2e-15 with it, on these seeds)."""
    bare = _offset_fit(adapter, seed, 1e4, monkeypatch, shifted=False)
    assert bare["split_regret"] > 100 * LIMITS["split_regret"], bare


def test_the_shift_is_the_workers_weighted_mean_and_leaves_hold_the_labels_as_given():
    mesh, X, y, w = _rows(4, n_dev=2)
    y = y + 300.0
    w = w.at[::3].set(0.0)
    shift = np.asarray(forest_ops.label_shift(y, w, forest_ops.VARIANCE, mesh))
    yh, wh = np.asarray(y, np.float64), np.asarray(w, np.float64)
    for dev, part in enumerate(np.split(np.arange(ROWS), 2)):
        assert shift[dev] == pytest.approx((wh[part] * yh[part]).sum() / wh[part].sum(), rel=1e-6)
    assert not np.asarray(forest_ops.label_shift(y, w, forest_ops.GINI, mesh)).any()
    model = RandomForestRegressor(numTrees=2, maxDepth=3, maxBins=32, seed=2, bootstrap=False,
                                  num_workers=2).fit(DeviceDataset(mesh, X, ROWS, y=y, weight=w))
    for t in range(2):  # tree t is device t's: the root's rows are its half of the rows
        part = np.split(np.arange(ROWS), 2)[t]
        leaf = model.feature[t] < 0
        np.testing.assert_allclose(
            model.leaf_stats[t][leaf].sum(axis=0),
            [wh[part].sum(), (wh * yh)[part].sum(), (wh * yh * yh)[part].sum()], rtol=2e-6)


def test_three_bfloat16_parts_add_up_to_float32_and_are_cut_by_reduce_precision(rng):
    """The statistics enter the one-hot products as three bfloat16 parts.
    A cast there and back is a pair XLA may elide on a TPU, leaving ONE
    part (2e-3 of a leaf's sum y on the chip); `reduce_precision` is an
    operation of its own."""
    x = (rng.normal(size=(3, 4096)) * np.exp(rng.uniform(-20, 20, size=(3, 4096)))).astype(np.float32)
    parts = np.asarray(forest_ops._three_parts(jnp.asarray(x), jnp.bfloat16).astype(jnp.float32))
    assert parts.shape == (9, 4096)
    total = parts[:3].astype(np.float64) + parts[3:6] + parts[6:]
    assert np.abs(total - x).max() <= 2.0 ** -22 * np.abs(x).max()
    assert (np.abs(total - x) <= 2.0 ** -22 * np.abs(x)).all()
    assert (parts[3:6] != 0).any() and (parts[6:] != 0).any()
    lowered = jax.jit(lambda a: forest_ops._three_parts(a, jnp.bfloat16)).lower(jnp.asarray(x))
    assert lowered.as_text().count("reduce_precision") == 3
    # float32 operands (the CPU's): the value itself and two parts of zeros
    same = np.asarray(forest_ops._three_parts(jnp.asarray(x), jnp.float32))
    np.testing.assert_array_equal(same[:3], x)
    assert not same[3:].any()


# -- panels, chunks, the classifier's path, the worker's share ---------------------

@pytest.mark.parametrize("width", [1, 5, 16])
def test_forest_is_bit_identical_for_any_panel_width(width, monkeypatch):
    mesh, X, y, w = _rows(21)
    ds = DeviceDataset(mesh, X, ROWS, y=y, weight=w)

    def fit():
        return RandomForestRegressor(numTrees=2, maxDepth=6, maxBins=128, seed=5).fit(ds)

    whole = fit()
    assert whole.fit_report()["forest"]["panels_per_level"] == 1
    monkeypatch.setattr(forest_ops, "feature_panel", lambda k, bins: min(k, width))
    panelled = fit()
    fact = panelled.fit_report()["forest"]
    assert (fact["feature_panel"], fact["panels_per_level"]) == (width, -(-16 // width))
    _same_forest(whole, panelled)


def test_panels_are_sized_from_shapes_and_tree_bytes_follows_them():
    assert forest_ops.feature_panel(54, 128) == 54  # the classifier's selection is one panel
    assert forest_ops.feature_panel(1000, 128) == 64 and forest_ops.feature_panel(1000, 256) == 32
    assert forest_ops.feature_panel(3, 2) == 3 and forest_ops.feature_panel(7, 16_384) == 1
    room = forest_ops.rows_room(500_000, True, 1.0)
    per_tree = forest_ops.tree_bytes(room, 3000, 6, 128, 3, 1000, 500_000)
    # 16 panels of 64: the selected ids and three histograms, not a 2.1 GB one-hot
    assert 0.5e9 < per_tree < 1.0e9
    assert forest_ops.chunk_trees_for(15, per_tree, 8_800_000_000) == 5
    # one panel (the parent's shapes) is what the parent counted
    assert forest_ops.tree_bytes(room, 3000, 13, 128, 2, 54, 500_000) == 1_299_890_148


@pytest.mark.parametrize("chunk", [1, 3, None])
def test_regression_forest_is_bit_identical_for_any_chunking(chunk, monkeypatch):
    mesh, X, y, w = _rows(21)
    real = forest_ops.forest_fit
    sizes = []

    def fit(chunk_trees):
        monkeypatch.setattr(
            forest_ops, "forest_fit",
            lambda *a, **kw: real(*a, **dict(kw, chunk_trees=chunk_trees)))
        model = RandomForestRegressor(numTrees=6, maxDepth=5, maxBins=128, seed=5).fit(
            DeviceDataset(mesh, X, ROWS, y=y, weight=w))
        sizes.append(model.fit_report()["forest"]["chunk_trees"])
        return model

    one, other = fit(6), fit(chunk)
    assert sizes == [6, chunk or 6]
    _same_forest(one, other)


def test_classification_trees_at_54_features_a_node_are_the_parents_bit_for_bit():
    """sha256 over the six node-table arrays of a 2-tree, depth-5 classifier on
    2,048 x 3,000 sign-labelled rows (floor(sqrt(3000)) = 54 features a node,
    one panel), as the commit before the panels grew it (c964e7e, jax 0.9.0,
    the CPU backend)."""
    mesh = get_mesh(1)
    X, y, w = datagen.make_rows(mesh, 2048, 3000, 2**31 + 7,
                                {"model": "hidden_direction", "labels": "sign"}, 256)
    model = RandomForestClassifier(numTrees=2, maxDepth=5, maxBins=128, seed=3,
                                   num_workers=1).fit(DeviceDataset(mesh, X, 2048, y=y, weight=w))
    fact = model.fit_report()["forest"]
    assert fact["features_per_node"] == 54 and fact["panels_per_level"] == 1
    assert fact["criterion"] == "gini"
    digest = hashlib.sha256()
    for attr in FIELDS:
        digest.update(np.ascontiguousarray(getattr(model, attr)).tobytes())
    assert digest.hexdigest()[:16] == "9ea471df7330d52f"


def test_a_workers_trees_are_those_of_a_one_device_fit_on_its_rows():
    """The cell is one of the deployment's two workers: device 0 of a
    two-device fit grows, on the first half of the rows, what one device
    grows given that half alone."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    rows = 2 * ROWS
    mesh2, X, y, w = _rows(8, n_dev=2, rows=rows)
    two = RandomForestRegressor(numTrees=4, maxDepth=6, maxBins=128, seed=1, num_workers=2).fit(
        DeviceDataset(mesh2, X, rows, y=y, weight=w))
    mesh1 = get_mesh(1)
    half = [jax.device_put(np.asarray(a)[:ROWS], mesh1.devices.flat[0]) for a in (X, y, w)]
    one = RandomForestRegressor(numTrees=2, maxDepth=6, maxBins=128, seed=1, num_workers=1).fit(
        DeviceDataset(mesh1, half[0], ROWS, y=half[1], weight=half[2]))
    for attr in FIELDS:  # device-major: the first two of the four trees are device 0's
        np.testing.assert_array_equal(getattr(two, attr)[:2], getattr(one, attr), err_msg=attr)
