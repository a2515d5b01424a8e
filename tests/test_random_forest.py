#
# Random forest tests — the analog of reference tests/test_random_forest.py:
# accuracy/R2 parity vs sklearn forests on synthetic data, across mesh
# sizes, impurities, subset strategies; model structure and persistence.
#
import numpy as np
import pandas as pd
import pytest
from sklearn.datasets import make_classification, make_regression
from sklearn.ensemble import (
    RandomForestClassifier as SkRFC,
    RandomForestRegressor as SkRFR,
)
from sklearn.metrics import accuracy_score, r2_score

from spark_rapids_ml_tpu.classification import (
    RandomForestClassifier,
    RandomForestClassificationModel,
)
from spark_rapids_ml_tpu.regression import (
    RandomForestRegressor,
    RandomForestRegressionModel,
)


@pytest.fixture
def clf_data():
    X, y = make_classification(
        n_samples=600, n_features=8, n_informative=5, n_redundant=1,
        n_classes=3, random_state=11, class_sep=1.5,
    )
    return X.astype(np.float32), y.astype(np.float64)


@pytest.fixture
def reg_data():
    X, y = make_regression(
        n_samples=600, n_features=8, n_informative=6, noise=2.0,
        random_state=5,
    )
    return X.astype(np.float32), y.astype(np.float64)


def test_classifier_accuracy_vs_sklearn(clf_data, num_workers):
    X, y = clf_data
    rf = RandomForestClassifier(
        numTrees=16, maxDepth=8, seed=42, num_workers=num_workers
    )
    model = rf.fit((X, y))
    out = model._transform_array(X)
    acc = accuracy_score(y, out[model.getOrDefault("predictionCol")])
    sk = SkRFC(n_estimators=16, max_depth=8, random_state=42).fit(X, y)
    sk_acc = accuracy_score(y, sk.predict(X))
    # partition-local trees see 1/num_workers of the rows (reference
    # semantics, tree.py:330-341), so multi-worker train accuracy trails
    # full-data sklearn slightly
    assert acc > sk_acc - 0.1, f"tpu acc {acc} vs sklearn {sk_acc}"


def test_classifier_probability_outputs(clf_data):
    X, y = clf_data
    model = RandomForestClassifier(numTrees=8, maxDepth=6, seed=1).fit((X, y))
    df = pd.DataFrame({"features": list(X)})
    out = model.transform(df)
    probs = np.stack(out["probability"].to_numpy())
    assert probs.shape == (len(X), 3)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-4)
    raw = np.stack(out["rawPrediction"].to_numpy())
    assert np.array_equal(np.argmax(raw, axis=1), out["prediction"].to_numpy())
    assert model.numClasses == 3


def test_regressor_r2_vs_sklearn(reg_data, num_workers):
    X, y = reg_data
    rf = RandomForestRegressor(
        numTrees=16, maxDepth=8, seed=42, num_workers=num_workers
    )
    model = rf.fit((X, y))
    preds = model._transform_array(X)[model.getOrDefault("predictionCol")]
    r2 = r2_score(y, preds)
    # Spark featureSubsetStrategy=auto -> onethird for regression; align
    # the sklearn oracle, and allow for partition-local trees seeing
    # 1/num_workers of the rows (reference semantics, tree.py:330-341)
    sk = SkRFR(
        n_estimators=16, max_depth=8, max_features=1 / 3, random_state=42
    ).fit(X, y)
    sk_r2 = r2_score(y, sk.predict(X))
    assert r2 > sk_r2 - 0.15, f"tpu r2 {r2} vs sklearn {sk_r2}"


def test_entropy_impurity(clf_data):
    X, y = clf_data
    model = RandomForestClassifier(
        numTrees=8, maxDepth=6, impurity="entropy", seed=2
    ).fit((X, y))
    preds = model._transform_array(X)["prediction"]
    assert accuracy_score(y, preds) > 0.8


def test_feature_subset_strategies(clf_data):
    X, y = clf_data
    for strategy in ("all", "sqrt", "log2", "onethird", "2", "0.5"):
        model = RandomForestClassifier(
            numTrees=4, maxDepth=5, featureSubsetStrategy=strategy, seed=3
        ).fit((X, y))
        assert model.numTrees == 4


def test_model_structure_and_importances(clf_data):
    X, y = clf_data
    model = RandomForestClassifier(numTrees=6, maxDepth=5, seed=4).fit((X, y))
    assert model.numTrees == 6
    assert model.totalNumNodes > 6  # at least a split per tree
    assert len(model.treeWeights) == 6
    imp = model.featureImportances
    assert imp.shape == (8,)
    assert np.isclose(imp.sum(), 1.0)
    s = model.toDebugString()
    assert "Tree 0" in s and "If (feature" in s
    js = model.to_json()
    assert '"num_trees": 6' in js


def test_no_bootstrap_deterministic_labels(rng):
    # without bootstrap and full features, a deep tree fits exactly
    X = rng.normal(size=(200, 4)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    model = RandomForestClassifier(
        numTrees=2, maxDepth=6, bootstrap=False,
        featureSubsetStrategy="all", seed=0,
    ).fit((X, y))
    preds = model._transform_array(X)["prediction"]
    assert accuracy_score(y, preds) > 0.97


def test_min_instances_per_node(clf_data):
    X, y = clf_data
    big = RandomForestClassifier(
        numTrees=2, maxDepth=8, minInstancesPerNode=100, seed=0
    ).fit((X, y))
    small = RandomForestClassifier(
        numTrees=2, maxDepth=8, minInstancesPerNode=1, seed=0
    ).fit((X, y))
    assert big.totalNumNodes < small.totalNumNodes


def test_bad_labels_raise():
    X = np.zeros((10, 2), np.float32)
    y = np.array([0.0, 1.5] * 5)
    with pytest.raises(ValueError, match="non-negative integers"):
        RandomForestClassifier(numTrees=2).fit((X, y))


def test_save_load_classifier(tmp_path, clf_data):
    X, y = clf_data
    model = RandomForestClassifier(numTrees=4, maxDepth=5, seed=9).fit((X, y))
    path = str(tmp_path / "rf")
    model.save(path)
    loaded = RandomForestClassificationModel.load(path)
    a = model._transform_array(X)["prediction"]
    b = loaded._transform_array(X)["prediction"]
    assert np.array_equal(a, b)
    assert loaded.numClasses == model.numClasses


def test_save_load_regressor(tmp_path, reg_data):
    X, y = reg_data
    model = RandomForestRegressor(numTrees=4, maxDepth=5, seed=9).fit((X, y))
    path = str(tmp_path / "rfr")
    model.save(path)
    loaded = RandomForestRegressionModel.load(path)
    np.testing.assert_allclose(
        model._transform_array(X)["prediction"],
        loaded._transform_array(X)["prediction"],
    )


def test_cpu_predictor_matches(clf_data):
    X, y = clf_data
    model = RandomForestClassifier(numTrees=4, maxDepth=5, seed=6).fit((X, y))
    tpu_preds = model._transform_array(X)["prediction"]
    cpu_preds = model.cpu().predict(X)
    assert np.array_equal(tpu_preds, cpu_preds)


def test_sample_weights(rng):
    # two overlapping groups; weighting group B heavily flips predictions
    X = np.concatenate([np.zeros((50, 1)), np.zeros((50, 1))]).astype(np.float32)
    y = np.array([0.0] * 50 + [1.0] * 50)
    w = np.array([1.0] * 50 + [100.0] * 50)
    # shuffle so every shard sees both classes (trees are partition-local)
    perm = rng.permutation(len(y))
    X, y, w = X[perm], y[perm], w[perm]
    df = pd.DataFrame({"features": list(X), "label": y, "w": w})
    model = (
        RandomForestClassifier(numTrees=4, maxDepth=3, seed=0, bootstrap=False)
        .setFeaturesCol("features").setLabelCol("label").setWeightCol("w")
        .fit(df)
    )
    preds = model._transform_array(X)["prediction"]
    assert np.all(preds == 1)


def test_deep_tree_depth16_quality(clf_data):
    # cuML's default depth (16): the active-node frontier keeps program
    # size linear in depth; quality must track sklearn at the same depth
    X, y = clf_data
    model = RandomForestClassifier(
        numTrees=16, maxDepth=16, seed=42, num_workers=1
    ).fit((X, y))
    acc = accuracy_score(y, model._transform_array(X)["prediction"])
    sk = SkRFC(n_estimators=16, max_depth=16, random_state=42).fit(X, y)
    sk_acc = accuracy_score(y, sk.predict(X))
    assert acc > sk_acc - 0.1, f"tpu acc {acc} vs sklearn {sk_acc}"


def test_depth12_parity_vs_sklearn(clf_data):
    # sklearn-facade default depth with default frontier width
    X, y = clf_data
    model = RandomForestClassifier(
        numTrees=16, maxDepth=12, seed=7, num_workers=1
    ).fit((X, y))
    acc = accuracy_score(y, model._transform_array(X)["prediction"])
    sk = SkRFC(n_estimators=16, max_depth=12, random_state=7).fit(X, y)
    sk_acc = accuracy_score(y, sk.predict(X))
    assert acc > sk_acc - 0.1, f"tpu acc {acc} vs sklearn {sk_acc}"


def test_capped_frontier_still_learns(clf_data):
    # a tiny width budget (8 active nodes/level) degrades gracefully:
    # best-first growth keeps the largest nodes splitting
    X, y = clf_data
    est = RandomForestClassifier(numTrees=8, maxDepth=10, seed=5)
    est._tpu_params["max_active_nodes"] = 8
    model = est.fit((X, y))
    acc = accuracy_score(y, model._transform_array(X)["prediction"])
    assert acc > 0.8, f"capped-frontier acc {acc}"
    # structure stays consistent: every split node has in-table children
    lc = model.left_child[model.feature >= 0]
    assert lc.min() >= 1 and lc.max() + 1 < model.feature.shape[1]


def test_capped_matches_uncapped_when_wide_enough(clf_data):
    # max_active >= 2^level for every level => exact level-wise growth,
    # so widening the budget beyond the tree width changes nothing
    X, y = clf_data
    preds = []
    for width in (64, 4096):
        est = RandomForestClassifier(
            numTrees=4, maxDepth=6, seed=3, num_workers=1
        )
        est._tpu_params["max_active_nodes"] = width
        model = est.fit((X, y))
        preds.append(np.asarray(model._transform_array(X)["prediction"]))
    assert np.array_equal(preds[0], preds[1])


def test_single_sample_api(clf_data, reg_data):
    # the reference answers these via pyspark CPU fallback; here the
    # node-table forest answers directly
    Xc, yc = clf_data
    mc = RandomForestClassifier(numTrees=8, maxDepth=6, seed=1).fit((Xc, yc))
    batch = mc._transform_array(Xc[:5])
    for i in range(5):
        p = mc.predictProbability(Xc[i])
        np.testing.assert_allclose(
            p, np.asarray(batch["probability"])[i], rtol=1e-5, atol=1e-6
        )
        assert mc.predict(Xc[i]) == float(np.asarray(batch["prediction"])[i])
        np.testing.assert_allclose(
            mc.predictRaw(Xc[i]), p * mc.numTrees, rtol=1e-6
        )
    Xr, yr = reg_data
    mr = RandomForestRegressor(numTrees=8, maxDepth=6, seed=1).fit((Xr, yr))
    br = np.asarray(mr._transform_array(Xr[:5])["prediction"])
    for i in range(5):
        assert np.isclose(mr.predict(Xr[i]), br[i], rtol=1e-4, atol=1e-4)


def test_evaluate_on_dataset(clf_data):
    X, y = clf_data
    df = pd.DataFrame({"features": list(X), "label": y})
    m = RandomForestClassifier(numTrees=8, maxDepth=6, seed=2).fit(df)
    s = m.evaluate(df)
    assert s.accuracy > 0.85
    assert 0.0 < s.weightedFMeasure() <= 1.0
    assert "rawPrediction" in s.predictions.columns


def test_chunked_build_matches_single_dispatch(num_workers):
    """forest_fit dispatches tree chunks from the host on big builds;
    the forest must be
    IDENTICAL for any chunking — including device-major tree order, which
    the caller's [:n_trees] padding trim depends on."""
    import pandas as pd

    from spark_rapids_ml_tpu.classification import RandomForestClassifier
    from spark_rapids_ml_tpu.ops import forest as forest_ops

    rng = np.random.default_rng(3)
    X = rng.standard_normal((512, 8)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    df = pd.DataFrame({"features": list(X), "label": y})

    # 10 trees on num_workers devices: trees_per_worker pads unevenly
    def fit(chunk):
        orig = forest_ops.forest_fit

        def patched(*a, **kw):
            kw["chunk_trees"] = chunk
            return orig(*a, **kw)

        # models/tree.py re-imports forest_fit from ops.forest inside
        # _fit_array, so the module attribute is the effective target
        forest_ops.forest_fit = patched
        try:
            est = RandomForestClassifier(
                numTrees=10, maxDepth=5, seed=11, num_workers=num_workers
            )
            return est.fit(df)
        finally:
            forest_ops.forest_fit = orig

    m_single = fit(None)
    m_chunk2 = fit(2)
    from spark_rapids_ml_tpu.ops.forest import TreeArrays

    for attr in TreeArrays._fields:
        np.testing.assert_array_equal(
            getattr(m_single, attr), getattr(m_chunk2, attr),
            err_msg=f"{attr} differs between chunked and single dispatch",
        )
