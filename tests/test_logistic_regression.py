#
# LogisticRegression equivalence tests vs sklearn (SURVEY.md §4; analog of
# the ~30-test reference suite tests/test_logistic_regression.py:115-2409).
# Objective parity: Spark obj = (1/Σw)Σ w·logloss + regParam(α‖β‖₁ +
# (1-α)/2‖β‖²) -> sklearn C = 1/(n·regParam·(scale of matching penalty)).
#
import numpy as np
import pandas as pd
import pytest
from sklearn.datasets import make_classification
from sklearn.linear_model import LogisticRegression as SkLR

from spark_rapids_ml_tpu.classification import (
    LogisticRegression,
    LogisticRegressionModel,
)
from spark_rapids_ml_tpu.utils import array_equal_tol


def _binary_data(seed=0, n=600, d=8):
    X, y = make_classification(
        n_samples=n, n_features=d, n_informative=5, n_redundant=0,
        random_state=seed, class_sep=1.0,
    )
    return X.astype(np.float64), y.astype(np.float64)


def _multi_data(seed=0, n=900, d=10, k=4):
    X, y = make_classification(
        n_samples=n, n_features=d, n_informative=6, n_redundant=0,
        n_classes=k, n_clusters_per_class=1, random_state=seed,
    )
    return X.astype(np.float64), y.astype(np.float64)


def test_binary_l2_matches_sklearn(num_workers):
    X, y = _binary_data()
    reg = 0.1
    model = LogisticRegression(
        regParam=reg, standardization=False, maxIter=200, tol=1e-10,
        num_workers=num_workers, float32_inputs=False,
    ).fit((X, y))
    sk = SkLR(C=1.0 / (reg * len(y)), penalty="l2", tol=1e-10, max_iter=1000).fit(X, y)
    assert array_equal_tol(model.coefficients, sk.coef_[0], 1e-3)
    assert model.intercept == pytest.approx(sk.intercept_[0], abs=1e-3)


def test_binary_unregularized(num_workers):
    X, y = _binary_data(n=400)
    model = LogisticRegression(
        regParam=0.0, standardization=False, maxIter=300, tol=1e-12,
        num_workers=num_workers, float32_inputs=False,
    ).fit((X, y))
    sk = SkLR(penalty=None, tol=1e-12, max_iter=2000).fit(X, y)
    assert array_equal_tol(model.coefficients, sk.coef_[0], 5e-3)


def test_binary_elasticnet_owlqn(num_workers):
    X, y = _binary_data(n=800)
    reg, en = 0.05, 0.5
    model = LogisticRegression(
        regParam=reg, elasticNetParam=en, standardization=False,
        maxIter=500, tol=1e-10, num_workers=num_workers, float32_inputs=False,
    ).fit((X, y))
    # sklearn saga: obj = (1/n)Σlogloss·n ... C scaling: C=1/(n·reg)
    sk = SkLR(
        C=1.0 / (reg * len(y)), penalty="elasticnet", l1_ratio=en,
        solver="saga", tol=1e-10, max_iter=20000,
    ).fit(X, y)
    assert array_equal_tol(model.coefficients, sk.coef_[0], 5e-3)
    assert model.intercept == pytest.approx(sk.intercept_[0], abs=5e-3)


def test_binary_l1_sparsity(num_workers):
    X, y = _binary_data(n=800)
    reg = 0.1
    model = LogisticRegression(
        regParam=reg, elasticNetParam=1.0, standardization=False,
        maxIter=500, tol=1e-10, num_workers=num_workers, float32_inputs=False,
    ).fit((X, y))
    sk = SkLR(
        C=1.0 / (reg * len(y)), penalty="l1", solver="saga",
        tol=1e-10, max_iter=20000,
    ).fit(X, y)
    np.testing.assert_array_equal(
        np.abs(model.coefficients) < 1e-9, np.abs(sk.coef_[0]) < 1e-9
    )


def test_multinomial_matches_sklearn(num_workers):
    X, y = _multi_data()
    reg = 0.05
    model = LogisticRegression(
        regParam=reg, standardization=False, maxIter=300, tol=1e-10,
        num_workers=num_workers, float32_inputs=False,
    ).fit((X, y))
    sk = SkLR(C=1.0 / (reg * len(y)), tol=1e-10, max_iter=2000).fit(X, y)
    assert model.numClasses == 4
    # sklearn centers coef rows for multinomial; ours is uncentered softmax
    # with centered intercepts -> compare centered coefficient matrices
    ours = model.coefficientMatrix - model.coefficientMatrix.mean(axis=0)
    theirs = sk.coef_ - sk.coef_.mean(axis=0)
    assert array_equal_tol(ours, theirs, 5e-3)
    assert model.interceptVector.sum() == pytest.approx(0.0, abs=1e-6)


def test_standardization_equivalence():
    # standardization=True == manual standardization + coefficient unscaling
    X, y = _binary_data(n=500)
    reg = 0.1
    model = LogisticRegression(
        regParam=reg, standardization=True, maxIter=300, tol=1e-12,
        float32_inputs=False,
    ).fit((X, y))
    mean, std = X.mean(axis=0), X.std(axis=0, ddof=1)
    Xs = (X - mean) / std
    sk = SkLR(C=1.0 / (reg * len(y)), tol=1e-12, max_iter=2000).fit(Xs, y)
    assert array_equal_tol(model.coefficients, sk.coef_[0] / std, 1e-3)


def test_transform_outputs(num_workers):
    X, y = _binary_data(n=200)
    df = pd.DataFrame({"features": list(X), "label": y})
    model = (
        LogisticRegression(regParam=0.01, num_workers=num_workers)
        .setFeaturesCol("features")
        .fit(df)
    )
    out = model.transform(df)
    assert {"prediction", "probability", "rawPrediction"} <= set(out.columns)
    probs = np.stack(out["probability"].to_numpy())
    assert probs.shape == (200, 2)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-5)
    acc = (out["prediction"].to_numpy() == y).mean()
    assert acc > 0.85


def test_threshold(num_workers):
    X, y = _binary_data(n=200)
    model = LogisticRegression(regParam=0.01, num_workers=num_workers).fit((X, y))
    model_hi = model.copy({model.threshold: 0.99})
    out = model_hi.transform(X)
    probs = out["probability"]
    preds = out["prediction"]
    assert (preds == (probs[:, 1] > 0.99).astype(int)).all()


def test_single_label_degenerate(num_workers):
    X = np.random.default_rng(0).normal(size=(50, 4))
    y = np.ones(50)
    model = LogisticRegression(num_workers=num_workers).fit((X, y))
    assert model.intercept == np.inf
    assert (model.coefficients == 0).all()
    out = model.transform(X)
    assert (out["prediction"] == 1).all()

    with pytest.raises(RuntimeError, match="either 1. or 0."):
        LogisticRegression(num_workers=num_workers).fit((X, np.full(50, 3.0)))


def test_non_integer_labels_rejected(num_workers):
    X = np.random.default_rng(0).normal(size=(50, 4))
    with pytest.raises(RuntimeError, match="Integers"):
        LogisticRegression(num_workers=num_workers).fit((X, np.full(50, 0.5)))


def test_weighted_fit(num_workers):
    X, y = _binary_data(n=300)
    rng = np.random.default_rng(3)
    wt = rng.uniform(0.2, 2.0, len(y))
    df = pd.DataFrame({"features": list(X), "label": y, "wt": wt})
    model = (
        LogisticRegression(
            regParam=0.1, standardization=False, maxIter=300, tol=1e-10,
            num_workers=num_workers, float32_inputs=False,
        )
        .setFeaturesCol("features")
        .setWeightCol("wt")
        .fit(df)
    )
    sk = SkLR(C=1.0 / (reg_eff := 0.1 * wt.sum()), penalty="l2", tol=1e-10,
              max_iter=2000).fit(X, y, sample_weight=wt)
    assert array_equal_tol(model.coefficients, sk.coef_[0], 5e-3)


def test_save_load(tmp_path):
    X, y = _multi_data(n=300)
    model = LogisticRegression(regParam=0.01).fit((X, y))
    path = str(tmp_path / "lrm")
    model.write().save(path)
    loaded = LogisticRegressionModel.load(path)
    np.testing.assert_allclose(loaded.coefficientMatrix, model.coefficientMatrix)
    np.testing.assert_allclose(loaded.interceptVector, model.interceptVector)
    assert loaded.numClasses == model.numClasses
    out1 = model.transform(X)["prediction"]
    out2 = loaded.transform(X)["prediction"]
    np.testing.assert_array_equal(out1, out2)


def test_unsupported_params():
    with pytest.raises(ValueError, match="not supported"):
        LogisticRegression(thresholds=[0.3, 0.7])
    with pytest.raises(ValueError, match="not supported"):
        LogisticRegression(regParam=-1.0)


def test_bf16_features_close_to_f32(rng):
    """bf16 feature storage (config bf16_features): coefficients must stay
    close to the f32 fit — the bandwidth lever may cost ~3 digits of
    feature precision but not solution quality."""
    from spark_rapids_ml_tpu.config import reset_config, set_config

    X = rng.normal(size=(3000, 16)).astype(np.float32)
    beta = rng.normal(size=16)
    y = (X @ beta > 0).astype(np.float64)
    m32 = LogisticRegression(regParam=0.01, maxIter=200, tol=1e-9).fit((X, y))
    try:
        set_config(bf16_features=True)
        m16 = LogisticRegression(regParam=0.01, maxIter=200, tol=1e-9).fit((X, y))
    finally:
        reset_config()
    # relative coefficient agreement ~1% (bf16 has ~3 significant digits)
    denom = np.maximum(np.abs(m32.coef_), 0.1)
    rel = np.abs(m16.coef_ - m32.coef_) / denom
    assert rel.max() < 0.05, rel.max()
    p32 = m32._transform_array(X)["prediction"]
    p16 = m16._transform_array(X)["prediction"]
    assert (np.asarray(p32) == np.asarray(p16)).mean() > 0.995


def test_objective_history_summary(rng):
    """Spark LogisticRegressionTrainingSummary parity: objectiveHistory is
    monotone non-increasing and ends at the reported objective."""
    X = rng.normal(size=(1000, 6))
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
    m = LogisticRegression(regParam=0.01, maxIter=50).fit((X, y))
    assert m.hasSummary
    h = m.summary.objectiveHistory
    assert len(h) == m.num_iters + 1
    assert m.summary.totalIterations == m.num_iters
    diffs = np.diff(h)
    assert (diffs <= 1e-7).all(), h  # monotone decrease (OWL-QN allows ~eps)
    assert abs(h[-1] - m.objective) < 1e-5 * max(1.0, abs(m.objective))
    # persists through save/load
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        m.save(td + "/m")
        lm = LogisticRegressionModel.load(td + "/m")
        assert lm.summary.objectiveHistory == h


def test_objective_history_l1_consistency(rng):
    """Under OWL-QN the reported objective and the history tail use the
    SAME (penalty-inclusive) definition."""
    X = rng.normal(size=(800, 6))
    y = (X[:, 0] > 0).astype(np.float64)
    m = LogisticRegression(regParam=0.05, elasticNetParam=1.0, maxIter=60).fit(
        (X, y)
    )
    h = m.summary.objectiveHistory
    assert len(h) == m.summary.totalIterations + 1
    assert abs(h[-1] - m.objective) < 1e-12


def test_single_sample_api_and_evaluate(rng):
    """pyspark Model surface: predict/predictRaw/predictProbability on one
    vector + evaluate(dataset) — computed natively (the reference falls
    back to the pyspark CPU model, classification.py:1593-1615)."""
    import pandas as pd

    X = rng.normal(size=(400, 4)).astype(np.float32)
    y = (X[:, 0] - X[:, 1] > 0).astype(np.float64)
    df = pd.DataFrame({"features": list(X), "label": y})
    m = LogisticRegression(regParam=0.01).fit(df)

    v = X[0]
    raw = m.predictRaw(v)
    probs = m.predictProbability(v)
    assert raw.shape == (2,) and np.isclose(raw[0], -raw[1])
    assert np.isclose(probs.sum(), 1.0)
    # consistent with the batch transform
    out = m._transform_array(X[:1])
    np.testing.assert_allclose(
        probs, np.asarray(out["probability"])[0], rtol=1e-5, atol=1e-6
    )
    assert m.predict(v) == float(np.asarray(out["prediction"])[0])

    s = m.evaluate(df)
    assert s.accuracy > 0.9
    assert 0.0 < s.weightedPrecision <= 1.0
    assert 0.0 < s.weightedFMeasure() <= 1.0
    assert 0.0 < s.weightedFMeasure(beta=0.5) <= 1.0
    assert len(s.predictions) == 400

    # multinomial path
    W = rng.normal(size=(3, 4))
    y3 = np.argmax(X @ W.T, axis=1).astype(np.float64)
    m3 = LogisticRegression(regParam=0.01).fit(
        pd.DataFrame({"features": list(X), "label": y3})
    )
    p3 = m3.predictProbability(v)
    assert p3.shape == (3,) and np.isclose(p3.sum(), 1.0)
    assert m3.predict(v) == float(np.argmax(p3))


def test_evaluate_with_features_cols_and_weights(rng):
    """evaluate() rides the standard transform: multi-column features and
    sample weights are honored, and the predictions frame keeps the raw
    prediction column."""
    import pandas as pd

    X = rng.normal(size=(300, 3)).astype(np.float64)
    y = (X[:, 0] > 0).astype(np.float64)
    w = np.where(y > 0, 2.0, 1.0)
    df = pd.DataFrame(
        {"a": X[:, 0], "b": X[:, 1], "c": X[:, 2], "label": y, "w": w}
    )
    m = (
        LogisticRegression(regParam=0.01)
        .setFeaturesCol(["a", "b", "c"])
        .setWeightCol("w")
        .fit(df)
    )
    s = m.evaluate(df)
    assert s.accuracy > 0.9
    assert "rawPrediction" in s.predictions.columns
    assert set("abc") <= set(s.predictions.columns)


def test_host_dispatched_lbfgs_matches_fused(rng):
    # forcing a tiny per-program budget routes the dense fit through the
    # host-driven L-BFGS (one dispatched evaluation per program, the
    # `dispatch_flops_limit` path); the optimum must match the fused
    # while_loop
    from spark_rapids_ml_tpu.config import reset_config, set_config

    n, d = 4000, 12
    X = rng.normal(size=(n, d)).astype(np.float32)
    tw = rng.normal(size=d).astype(np.float32)
    y = (X @ tw > 0).astype(np.float64)
    y_mc = np.digitize(X @ tw, np.quantile(X @ tw, [0.33, 0.66])).astype(
        np.float64
    )
    for labels, fam in ((y, "binomial"), (y_mc, "multinomial")):
        kw = dict(regParam=0.01, maxIter=120, tol=1e-9)
        m_fused = LogisticRegression(**kw).fit((X, labels))
        set_config(dispatch_flops_limit=1e6)
        try:
            m_host = LogisticRegression(**kw).fit((X, labels))
        finally:
            reset_config()
        np.testing.assert_allclose(
            np.asarray(m_host.coefficientMatrix),
            np.asarray(m_fused.coefficientMatrix), rtol=2e-3, atol=2e-4,
        )
        np.testing.assert_allclose(
            np.asarray(m_host.interceptVector),
            np.asarray(m_fused.interceptVector), rtol=2e-3, atol=2e-4,
        )
        assert abs(
            m_host.summary.objectiveHistory[-1]
            - m_fused.summary.objectiveHistory[-1]
        ) < 1e-5


def test_host_dispatched_lbfgs_no_constant_capture(rng):
    # the host-driven evaluation must take the dataset as a jit ARGUMENT:
    # jitting a closure over the concrete arrays captures them as lowered
    # constants (at the refconfig 1M x 3000 scale that was a 12 GB
    # host-side materialization during lowering — jax's "large amount of
    # constants were captured" warning, observed live on chip).
    #
    # Measured DIRECTLY via the shared jit-audit harness (this test's
    # original inline proxy grew into analysis/jit_audit.py): every
    # call-time jit on the host-dispatch path is re-traced with
    # make_jaxpr and its captured-const bytes bounded at 16 KB — at
    # test scale the dataset alone is 128 KB, so a closure-capture
    # regression trips the bound loudly.
    from spark_rapids_ml_tpu.analysis.jit_audit import (
        assert_clean,
        audit_jits,
    )
    from spark_rapids_ml_tpu.config import reset_config, set_config

    n, d = 2000, 16
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)

    # host_lbfgs_fit builds its jitted oracle at CALL time, so the audit
    # sees exactly the programs the host-dispatch path creates
    # (module-level @jax.jit functions were bound at import and are
    # data-as-argument by construction)
    set_config(dispatch_flops_limit=1e6)
    try:
        with audit_jits(
            modules=("spark_rapids_ml_tpu.ops.logistic",)
        ) as report:
            m = LogisticRegression(maxIter=40).fit((X, y))
        assert m.summary.totalIterations > 0
    finally:
        reset_config()
    # expect_records guards against the vacuous pass (the proxy must
    # have seen the jitted evaluation); assert_clean enforces the
    # report's 16 KB captured-const bound
    assert_clean(report, expect_records=True)


def test_host_dispatched_lbfgs_elasticnet(rng):
    # OWL-QN (l1>0) through the host path: same sparsity pattern and
    # objective as the fused solver
    from spark_rapids_ml_tpu.config import reset_config, set_config

    n, d = 3000, 10
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] - X[:, 3] > 0).astype(np.float64)
    kw = dict(regParam=0.05, elasticNetParam=0.7, maxIter=200,
              standardization=False)
    m_fused = LogisticRegression(**kw).fit((X, y))
    set_config(dispatch_flops_limit=1e6)
    try:
        m_host = LogisticRegression(**kw).fit((X, y))
    finally:
        reset_config()
    cf = np.asarray(m_fused.coefficientMatrix).ravel()
    ch = np.asarray(m_host.coefficientMatrix).ravel()
    np.testing.assert_array_equal(np.abs(cf) < 1e-8, np.abs(ch) < 1e-8)
    np.testing.assert_allclose(ch, cf, rtol=5e-3, atol=5e-4)
