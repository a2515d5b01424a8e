#
# LinearRegression equivalence tests vs sklearn (SURVEY.md §4; analog of
# reference tests/test_linear_regression.py).  Objective parity notes:
# Spark obj = 1/(2n)Σ(residual²) + regParam(α‖β‖₁ + (1-α)/2‖β‖²), so
# sklearn Ridge(alpha = n·regParam) and ElasticNet(alpha=regParam,
# l1_ratio=elasticNetParam) are the matching CPU references.
#
import numpy as np
import pandas as pd
import pytest
from sklearn.linear_model import ElasticNet, LinearRegression as SkLR, Ridge

from spark_rapids_ml_tpu.regression import LinearRegression, LinearRegressionModel
from spark_rapids_ml_tpu.utils import array_equal_tol


def _make_data(seed=0, n=400, d=6, noise=0.1):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)) * rng.uniform(0.5, 3.0, d) + rng.normal(size=d)
    true_coef = rng.normal(size=d)
    y = X @ true_coef + 1.7 + noise * rng.normal(size=n)
    return X, y


def test_ols_matches_sklearn(num_workers):
    X, y = _make_data()
    df = pd.DataFrame({"features": list(X), "label": y})
    model = (
        LinearRegression(regParam=0.0, num_workers=num_workers, float32_inputs=False)
        .setFeaturesCol("features")
        .fit(df)
    )
    sk = SkLR().fit(X, y)
    assert array_equal_tol(model.coefficients, sk.coef_, 1e-6)
    assert model.intercept == pytest.approx(sk.intercept_, abs=1e-6)


def test_ols_no_intercept(num_workers):
    X, y = _make_data()
    model = LinearRegression(
        regParam=0.0, fitIntercept=False, num_workers=num_workers, float32_inputs=False
    ).fit((X, y))
    sk = SkLR(fit_intercept=False).fit(X, y)
    assert array_equal_tol(model.coefficients, sk.coef_, 1e-6)
    assert model.intercept == 0.0


def test_ridge_matches_sklearn(num_workers):
    X, y = _make_data()
    reg = 0.5
    model = LinearRegression(
        regParam=reg, elasticNetParam=0.0, standardization=False,
        num_workers=num_workers, float32_inputs=False,
    ).fit((X, y))
    sk = Ridge(alpha=reg * X.shape[0]).fit(X, y)
    assert array_equal_tol(model.coefficients, sk.coef_, 1e-5)
    assert model.intercept == pytest.approx(sk.intercept_, abs=1e-5)


def test_elasticnet_matches_sklearn(num_workers):
    X, y = _make_data(n=500)
    reg, l1r = 0.1, 0.5
    model = LinearRegression(
        regParam=reg, elasticNetParam=l1r, standardization=False,
        maxIter=2000, tol=1e-10, num_workers=num_workers, float32_inputs=False,
    ).fit((X, y))
    sk = ElasticNet(alpha=reg, l1_ratio=l1r, max_iter=10000, tol=1e-10).fit(X, y)
    assert array_equal_tol(model.coefficients, sk.coef_, 1e-4)
    assert model.intercept == pytest.approx(sk.intercept_, abs=1e-4)


def test_lasso_sparsity(num_workers):
    X, y = _make_data(n=500)
    model = LinearRegression(
        regParam=1.0, elasticNetParam=1.0, standardization=False,
        maxIter=3000, tol=1e-10, num_workers=num_workers, float32_inputs=False,
    ).fit((X, y))
    sk = ElasticNet(alpha=1.0, l1_ratio=1.0, max_iter=10000, tol=1e-10).fit(X, y)
    np.testing.assert_array_equal(model.coefficients == 0.0, sk.coef_ == 0.0)
    assert array_equal_tol(model.coefficients, sk.coef_, 1e-4)


def test_standardization_ols_invariant(num_workers):
    # standardization shouldn't change the OLS optimum
    X, y = _make_data()
    m1 = LinearRegression(regParam=0.0, standardization=True,
                          num_workers=num_workers, float32_inputs=False).fit((X, y))
    m2 = LinearRegression(regParam=0.0, standardization=False,
                          num_workers=num_workers, float32_inputs=False).fit((X, y))
    assert array_equal_tol(m1.coefficients, m2.coefficients, 1e-6)


def test_ridge_standardization_penalizes_scaled_space():
    # With standardization=True the penalty applies to standardized coefs:
    # equivalent to sklearn Ridge on scaled features with unscaled-back coefs.
    X, y = _make_data()
    reg = 0.7
    model = LinearRegression(
        regParam=reg, standardization=True, float32_inputs=False
    ).fit((X, y))
    std = X.std(axis=0, ddof=1)
    Xs = (X - X.mean(axis=0)) / std
    sk = Ridge(alpha=reg * X.shape[0]).fit(Xs, y)
    assert array_equal_tol(model.coefficients, sk.coef_ / std, 1e-5)


def test_weighted_ols(num_workers):
    X, y = _make_data(n=300)
    rng = np.random.default_rng(1)
    w = rng.uniform(0.1, 3.0, X.shape[0])
    df = pd.DataFrame({"features": list(X), "label": y, "wt": w})
    model = (
        LinearRegression(regParam=0.0, num_workers=num_workers, float32_inputs=False)
        .setFeaturesCol("features")
        .setWeightCol("wt")
        .fit(df)
    )
    sk = SkLR().fit(X, y, sample_weight=w)
    assert array_equal_tol(model.coefficients, sk.coef_, 1e-6)


def test_transform_and_save_load(tmp_path, num_workers):
    X, y = _make_data(n=100)
    model = LinearRegression(num_workers=num_workers).fit((X, y))
    preds = model.transform(X)
    assert preds.shape == (100,)
    path = str(tmp_path / "lr")
    model.write().save(path)
    loaded = LinearRegressionModel.load(path)
    np.testing.assert_allclose(loaded.coef_, model.coef_)
    assert loaded.intercept == pytest.approx(model.intercept)


def test_unsupported_values():
    with pytest.raises(ValueError, match="not supported"):
        LinearRegression(loss="huber")
    with pytest.raises(ValueError, match="not supported"):
        LinearRegression(solver="l-bfgs")


def test_training_summary_matches_sklearn_metrics(rng):
    """LinearRegressionTrainingSummary: rmse/r2 computed exactly from the
    fit's sufficient statistics must match recomputed residual metrics."""
    from sklearn.metrics import mean_squared_error, r2_score

    X = rng.normal(size=(600, 5))
    y = X @ np.array([1.0, -2.0, 0.5, 3.0, 0.0]) + 1.5 + 0.3 * rng.normal(size=600)
    m = LinearRegression(regParam=0.0, float32_inputs=False).fit((X, y))
    pred = np.asarray(m._transform_array(X)["prediction"], np.float64)
    assert m.hasSummary
    s = m.summary
    np.testing.assert_allclose(s.meanSquaredError,
                               mean_squared_error(y, pred), rtol=1e-6)
    np.testing.assert_allclose(s.rootMeanSquaredError,
                               np.sqrt(mean_squared_error(y, pred)), rtol=1e-6)
    np.testing.assert_allclose(s.r2, r2_score(y, pred), rtol=1e-6)


def test_training_summary_streaming_path(tmp_path, rng):
    import pandas as pd

    from spark_rapids_ml_tpu.config import reset_config, set_config

    X = rng.normal(size=(400, 4)).astype(np.float32)
    y = (X @ np.array([2.0, -1.0, 0.5, 1.0])).astype(np.float64)
    path = str(tmp_path / "d.parquet")
    pd.DataFrame({"features": list(X), "label": y}).to_parquet(path)
    try:
        set_config(force_streaming_stats=True)
        m = LinearRegression().fit(path)
    finally:
        reset_config()
    assert m.hasSummary and m.summary.r2 > 0.99


def test_training_summary_precision_on_near_exact_fit(rng):
    """The residual-pass SSE must not suffer one-pass cancellation: on a
    noiseless f32 fit the reported rmse tracks the true tiny residual."""
    X = rng.normal(size=(400, 4)).astype(np.float32) * 10.0
    y = (X @ np.array([1.0, 2.0, -1.0, 0.5]) + 3.0).astype(np.float64)
    m = LinearRegression(regParam=0.0).fit((X, y))
    pred = np.asarray(m._transform_array(X)["prediction"], np.float64)
    true_rmse = float(np.sqrt(((y - pred) ** 2).mean()))
    # within 10x of the recomputed value (both ~f32-noise scale), never
    # the ~1000x overstatement the one-pass expansion produced
    assert m.summary.rootMeanSquaredError <= max(10 * true_rmse, 1e-4)


def test_training_summary_no_intercept_through_origin(rng):
    """Spark parity: fitIntercept=False uses through-origin SStot."""
    X = rng.normal(size=(500, 3))
    y = X @ np.array([2.0, -1.0, 0.5]) + 0.1 * rng.normal(size=500)
    m = LinearRegression(
        regParam=0.0, fitIntercept=False, float32_inputs=False
    ).fit((X, y))
    pred = np.asarray(m._transform_array(X)["prediction"], np.float64)
    sse = float(((y - pred) ** 2).sum())
    r2_origin = 1.0 - sse / float((y * y).sum())
    np.testing.assert_allclose(m.summary.r2, r2_origin, rtol=1e-6)


def test_single_sample_predict(rng):
    X = rng.normal(size=(200, 4)).astype(np.float32)
    y = (X @ np.array([1.0, -2.0, 0.5, 3.0]) + 0.7).astype(np.float64)
    m = LinearRegression().fit(pd.DataFrame({"features": list(X), "label": y}))
    batch = np.asarray(m._transform_array(X[:5])["prediction"], np.float64)
    for i in range(5):
        assert np.isclose(m.predict(X[i]), batch[i], rtol=1e-4, atol=1e-4)


def test_evaluate_on_dataset(rng):
    """evaluate(dataset) computes metrics natively (the reference falls
    back to the pyspark CPU model, regression.py:770)."""
    X = rng.normal(size=(300, 3)).astype(np.float32)
    y = (X @ np.array([2.0, -1.0, 0.5]) + 1.0
         + 0.1 * rng.normal(size=300)).astype(np.float64)
    df = pd.DataFrame({"features": list(X), "label": y})
    m = LinearRegression().fit(df)
    s = m.evaluate(df)
    # matches the training summary computed from sufficient statistics
    assert abs(s.rootMeanSquaredError - m.summary.rootMeanSquaredError) < 1e-3
    assert abs(s.r2 - m.summary.r2) < 1e-3
    assert 0.0 <= s.meanAbsoluteError < 0.2
    assert s.explainedVariance > 0
    assert "prediction" in s.predictions.columns


def test_evaluate_r2_through_origin(rng):
    """fitIntercept=False evaluates r2 through the origin (Spark's
    throughOrigin=!fitIntercept), matching the training summary even with
    a large label offset."""
    X = rng.normal(size=(300, 2)).astype(np.float32)
    y = (X @ np.array([1.5, -0.5]) + 10.0).astype(np.float64)  # big offset
    df = pd.DataFrame({"features": list(X), "label": y})
    m = LinearRegression(fitIntercept=False).fit(df)
    s = m.evaluate(df)
    assert abs(s.r2 - m.summary.r2) < 1e-3, (s.r2, m.summary.r2)


# ---- solve_linear_host's closed-form ridge branch, from statistics alone ----

_REG = 0.3


def _ridge_stats(dtype, weighted, n=500, d=24, seed=3):
    """Sufficient statistics of a weighted regression problem, summed in
    float64 and handed over in `dtype` as a caller would hold them."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)) * rng.uniform(0.5, 3.0, d) + rng.normal(size=d)
    y = X @ rng.normal(size=d) + 1.7 + rng.normal(size=n)
    w = rng.uniform(0.5, 2.0, n) if weighted else np.ones(n)
    Xw = X * w[:, None]
    gram = Xw.T @ X
    # symmetric to the bit: the factorisation reads one triangle, the LU of
    # the reference both, and the two are held to 1e-10 here
    gram = ((gram + gram.T) / 2).astype(dtype)
    sxy, s1 = (Xw.T @ y).astype(dtype), Xw.sum(axis=0).astype(dtype)
    return gram, sxy, s1, float(w.sum()), float(w @ y), float(w @ (y * y))


def _textbook_ridge(gram, sxy, s1, sw, sy, syy, fit_intercept, standardization):
    """The plain float64 solve: centre, scale, add the penalty, pivoted LU;
    then the summary from the same expansion of the weighted SSE."""
    gram, sxy, s1 = (np.asarray(a, np.float64) for a in (gram, sxy, s1))
    d = gram.shape[0]
    mean, ymean = s1 / sw, sy / sw
    A, b = gram, sxy
    if fit_intercept:
        A, b = gram - sw * np.outer(mean, mean), sxy - sw * mean * ymean
    scale = np.ones(d)
    if standardization:
        var = (np.diag(gram) / sw - mean**2) * (sw / (sw - 1.0))
        scale = np.sqrt(var)
    A, b = A / np.outer(scale, scale), b / scale
    coef = np.linalg.solve(A + sw * _REG * np.eye(d), b) / scale
    intercept = float(ymean - mean @ coef) if fit_intercept else 0.0
    sse = (syy - 2.0 * (coef @ sxy + intercept * sy) + coef @ gram @ coef
           + 2.0 * intercept * (s1 @ coef) + intercept * intercept * sw)
    sst = syy - sy * sy / sw if fit_intercept else syy
    return coef, intercept, {"mse": sse / sw, "rmse": np.sqrt(sse / sw),
                             "r2": 1.0 - sse / sst}


def _solve_and_events(stats, fit_intercept, standardization):
    from spark_rapids_ml_tpu import tracing
    from spark_rapids_ml_tpu.ops.linear import solve_linear_host

    tracing.reset_trace()
    out = solve_linear_host(
        *stats, reg_param=_REG, elasticnet_param=0.0,
        fit_intercept=fit_intercept, standardization=standardization,
        tol=1e-30, max_iter=10,
    )
    events = [e.name for e in tracing.get_trace_events()
              if e.name.startswith("linreg_solver[")]
    return out, events


@pytest.mark.parametrize("weighted", [False, True], ids=["unit_w", "weighted"])
@pytest.mark.parametrize("dtype", [np.float32, np.float64], ids=["f32", "f64"])
@pytest.mark.parametrize("standardization", [False, True], ids=["raw", "std"])
@pytest.mark.parametrize("fit_intercept", [False, True], ids=["origin", "intercept"])
def test_closed_form_ridge_solve_matches_textbook(
    fit_intercept, standardization, dtype, weighted
):
    stats = _ridge_stats(dtype, weighted)
    for a in stats[:3]:
        a.setflags(write=False)  # as a fetched device array is
    before = [a.tobytes() for a in stats[:3]]
    (coef, intercept, diag), events = _solve_and_events(
        stats, fit_intercept, standardization)
    ref_coef, ref_intercept, ref_diag = _textbook_ridge(
        *stats, fit_intercept, standardization)

    assert coef.dtype == np.float64
    np.testing.assert_allclose(coef, ref_coef, rtol=0, atol=1e-10 * np.abs(ref_coef).max())
    assert intercept == pytest.approx(ref_intercept, rel=1e-10, abs=1e-10)
    for k in ("mse", "rmse", "r2"):
        assert diag[k] == pytest.approx(ref_diag[k], rel=1e-12), k
    assert diag["n_iter"] == 0.0
    # the caller's statistics are never the buffer the system is built in
    assert [a.tobytes() for a in stats[:3]] == before
    assert events == ["linreg_solver[cholesky]"]


def _indefinite_stats(dtype):
    """`_ridge_stats` less enough of the centred Gram's lowest eigenvector
    that the penalised system is no longer positive definite."""
    gram, sxy, s1, sw, sy, syy = _ridge_stats(np.float64, weighted=True)
    mean = s1 / sw
    lam, V = np.linalg.eigh(gram - sw * np.outer(mean, mean))
    gram = gram - (lam[0] + 2.0 * sw * _REG) * np.outer(V[:, 0], V[:, 0])
    return (gram.astype(dtype), sxy.astype(dtype), s1.astype(dtype), sw, sy, syy)


@pytest.mark.parametrize("dtype", [np.float32, np.float64], ids=["f32", "f64"])
def test_closed_form_ridge_indefinite_system_takes_the_lu(dtype):
    """A Gram whose centred part has an eigenvalue under -sw*l2 (what a
    float32 accumulation over near-collinear columns can hand over) has no
    Cholesky factor: the solve answers what the pivoted LU answers."""
    stats = _indefinite_stats(dtype)
    sw = stats[3]
    g64, m64 = np.float64(stats[0]), np.float64(stats[2]) / sw
    system = g64 - sw * np.outer(m64, m64) + sw * _REG * np.eye(len(m64))
    assert np.linalg.eigvalsh(system)[0] < 0.0

    (coef, intercept, diag), events = _solve_and_events(stats, True, False)
    ref_coef, ref_intercept, ref_diag = _textbook_ridge(*stats, True, False)
    np.testing.assert_allclose(coef, ref_coef, rtol=0, atol=1e-10 * np.abs(ref_coef).max())
    assert intercept == pytest.approx(ref_intercept, rel=1e-10, abs=1e-10)
    for k in ("mse", "rmse", "r2"):
        assert diag[k] == pytest.approx(ref_diag[k], rel=1e-9), k
    assert events == ["linreg_solver[lu_fallback]"]


@pytest.mark.parametrize("dtype", [np.float32, np.float64], ids=["f32", "f64"])
def test_closed_form_ridge_solve_makes_one_matrix(dtype):
    """At d = 3000 a (d,d) float64 is 72 MB, first-touched page by page:
    the ten temporaries of the earlier build cost more than the
    factorisation (its peak by this probe: 5.0x d^2 x 8 bytes for a float32
    Gram, 4.0x for a float64 one).  Now: the one system buffer, kept from
    the solve before and factored in place, beside blocks of the Gram for
    the assembly and the summary: no (d,d) array is made at all."""
    d = 1024  # the summary's blocks are ~2 MB: a quarter of a matrix here
    stats = _ridge_stats(dtype, weighted=False, n=2 * d, d=d)
    _solve_and_events(stats, True, True)  # imports and lazy set-up, untraced
    events, peak = _solve_peak(stats)
    assert events == ["linreg_solver[cholesky]"]
    assert peak < 0.5 * d * d * 8, peak / (d * d * 8)


def _solve_peak(stats):
    """The solver's instants and the peak of what the solve allocated."""
    import tracemalloc

    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        _, events = _solve_and_events(stats, True, True)
        return events, tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()


@pytest.mark.parametrize("why", ["first", "busy"])
@pytest.mark.parametrize("dtype", [np.float32, np.float64], ids=["f32", "f64"])
def test_a_solve_into_a_new_system_buffer_makes_one_matrix(dtype, why, monkeypatch):
    """Where the slot cannot serve (empty, or held by another solve), the
    solve maps its one (d,d) float64 buffer and factors it in place, beside
    blocks of the Gram: one matrix, no second."""
    from spark_rapids_ml_tpu.ops.linear import _SystemWorkspace

    d = 1024
    stats = _ridge_stats(dtype, weighted=False, n=2 * d, d=d)
    _solve_and_events(stats, True, True)  # imports and lazy set-up, untraced
    if why == "first":
        monkeypatch.setattr(_SystemWorkspace, "_kept", None)
        events, peak = _solve_peak(stats)
    else:
        held = _held_slot()[0]
        try:
            events, peak = _solve_peak(stats)
        finally:
            held.close()
    assert events == ["linreg_solver[cholesky]"]
    assert d * d * 8 <= peak < 1.5 * d * d * 8, peak / (d * d * 8)


# ---- the system's buffer, kept across solves (`_SystemWorkspace`) ----

# branch -> (regParam, elasticNetParam, the solver instants it records)
_BRANCHES = {
    "cholesky": (_REG, 0.0, ["linreg_solver[cholesky]"]),
    "lu_fallback": (_REG, 0.0, ["linreg_solver[lu_fallback]"]),
    "lstsq": (0.0, 0.0, []),
    "fista": (_REG, 0.5, []),
}


def _workspace_solve(stats, reg_param=_REG, elasticnet_param=0.0, standardization=True):
    """The solve, and its instants: the solver's, and how each assembly had
    its buffer as (name, detail)."""
    from spark_rapids_ml_tpu import tracing
    from spark_rapids_ml_tpu.ops.linear import solve_linear_host

    tracing.reset_trace()
    out = solve_linear_host(
        *stats, reg_param=reg_param, elasticnet_param=elasticnet_param,
        fit_intercept=True, standardization=standardization, tol=1e-30, max_iter=10,
    )
    events = tracing.get_trace_events()
    return (out,
            [e.name for e in events if e.name.startswith("linreg_solver[")],
            [(e.name, e.detail) for e in events
             if e.name.startswith("linreg_solve_workspace[")])


def _assert_same_answer(got, want, rel):
    (coef, intercept, diag), (ref_coef, ref_intercept, ref_diag) = got, want
    np.testing.assert_allclose(coef, ref_coef, rtol=rel, atol=0)
    assert intercept == pytest.approx(ref_intercept, rel=rel, abs=0)
    for k in ("mse", "rmse", "r2", "n_iter"):
        assert diag[k] == pytest.approx(ref_diag[k], rel=rel, abs=0), k


def _assert_textbook(out, stats):
    (coef, intercept, diag), (ref_coef, ref_intercept, ref_diag) = (
        out, _textbook_ridge(*stats, True, False))
    np.testing.assert_allclose(coef, ref_coef, rtol=0, atol=1e-10 * np.abs(ref_coef).max())
    assert intercept == pytest.approx(ref_intercept, rel=1e-10, abs=1e-10)
    for k in ("mse", "rmse", "r2"):
        assert diag[k] == pytest.approx(ref_diag[k], rel=1e-12), k


def _held_slot():
    """A solve in progress elsewhere: its assembly holds the slot until
    `close`, and its system must come through another solve unwritten."""
    from spark_rapids_ml_tpu.ops.linear import _SystemWorkspace

    held = _SystemWorkspace()
    stats = _ridge_stats(np.float64, weighted=False, seed=11)
    system = held.system(stats[0], stats[3], stats[2] / stats[3], None, 1.0)
    return held, system, system.copy()


@pytest.mark.parametrize("standardization", [False, True], ids=["raw", "std"])
@pytest.mark.parametrize("branch", sorted(_BRANCHES))
def test_a_kept_system_buffer_answers_as_a_fresh_one(branch, standardization):
    """The second solve of a d writes into the buffer the first left (its
    factor, of another system, in one triangle) and answers what a solve
    into a buffer of its own answers, on every branch: the LU's second
    assembly over the factorisation that refused, OLS' `lstsq` that reads
    both triangles, FISTA's system divided in place.  The caller's
    statistics stay as they came."""
    reg_param, enet, solvers = _BRANCHES[branch]
    stats = (_indefinite_stats(np.float32) if branch == "lu_fallback"
             else _ridge_stats(np.float32, weighted=True))
    for a in stats[:3]:
        a.setflags(write=False)  # as a fetched device array is
    before = [a.tobytes() for a in stats[:3]]

    _workspace_solve(_ridge_stats(np.float64, weighted=False, seed=7))
    kept, kept_solvers, kept_how = _workspace_solve(
        stats, reg_param, enet, standardization)
    held, system, want = _held_slot()
    try:
        fresh, fresh_solvers, fresh_how = _workspace_solve(
            stats, reg_param, enet, standardization)
    finally:
        held.close()

    assembled = 2 if branch == "lu_fallback" else 1
    assert kept_how == [("linreg_solve_workspace[reused]", "d=24")] * assembled
    assert fresh_how[0] == ("linreg_solve_workspace[new]", "d=24 busy")
    assert kept_solvers == fresh_solvers == solvers
    np.testing.assert_array_equal(system, want)
    _assert_same_answer(kept, fresh, 1e-12)
    assert [a.tobytes() for a in stats[:3]] == before



@pytest.mark.parametrize("why", ["first", "shape"])
def test_a_solve_of_another_d_replaces_the_kept_buffer(why, monkeypatch):
    """An empty slot, or one that holds another d, gets a new buffer, which
    the slot keeps in the old one's place for the next solve of this d."""
    from spark_rapids_ml_tpu.ops.linear import _SystemWorkspace

    if why == "first":
        monkeypatch.setattr(_SystemWorkspace, "_kept", None)
    else:
        _workspace_solve(_ridge_stats(np.float64, weighted=False, d=24))
    stats = _ridge_stats(np.float64, weighted=True, d=16)
    out, solvers, how = _workspace_solve(stats, standardization=False)
    assert how == [("linreg_solve_workspace[new]", f"d=16 {why}")]
    assert solvers == ["linreg_solver[cholesky]"]
    _assert_textbook(out, stats)
    assert _SystemWorkspace._kept.shape == (16, 16)
    assert _workspace_solve(stats)[2] == [("linreg_solve_workspace[reused]", "d=16")]


def test_a_solve_that_finds_the_slot_held_builds_in_a_buffer_of_its_own():
    """Two solves at once (threads of `fitMultiple`, two estimators): the
    second makes its own buffer, as every solve did before the slot, answers
    what the textbook does and leaves the first's system as it was; once the
    first gives the slot back the next solve has it again."""
    stats = _ridge_stats(np.float32, weighted=True)
    held, system, want = _held_slot()
    try:
        out, solvers, how = _workspace_solve(stats, standardization=False)
        np.testing.assert_array_equal(system, want)
    finally:
        held.close()
    assert how == [("linreg_solve_workspace[new]", "d=24 busy")]
    assert solvers == ["linreg_solver[cholesky]"]
    _assert_textbook(out, stats)
    assert _workspace_solve(stats)[2] == [("linreg_solve_workspace[reused]", "d=24")]


def test_solves_on_eight_threads_each_answer_their_own_system():
    """Solves at once on eight threads against the one slot, the interpreter
    switching threads every microsecond, over systems of two widths:
    whichever solve holds the slot, and whatever width it keeps, no solve's
    system is written by another's."""
    import sys
    from concurrent.futures import ThreadPoolExecutor

    from spark_rapids_ml_tpu.ops.linear import solve_linear_host

    problems = ([_ridge_stats(np.float64, weighted=True, seed=s) for s in range(6)]
                + [_ridge_stats(np.float64, weighted=True, d=16, seed=s) for s in range(2)])

    def solve(k):
        return solve_linear_host(
            *problems[k], reg_param=_REG, elasticnet_param=0.0, fit_intercept=True,
            standardization=False, tol=1e-30, max_iter=10)

    def work(i):
        for j in range(24):
            k = (i + j) % len(problems)
            _assert_textbook(solve(k), problems[k])

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = 8
        with ThreadPoolExecutor(threads) as pool:
            for done in [pool.submit(work, i) for i in range(threads)]:
                done.result(timeout=120)
    finally:
        sys.setswitchinterval(switch)
