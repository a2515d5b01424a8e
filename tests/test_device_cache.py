#
# Device-resident dataset cache tests (parallel/device_cache.py): CV
# metric parity between the cached on-device fold path and the legacy
# host-slicing path, stagings-per-run accounting (2k+1 -> 1), LRU
# eviction and over-budget graceful fallback, fold-view byte parity
# against fresh stagings, and the zero-weight-row kernel contract the
# masked fold views rely on (ops SUPPORTS_ZERO_WEIGHT_ROWS).
#
import numpy as np
import pandas as pd
import pytest

import jax

from spark_rapids_ml_tpu.classification import LogisticRegression
from spark_rapids_ml_tpu.config import reset_config, set_config
from spark_rapids_ml_tpu.evaluation import (
    MulticlassClassificationEvaluator,
    RegressionEvaluator,
)
from spark_rapids_ml_tpu.parallel.device_cache import (
    CACHE_METRICS,
    clear_device_cache,
    dataset_fingerprint,
    get_or_stage,
)
from spark_rapids_ml_tpu.parallel.mesh import STAGE_COUNTS, RowStager, get_mesh
from spark_rapids_ml_tpu.regression import LinearRegression
from spark_rapids_ml_tpu.tuning import CrossValidator, ParamGridBuilder


@pytest.fixture(autouse=True)
def _clean_cache():
    # the chunk cache books its device tier through the same external
    # ledger these tests assert exact byte counts against — start from a
    # clean claim table
    from spark_rapids_ml_tpu.parallel.device_cache import clear_chunk_cache

    clear_chunk_cache()
    clear_device_cache()
    yield
    clear_chunk_cache()
    clear_device_cache()
    reset_config()


@pytest.fixture
def reg_df(rng):
    X = rng.normal(size=(300, 4))
    y = X @ np.array([1.0, -2.0, 0.5, 3.0]) + rng.normal(scale=0.1, size=300)
    return pd.DataFrame({"features": list(X), "label": y})


@pytest.fixture
def clf_df(rng):
    X = rng.normal(size=(300, 5))
    y = (X[:, 0] + 0.5 * X[:, 1] + rng.normal(scale=0.3, size=300) > 0)
    return pd.DataFrame({"features": list(X), "label": y.astype(float)})


def _cv(est, grid, evaluator, k=3, seed=7):
    return CrossValidator(
        estimator=est, estimatorParamMaps=grid, evaluator=evaluator,
        numFolds=k, seed=seed,
    )


def _run_both_paths(build_cv, df):
    """Fit the same CV on the cached and legacy paths; return
    ((model, stagings, used_cache), ...) for each."""
    out = []
    for mode in ("on", "off"):
        set_config(device_cache=mode)
        clear_device_cache()
        cv = build_cv()
        s0 = STAGE_COUNTS["dataset_stagings"]
        model = cv.fit(df)
        out.append(
            (model, STAGE_COUNTS["dataset_stagings"] - s0,
             cv._last_fit_used_cache)
        )
    return out


# ---------------------------------------------------------------------------
# CV metric parity: cached on-device folds == legacy host slicing
# ---------------------------------------------------------------------------


def test_cv_parity_linear_regression(reg_df):
    def build():
        lr = LinearRegression()
        grid = ParamGridBuilder().addGrid(lr.regParam, [0.0, 100.0]).build()
        return _cv(lr, grid, RegressionEvaluator(metricName="rmse"), seed=1)

    (m_cached, st_cached, used), (m_legacy, st_legacy, legacy_used) = (
        _run_both_paths(build, reg_df)
    )
    assert used and not legacy_used
    # the whole CV run (3 fold fits + 3 evals x 2 models + refit) pays
    # exactly ONE host->device dataset staging on the cached path
    assert st_cached == 1
    assert st_legacy > 1
    assert m_cached.bestIndex == m_legacy.bestIndex
    np.testing.assert_allclose(
        m_cached.avgMetrics, m_legacy.avgMetrics, rtol=1e-4
    )
    # the refit models predict identically (same resident rows)
    a = m_cached.transform(reg_df)["prediction"].to_numpy()
    b = m_legacy.transform(reg_df)["prediction"].to_numpy()
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_cv_parity_logistic_regression(clf_df):
    def build():
        lr = LogisticRegression(maxIter=50)
        grid = ParamGridBuilder().addGrid(lr.regParam, [0.0, 10.0]).build()
        return _cv(
            lr, grid,
            MulticlassClassificationEvaluator(metricName="accuracy"),
            seed=7,
        )

    (m_cached, st_cached, used), (m_legacy, _, _) = _run_both_paths(
        build, clf_df
    )
    assert used
    assert st_cached == 1
    assert m_cached.bestIndex == m_legacy.bestIndex
    # L-BFGS trajectories under mask-vs-slice differ in f32 reduction
    # order only; accuracy on 100-row folds must agree to a row or two
    np.testing.assert_allclose(
        m_cached.avgMetrics, m_legacy.avgMetrics, atol=0.02
    )


def test_cv_parity_random_forest_gather_path(rng):
    """End-to-end gather-path CV (RandomForest keeps the default
    `_supports_fold_weights() == False`): the compacted on-device views
    are byte-identical to legacy stagings, so the seeded forest — and
    hence the metrics — match the legacy path exactly."""
    from spark_rapids_ml_tpu.classification import RandomForestClassifier

    X = rng.normal(size=(240, 4))
    y = (X[:, 0] > 0).astype(float)
    df = pd.DataFrame({"features": list(X), "label": y})

    def build():
        rf = RandomForestClassifier(numTrees=3, maxDepth=3, seed=5)
        grid = ParamGridBuilder().addGrid(rf.numTrees, [3]).build()
        return _cv(
            rf, grid,
            MulticlassClassificationEvaluator(metricName="accuracy"),
            k=2, seed=3,
        )

    (m_cached, st_cached, used), (m_legacy, _, _) = _run_both_paths(build, df)
    assert used
    assert st_cached == 1
    assert m_cached.bestIndex == m_legacy.bestIndex
    np.testing.assert_allclose(m_cached.avgMetrics, m_legacy.avgMetrics)


def test_cv_cache_hit_on_repeat_fit(reg_df):
    set_config(device_cache="on")
    lr = LinearRegression()
    grid = ParamGridBuilder().addGrid(lr.regParam, [0.0, 1.0]).build()
    build = lambda: _cv(lr, grid, RegressionEvaluator(metricName="rmse"))
    m1 = build().fit(reg_df)
    h0, s0 = CACHE_METRICS["hits"], STAGE_COUNTS["dataset_stagings"]
    m2 = build().fit(reg_df)
    # repeat tuning of the same data: zero stagings, served by the cache
    assert STAGE_COUNTS["dataset_stagings"] - s0 == 0
    assert CACHE_METRICS["hits"] - h0 >= 1
    np.testing.assert_allclose(m1.avgMetrics, m2.avgMetrics)


# ---------------------------------------------------------------------------
# fold views
# ---------------------------------------------------------------------------


def _entry(rng, n=333, d=5, with_weights=True):
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, 2, n).astype(np.float32)
    w = (
        rng.uniform(0.5, 2.0, n).astype(np.float32)
        if with_weights else None
    )
    entry = get_or_stage(X, y, w, dtype=np.float32, label_dtype=np.float32)
    assert entry is not None
    return X, y, w, entry


def test_gather_view_matches_fresh_staging(rng):
    """The on-device gather/compaction view is BYTE-identical to a fresh
    host staging of the fold's slice — the property that makes gather-path
    fits reproduce the legacy trajectory exactly (seeded inits included)."""
    X, y, w, entry = _entry(rng)
    folds = rng.integers(0, 3, X.shape[0])
    fold_set = entry.fold_set(folds)
    for fold in range(3):
        sel = folds != fold
        view = fold_set.gather_train_view(fold)
        st_ref = RowStager(int(sel.sum()), get_mesh())
        assert np.array_equal(
            np.asarray(jax.device_get(view.X)),
            np.asarray(jax.device_get(st_ref.stage(X[sel], np.float32))),
        )
        assert np.array_equal(
            np.asarray(jax.device_get(view.weight)),
            np.asarray(
                jax.device_get(st_ref.mask(np.float32, weights=w[sel]))
            ),
        )
        assert np.array_equal(
            np.asarray(jax.device_get(view.y)),
            np.asarray(jax.device_get(st_ref.stage(y[sel], np.float32))),
        )


def test_mask_view_zeroes_exactly_the_fold(rng):
    X, y, w, entry = _entry(rng)
    folds = rng.integers(0, 3, X.shape[0])
    fold_set = entry.fold_set(folds)
    for fold in range(3):
        view = fold_set.train_view(fold)
        wm = entry.stager.fetch(view.weight)
        np.testing.assert_allclose(wm, np.where(folds != fold, w, 0.0))
        # X and y are the SAME resident arrays (views, not copies)
        assert view.X is entry.dataset.X
        assert view.y is entry.dataset.y


def test_eval_view_selects_fold_rows(rng, reg_df):
    set_config(device_cache="on")
    lr = LinearRegression()
    entry = lr._cached_fit_entry(reg_df)
    assert entry is not None
    folds = rng.integers(0, 3, len(reg_df))
    fold_set = entry.fold_set(folds)
    model = lr.fit(entry.dataset)
    ev = RegressionEvaluator(metricName="rmse")
    view = fold_set.eval_view(1, reg_df[folds == 1].reset_index(drop=True))
    (cached_metric,) = view.evaluate([model], ev)
    legacy_metric = ev.evaluate(
        model.transform(reg_df[folds == 1].reset_index(drop=True))
    )
    np.testing.assert_allclose(cached_metric, legacy_metric, rtol=1e-5)


# ---------------------------------------------------------------------------
# budget accounting: LRU eviction + graceful fallback
# ---------------------------------------------------------------------------


def test_lru_eviction_under_budget(rng):
    X, y, w, entry = _entry(rng)
    one = entry.nbytes
    clear_device_cache()
    set_config(device_cache_bytes=one + one // 2)  # room for ONE entry
    e0, s0 = CACHE_METRICS["evictions"], STAGE_COUNTS["dataset_stagings"]
    e1 = get_or_stage(X, y, w, dtype=np.float32, label_dtype=np.float32)
    e2 = get_or_stage(X + 1.0, y, w, dtype=np.float32,
                      label_dtype=np.float32)
    assert e1 is not None and e2 is not None
    # the second insert evicted the first (LRU), residency stays bounded
    assert CACHE_METRICS["evictions"] - e0 == 1
    assert CACHE_METRICS["resident_entries"] == 1
    assert CACHE_METRICS["resident_bytes"] <= one + one // 2
    # the evicted dataset must RESTAGE on its next use (no stale handle)
    e1b = get_or_stage(X, y, w, dtype=np.float32, label_dtype=np.float32)
    assert e1b is not None and e1b is not e1
    assert STAGE_COUNTS["dataset_stagings"] - s0 == 3


def test_resident_bytes_visible_to_budget_model(rng):
    """Resident cache bytes count into `_over_device_budget` estimates,
    and because residency is re-creatable it is LRU-evicted rather than
    pushing a fit onto the streamed-statistics path."""
    from spark_rapids_ml_tpu.parallel.device_cache import (
        cache_resident_bytes,
        device_data_budget_bytes,
    )

    X, y, w, entry = _entry(rng)
    assert cache_resident_bytes() == entry.nbytes
    lr = LinearRegression()
    budget = device_data_budget_bytes()
    # an estimate within the residual headroom leaves the entry resident
    assert not lr._over_device_budget(1024)
    assert cache_resident_bytes() == entry.nbytes
    # one that fits only if the droppable residency goes EVICTS it
    # instead of degrading the fit
    assert not lr._over_device_budget(budget - entry.nbytes + 1)
    assert cache_resident_bytes() == 0
    # a genuinely over-budget estimate still reads over budget
    assert lr._over_device_budget(budget + 1)


def test_cache_hit_tops_up_gather_headroom(rng):
    """A gather-path consumer hitting an entry a mask-path consumer
    inserted must reserve its extra per-fold headroom (or miss)."""
    X, y, w, entry = _entry(rng)  # factor 1.0: nbytes == base_bytes
    assert entry.nbytes == entry.base_bytes
    e2 = get_or_stage(X, y, w, dtype=np.float32, label_dtype=np.float32,
                      working_factor=4.0)
    assert e2 is entry
    assert entry.nbytes == entry.base_bytes * 4
    # headroom that cannot fit -> the hit degrades to a miss, the entry
    # itself stays resident for its existing consumers
    set_config(device_cache_bytes=entry.nbytes + 1)
    e3 = get_or_stage(X, y, w, dtype=np.float32, label_dtype=np.float32,
                      working_factor=100.0)
    assert e3 is None
    assert CACHE_METRICS["resident_entries"] == 1


def test_over_budget_falls_back_to_legacy_cv(reg_df):
    set_config(device_cache="on", device_cache_bytes=64)  # nothing fits
    lr = LinearRegression()
    grid = ParamGridBuilder().addGrid(lr.regParam, [0.0, 100.0]).build()
    cv = _cv(lr, grid, RegressionEvaluator(metricName="rmse"), seed=1)
    model = cv.fit(reg_df)
    # degraded gracefully: legacy path ran and produced a valid result
    assert not cv._last_fit_used_cache
    assert CACHE_METRICS["resident_entries"] == 0
    assert model.bestIndex == 0


def test_device_cache_off_disables_path(reg_df):
    set_config(device_cache="off")
    lr = LinearRegression()
    grid = ParamGridBuilder().addGrid(lr.regParam, [0.0]).build()
    cv = _cv(lr, grid, RegressionEvaluator(metricName="rmse"))
    cv.fit(reg_df)
    assert not cv._last_fit_used_cache
    assert CACHE_METRICS["resident_entries"] == 0


def test_fingerprint_binds_content_and_dtype(rng):
    X = rng.normal(size=(64, 3)).astype(np.float32)
    mesh = get_mesh()
    fp = dataset_fingerprint(X, None, None, np.float32, None, mesh)
    assert fp == dataset_fingerprint(
        X.copy(), None, None, np.float32, None, mesh
    )
    X2 = X.copy()
    X2[5, 1] += 1e-3
    assert fp != dataset_fingerprint(X2, None, None, np.float32, None, mesh)
    assert fp != dataset_fingerprint(X, None, None, np.float64, None, mesh)
    y = np.ones((64,), np.float32)
    assert fp != dataset_fingerprint(X, y, None, np.float32, np.float32,
                                     mesh)


# ---------------------------------------------------------------------------
# the zero-weight-row kernel contract (ops sample-weight/mask plumbing)
# ---------------------------------------------------------------------------


def _with_zero_rows(X, w, rng, extra=7):
    """Append `extra` garbage rows at weight 0 — the masked-fold shape."""
    Xz = np.concatenate([X, rng.normal(size=(extra, X.shape[1]))]).astype(
        X.dtype
    )
    wz = np.concatenate([w, np.zeros((extra,), w.dtype)])
    return Xz, wz


def test_ops_zero_weight_row_invariance(rng):
    """pca/linear/kmeans kernels declare SUPPORTS_ZERO_WEIGHT_ROWS: a
    w=0 row must be mathematically absent from every reduction (the
    contract the masked fold views AND bucket padding rely on)."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import kmeans as kmeans_ops
    from spark_rapids_ml_tpu.ops import linear as linear_ops
    from spark_rapids_ml_tpu.ops import logistic as logistic_ops
    from spark_rapids_ml_tpu.ops import pca as pca_ops

    assert pca_ops.SUPPORTS_ZERO_WEIGHT_ROWS
    assert linear_ops.SUPPORTS_ZERO_WEIGHT_ROWS
    assert logistic_ops.SUPPORTS_ZERO_WEIGHT_ROWS
    assert kmeans_ops.SUPPORTS_ZERO_WEIGHT_ROWS

    X = rng.normal(size=(80, 4)).astype(np.float32)
    w = rng.uniform(0.5, 2.0, 80).astype(np.float32)
    y = rng.normal(size=80).astype(np.float32)
    Xz, wz = _with_zero_rows(X, w, rng)
    yz = np.concatenate([y, np.full((7,), 1e3, np.float32)])

    def pca(X, w):
        scatter, s1, sw, shift = pca_ops.pca_scatter(jnp.asarray(X), jnp.asarray(w))
        return pca_ops.pca_eigensolve_host(scatter, s1, float(sw), shift, 2)

    mean_a, comp_a, *_ = pca(X, w)
    mean_b, comp_b, *_ = pca(Xz, wz)
    np.testing.assert_allclose(np.asarray(mean_a), np.asarray(mean_b),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(comp_a), np.asarray(comp_b),
                               rtol=1e-4, atol=1e-5)

    stats_a = linear_ops.linreg_sufficient_stats(
        jnp.asarray(X), jnp.asarray(w), jnp.asarray(y)
    )
    stats_b = linear_ops.linreg_sufficient_stats(
        jnp.asarray(Xz), jnp.asarray(wz), jnp.asarray(yz)
    )
    # the split Gram too, a TPU's path (called directly: a CPU fit takes the
    # single matmul), its row blocks not tiling the rows
    split_a = linear_ops.linreg_stats_split(
        jnp.asarray(X), jnp.asarray(w), jnp.asarray(y), block_rows=32, panel_cols=2
    )
    split_b = linear_ops.linreg_stats_split(
        jnp.asarray(Xz), jnp.asarray(wz), jnp.asarray(yz), block_rows=32, panel_cols=2
    )
    for a, b in [*zip(stats_a, stats_b), *zip(split_a, split_b), *zip(split_a, stats_a)]:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)

    C = jnp.asarray(rng.normal(size=(3, 4)).astype(np.float32))
    np.testing.assert_allclose(
        float(kmeans_ops.kmeans_cost(jnp.asarray(X), jnp.asarray(w), C)),
        float(kmeans_ops.kmeans_cost(jnp.asarray(Xz), jnp.asarray(wz), C)),
        rtol=1e-5,
    )
