#
# PCA equivalence tests — the analog of the reference's tests/test_pca.py
# CPU-reference comparisons (SURVEY.md §4: every algorithm compared against
# pyspark.ml / sklearn with array_equal tolerances).
#
import numpy as np
import pandas as pd
import pytest
from sklearn.decomposition import PCA as SkPCA

from spark_rapids_ml_tpu.feature import PCA, PCAModel
from spark_rapids_ml_tpu.utils import array_equal_tol


def _make_data(rng, n=500, d=8):
    A = rng.normal(size=(d, d))
    X = rng.normal(size=(n, d)) @ A + rng.normal(size=(d,)) * 3.0
    return X.astype(np.float64)


def test_pca_matches_sklearn(num_workers, rng):
    X = _make_data(rng)
    k = 3
    model = PCA(k=k, num_workers=num_workers).setInputCol("features").fit(X)
    sk = SkPCA(n_components=k, svd_solver="full").fit(X)

    assert model.components_.shape == (k, X.shape[1])
    assert array_equal_tol(model.mean_, sk.mean_, 1e-3)
    assert array_equal_tol(model.explained_variance_, sk.explained_variance_, 1e-2)
    assert array_equal_tol(
        model.explained_variance_ratio_, sk.explained_variance_ratio_, 1e-4
    )
    # components equal up to per-component sign
    for i in range(k):
        dot = abs(float(np.dot(model.components_[i], sk.components_[i])))
        assert dot == pytest.approx(1.0, abs=1e-3)


def test_pca_spark_transform_semantics(num_workers, rng):
    """Spark PCA projects WITHOUT mean removal (reference feature.py:447-459)."""
    X = _make_data(rng, n=200, d=5)
    df = pd.DataFrame({"features": list(X)})
    model = (
        PCA(k=2, num_workers=num_workers)
        .setInputCol("features")
        .setOutputCol("pca_features")
        .fit(df)
    )
    out = model.transform(df)
    got = np.stack(out["pca_features"].to_numpy())
    expected = X.astype(np.float32) @ model.components_.T.astype(np.float32)
    assert array_equal_tol(got, expected, 1e-3)


def test_pca_doctest_example(num_workers):
    """Reference doctest (feature.py:155-197): 3-point diagonal."""
    df = pd.DataFrame({"features": [[-1.0, -1.0], [0.0, 0.0], [1.0, 1.0]]})
    model = (
        PCA(k=1, num_workers=num_workers)
        .setInputCol("features")
        .setOutputCol("pca_features")
        .fit(df)
    )
    out = model.transform(df)
    vals = np.array([v[0] for v in out["pca_features"]])
    expected = np.array([-1.41421356, 0.0, 1.41421356])
    sign = np.sign(vals[2]) or 1.0
    assert np.allclose(vals * sign, expected, atol=1e-5)


def test_pca_multi_col_input(num_workers, rng):
    X = _make_data(rng, n=100, d=4)
    cols = [f"c{i}" for i in range(4)]
    df = pd.DataFrame(X, columns=cols)
    model = PCA(k=2, num_workers=num_workers).setInputCol(cols).fit(df)
    sk = SkPCA(n_components=2, svd_solver="full").fit(X)
    assert array_equal_tol(model.explained_variance_, sk.explained_variance_, 1e-2)


def test_pca_save_load(tmp_path, rng):
    X = _make_data(rng, n=100, d=4)
    model = PCA(k=2).setInputCol("features").setOutputCol("out").fit(X)
    path = str(tmp_path / "pca_model")
    model.write().save(path)
    loaded = PCAModel.load(path)
    assert array_equal_tol(loaded.components_, model.components_, 1e-7)
    assert array_equal_tol(loaded.mean_, model.mean_, 1e-7)
    assert loaded.getOrDefault("outputCol") == "out"
    assert loaded.n_cols == 4

    est_path = str(tmp_path / "pca_est")
    est = PCA(k=3).setInputCol("features")
    est.write().save(est_path)
    est2 = PCA.load(est_path)
    assert est2.getOrDefault("k") == 3
    assert est2._tpu_params["n_components"] == 3


def test_pca_float64(rng):
    X = _make_data(rng, n=100, d=4)
    model = PCA(k=2, float32_inputs=False).setInputCol("features").fit(X)
    sk = SkPCA(n_components=2, svd_solver="full").fit(X)
    assert array_equal_tol(model.explained_variance_, sk.explained_variance_, 1e-8)


def test_pca_cpu_fallback(rng):
    X = _make_data(rng, n=50, d=4)
    from spark_rapids_ml_tpu import config

    config.set_config(cpu_fallback_enabled=True)
    try:
        est = PCA(k=2).setInputCol("features")
        est._set_params(svd_solver="randomized")  # backend kwarg passthrough
        model = est.fit(X)
        assert model.components_.shape == (2, 4)
    finally:
        config.reset_config()


def test_stats_precision_config_retraces():
    """Changing `stats_precision` must invalidate compiled kernels — it
    is baked in at trace time (ops/precision.py), so without cache
    invalidation a same-shape call would silently keep the old precision
    (mirror of test_distance_precision_config_retraces)."""
    import jax

    from spark_rapids_ml_tpu.config import reset_config, set_config
    from spark_rapids_ml_tpu.ops.linear import _linreg_sufficient_stats_xla as gram
    from spark_rapids_ml_tpu.ops.pca import pca_scatter

    X = np.random.default_rng(0).standard_normal((32, 5)).astype(np.float32)
    w = np.ones((32,), np.float32)

    # the covariance's products: the Gram program PCA shares with ridge
    def cov_fn(X, w):
        return gram(X, w, None, np.zeros(5, np.float32))

    jax.clear_caches()  # earlier tests' shapes would skew counts
    try:
        set_config(stats_precision="highest")
        assert "HIGHEST" in str(jax.make_jaxpr(cov_fn)(X, w))
        pca_scatter(jax.numpy.asarray(X), jax.numpy.asarray(w))
        assert gram._cache_size() == 1
        set_config(stats_precision="default")
        # the compiled HIGHEST executable must be GONE — a same-shape
        # call would otherwise silently keep the old precision
        assert gram._cache_size() == 0
        assert "HIGHEST" not in str(jax.make_jaxpr(cov_fn)(X, w))
        pca_scatter(jax.numpy.asarray(X), jax.numpy.asarray(w))
        assert gram._cache_size() == 1
    finally:
        reset_config()


def test_stats_precision_invalid_value():
    from spark_rapids_ml_tpu.config import reset_config, set_config
    from spark_rapids_ml_tpu.ops.precision import stats_precision

    try:
        set_config(stats_precision="sloppy")
        with pytest.raises(ValueError, match="stats_precision"):
            stats_precision()
    finally:
        reset_config()


def test_stats_precision_results_invariant_on_cpu(rng):
    """On CPU every precision level is true f32, so flipping the conf
    must not change PCA components or LinReg coefficients — this pins
    the conf to being a PRECISION knob, not a semantics knob."""
    from spark_rapids_ml_tpu.config import reset_config, set_config
    from spark_rapids_ml_tpu.regression import LinearRegression

    X = _make_data(rng, n=120, d=6)
    yw = rng.standard_normal(6).astype(np.float32)
    y = (X @ yw).astype(np.float32)
    results = {}
    try:
        for level in ("highest", "high", "default"):
            set_config(stats_precision=level)
            m = PCA(k=3).setInputCol("features").fit(X)
            lr = LinearRegression(regParam=0.0, elasticNetParam=0.0).fit(
                (np.ascontiguousarray(X).astype(np.float32), y)
            )
            results[level] = (m.components_, np.asarray(lr.coefficients))
    finally:
        reset_config()
    ref_c, ref_w = results["highest"]
    for level in ("high", "default"):
        c, wv = results[level]
        np.testing.assert_allclose(np.abs(c), np.abs(ref_c), atol=1e-6)
        np.testing.assert_allclose(wv, ref_w, atol=1e-6)


# ---------------------------------------------------------------------------
# The resident exact route (ops/pca.py `pca_scatter` + `pca_eigensolve_host`):
# what the reference's pca benchmark row (1M x 3000 low-rank rows, k=3;
# chipbench's `pca_fit_cached`) asks of the program, at sizes the CPU runs.
# ---------------------------------------------------------------------------

LOW_RANK = {"model": "low_rank", "effective_rank": 10, "tail_strength": 0.5}
K3 = {"k": 3}
# The five gaps of chipbench/estimators/pca.py at 4,096 rows of 48 and of 640
# columns, on one device and two, XLA's product and the split Gram.  Read
# when the route was written (seeds 2**31+11, +12): the program, whose
# float32 products are exact on the CPU, 1.4e-8 to 9.3e-8 / 2.5e-8 to 3.2e-8 /
# 2.5e-8 to 4.4e-8 / 3.0e-8 to 2.6e-7 / 2.7e-8 to 3.3e-8; the control (rows
# rounded to bfloat16) 1.6e-4 to 4.8e-4 / 1.1e-5 to 3.5e-5 / 1.2e-5 to 2.6e-5 /
# 1.5e-4 to 1.1e-3 / 8.2e-5 to 1.4e-4.  Each limit is near the geometric middle.
SMALL_LIMITS = {
    "mean_gap": 5e-6, "variance_gap": 1e-6, "ratio_gap": 1e-6,
    "component_gap": 1e-5, "residual": 2e-6,
}


def _low_rank_rows(n_dev, rows, cols, seed=2**31 + 11):
    import jax

    from chipbench import datagen
    from spark_rapids_ml_tpu.parallel import get_mesh

    if len(jax.devices()) < n_dev:
        pytest.skip(f"needs {n_dev} devices")
    mesh = get_mesh(n_dev)
    return mesh, datagen.make_rows(mesh, rows, cols, seed, LOW_RANK, 256)


def _span_nodes(model):
    def walk(nodes):
        for n in nodes:
            yield n
            yield from walk(n.get("children", []))

    return list(walk(model.fit_report()["spans"]))


def _span_names(model):
    return [n["name"] for n in _span_nodes(model)]


@pytest.fixture
def pca_adapter(monkeypatch):
    from chipbench import blocks
    from chipbench import manifest as mf
    from spark_rapids_ml_tpu.config import reset_config

    monkeypatch.setattr(blocks, "BLOCK_ROWS", 256)
    reset_config()
    yield mf.adapter("pca")
    reset_config()


@pytest.mark.parametrize("cols,kernel,eigensolver", [
    (48, "xla", "host_lapack"), (640, "symmetric_split", "subspace_polished")])
@pytest.mark.parametrize("n_dev", [1, 2])
def test_resident_fit_agrees_with_the_plain_reference(n_dev, cols, kernel, eigensolver,
                                                       pca_adapter, monkeypatch):
    from spark_rapids_ml_tpu.data import DeviceDataset
    from spark_rapids_ml_tpu.ops import linear

    if kernel == "symmetric_split":
        # a TPU's route, its bfloat16 products emulated on the CPU
        monkeypatch.setattr(linear, "_on_tpu", lambda X: True)
    mesh, (X, y, w) = _low_rank_rows(n_dev, 4096, cols)
    model = pca_adapter.build(K3, n_dev).fit(DeviceDataset(mesh, X, 4096, y=y, weight=w))
    names = _span_names(model)
    assert [n for n in names if n.startswith("linreg_gram_kernel[")] == [
        f"linreg_gram_kernel[{kernel}]"]
    assert [n for n in names if n.startswith("pca_eigensolver[")] == [
        f"pca_eigensolver[{eigensolver}]"]
    decision = model.fit_report()["solver_decision"]
    assert decision["solver"] == "full" and decision["reason"].startswith("auto:resident")
    ref = pca_adapter.reference(X, y, K3)
    got = pca_adapter.compare(pca_adapter.answer(model), ref)
    assert all(got[k] <= SMALL_LIMITS[k] for k in SMALL_LIMITS), got
    low = pca_adapter.compare(pca_adapter.reference(X, y, K3, lowered=True), ref)
    assert any(low[k] > SMALL_LIMITS[k] for k in SMALL_LIMITS), low
    assert set(got) == set(SMALL_LIMITS)


# ---------------------------------------------------------------------------
# The block route (ops/pca.py `_pca_subspace_iterate` + `pca_eigensolve_polished`)
# against LAPACK's (`pca_eigensolve_host`) on the SAME statistics, and the
# rule that chooses between them (`subspace_plan`, the polish's residual test).
# ---------------------------------------------------------------------------

def _eigensolver_instants(model):
    return [n for n in _span_names(model) if n.startswith("pca_eigensolver[")]


def _instant_detail(model, name):
    (detail,) = [n.get("detail", "") for n in _span_nodes(model) if n["name"] == name]
    return detail


@pytest.mark.parametrize("rows_are", ["plain", "weighted", "offset_column"])
@pytest.mark.parametrize("n_dev", [1, 2])
def test_the_block_route_gives_lapacks_pairs_of_the_same_statistics(n_dev, rows_are):
    """640 columns of the low-rank rows, k = 3, XLA's product (whose [i,j]
    and [j,i] are rounded apart: both routes read one triangle).  Before
    the cast: components to 1e-8 with the same signs, variances, ratios and
    singular values to 1e-10, the mean to the bit; then two fits of the same
    rows through the estimator, bit for bit."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.data import DeviceDataset
    from spark_rapids_ml_tpu.ops import pca
    from spark_rapids_ml_tpu.ops.linear import linreg_sufficient_stats

    mesh, (X, y, w) = _low_rank_rows(n_dev, 4096, 640)
    if rows_are == "weighted":
        w = w * jax.random.uniform(jax.random.key(3), w.shape, w.dtype, 0.25, 4.0)
    if rows_are == "offset_column":
        X = X.at[:, 5].add(3.0)
    scatter, s1, sw, shift = pca.pca_scatter(X, w)
    if rows_are == "offset_column":
        # ... and second moments about a shift 1e-3 off the mean in every
        # column: the rank-one centring is no rounding any more (it moves
        # the top eigenvalue by 6e-4 of itself, the gaps are 2e-2)
        shift = shift + 1e-3
        scatter, s1, sw = linreg_sufficient_stats(X, w, None, shift=shift)
        assert abs(float(s1[0] / sw)) > 5e-4 and abs(float(shift[5])) > 2.9
    if rows_are == "weighted":
        # (X w)^T X: the two triangles are different roundings
        assert float(jnp.max(jnp.abs(scatter - scatter.T))) > 0.0
    block, steps, why = pca.subspace_plan(640, 3)
    assert (block, why) == (pca._SUBSPACE_BLOCK, "")
    host = [np.asarray(a) for a in (scatter, s1, sw, shift)]
    start = np.asarray(pca._pca_subspace_iterate(scatter, block, steps))
    got, sweeps, bound = pca.pca_eigensolve_polished(*host, 3, start)
    want = pca.pca_eigensolve_host(*host, 3)
    assert 1 <= sweeps <= pca._SUBSPACE_POLISH_CAP and bound <= pca._SUBSPACE_TOL
    assert all(a.dtype == np.float64 for a in got)
    np.testing.assert_array_equal(got[0], want[0])
    assert np.max(np.linalg.norm(got[1] - want[1], axis=1)) <= 1e-8
    for g, h in zip(got[2:], want[2:]):
        np.testing.assert_allclose(g, h, rtol=1e-10, atol=0.0)

    rows = DeviceDataset(mesh, X, 4096, y=y, weight=w)
    first, again = PCA(k=3, num_workers=n_dev).fit(rows), PCA(k=3, num_workers=n_dev).fit(rows)
    assert _eigensolver_instants(first) == ["pca_eigensolver[subspace_polished]"]
    for name in ("mean_", "components_", "explained_variance_",
                 "explained_variance_ratio_", "singular_values_"):
        np.testing.assert_array_equal(getattr(first, name), getattr(again, name))
    detail = _instant_detail(first, "pca_eigensolver[subspace_polished]")
    assert f"block={block} device_steps={steps} polish_steps=" in detail
    assert float(detail.split("estimate=")[1].split(":")[0]) <= pca._SUBSPACE_TOL


def _double_top_rows(rng, n=4096, d=640):
    """Rows whose two largest eigenvalues are equal but for float32
    rounding: orthonormal columns with no mean, scaled 2, 2, 1.5, 1.4, ..."""
    U = rng.standard_normal((n, d))
    U -= U.mean(axis=0)
    U = np.linalg.qr(U)[0]
    s = np.concatenate([[2.0, 2.0], 1.5 * 0.93 ** np.arange(d - 2)])
    V = np.linalg.qr(rng.standard_normal((d, d)))[0]
    return ((U * s) @ V.T * np.sqrt(n)).astype(np.float32)


@pytest.mark.parametrize("case", [
    "iid_rows", "narrow", "k_none", "k_over_d_8", "double_top", "float64_rows"])
def test_which_eigensolver_answers_is_read_from_shape_and_spectrum(case, rng, monkeypatch):
    from spark_rapids_ml_tpu.data import DeviceDataset
    from spark_rapids_ml_tpu.ops import pca

    n, d, k = 4096, 640, 3
    exact = 2e-6  # answers cast to float32
    if case == "narrow":
        d = 48
    if case == "k_none":
        k = None
    if case == "k_over_d_8":
        k = 81
    if case == "double_top":
        X = _double_top_rows(rng)
    elif case == "float64_rows":
        # float64 second moments: the device half is float32 all the same,
        # and the polish ends at float64's pairs
        X = rng.standard_normal((n, d)) * 0.9 ** np.arange(d) + 5.0
        exact = 1e-9
    else:
        # iid rows: a Marchenko-Pastur bulk, lambda_{b+1} / lambda_k near 1
        X = rng.standard_normal((n, d)).astype(np.float32)
    if case in ("narrow", "k_none", "k_over_d_8"):
        # by shape, before any device program is traced
        def never(*a, **kw):
            raise AssertionError("the block iteration was dispatched")

        monkeypatch.setattr(pca, "_pca_subspace_iterate", never)
    seen = []
    real = pca.pca_eigensolve_host
    monkeypatch.setattr(pca, "pca_eigensolve_host",
                        lambda *a: seen.append(real(*a)) or seen[-1])
    model = PCA(k=k, float32_inputs=case != "float64_rows").fit(
        DeviceDataset.from_host(X, num_workers=1, dtype=X.dtype))
    (instant,) = _eigensolver_instants(model)
    detail = _instant_detail(model, instant)
    if case == "float64_rows":
        assert instant == "pca_eigensolver[subspace_polished]" and "float64 covariance" in detail
        assert model.components_.dtype == np.float64
    elif case == "double_top":
        # either route: the polish's residual test decides, and does not hang
        assert instant in ("pca_eigensolver[subspace_polished]", "pca_eigensolver[host_lapack]")
        assert (instant == "pca_eigensolver[host_lapack]") == detail.startswith("not_converged")
    else:
        assert instant == "pca_eigensolver[host_lapack]"
        assert detail.startswith({
            "iid_rows": "not_converged: block=32 device_steps=", "narrow": "narrow: d=48<",
            "k_none": "k>d/8: k=640,", "k_over_d_8": "k>d/8: k=81,"}[case]), detail
        # LAPACK's own answer, not a polished one
        for name, at in (("mean_", 0), ("components_", 1), ("explained_variance_", 2)):
            np.testing.assert_array_equal(getattr(model, name), seen[0][at].astype(np.float32))
    if case == "iid_rows":
        sweeps = int(detail.split("polish_steps=")[1].split()[0])
        assert 2 <= sweeps <= pca._SUBSPACE_POLISH_CAP
        assert float(detail.split("estimate=")[1].split(":")[0]) > pca._SUBSPACE_TOL
    # whoever answered, the pairs are eigenpairs of the rows' covariance
    top = 3 if k is None else min(k, 3)
    cov = np.cov(X.astype(np.float64), rowvar=False)
    v = np.asarray(model.components_[:top], np.float64)
    Cv = v @ cov
    rayleigh = np.einsum("ij,ij->i", Cv, v)
    evals = np.linalg.eigvalsh(cov)[::-1]
    assert np.max(np.linalg.norm(Cv - rayleigh[:, None] * v, axis=1)) <= exact * evals[0]
    np.testing.assert_allclose(model.explained_variance_[:top], evals[:top], rtol=exact)


def _fault(name, pca_adapter):
    """(a faulty answer, the reference it is held to)."""
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.data import DeviceDataset

    mesh, (X, y, w) = _low_rank_rows(1, 4096, 48)
    if name == "offset_mean_not_removed":
        # rows with an offset column, and second moments about zero
        X = X.at[:, 5].add(3.0)
    ref = pca_adapter.reference(X, y, K3)
    if name == "offset_mean_not_removed":
        Xh = np.asarray(X, np.float64)
        return pca_adapter.eigen_answer(Xh.T @ Xh, np.zeros(48), 4096, 3), ref
    if name == "half_the_rows":
        w = w * (np.arange(4096) < 2048)
    if name == "sketch_13_columns_no_power_iteration":
        set_config(pca_solver="randomized", pca_oversamples=10, pca_power_iters=0)
    est, rows = pca_adapter.build(K3, 1), DeviceDataset(mesh, X, 4096, y=y, weight=w)
    if name == "sketch_13_columns_no_power_iteration":
        # the adapter ends a run whose warm-up fit answers from a sketch; a
        # later fit is not asked, and its answer fails the comparison
        with pytest.raises(SystemExit, match="not the exact 'full'"):
            est.fit(rows)
    ans = pca_adapter.answer(est.fit(rows))
    if name == "component_coordinate_1pct_off":
        at = np.argmax(np.abs(ans["components"][1]))
        ans["components"][1, at] *= 1.01
    if name == "explained_variance_1pct_off":
        ans["variance"][2] *= 1.01
    return ans, ref


@pytest.mark.parametrize("fault", [
    "none", "component_coordinate_1pct_off", "explained_variance_1pct_off",
    "half_the_rows", "offset_mean_not_removed", "sketch_13_columns_no_power_iteration",
])
def test_a_faulty_answer_fails_the_comparison(fault, pca_adapter):
    ans, ref = _fault(fault, pca_adapter)
    got = pca_adapter.compare(ans, ref)
    over = {k for k in SMALL_LIMITS if not got[k] <= SMALL_LIMITS[k]}
    assert bool(over) == (fault != "none"), (over, got)
    # which number sees which fault
    see = {
        "component_coordinate_1pct_off": "component_gap",
        "explained_variance_1pct_off": "variance_gap",
        "offset_mean_not_removed": "mean_gap",
        "sketch_13_columns_no_power_iteration": "residual",
    }
    if fault in see:
        assert see[fault] in over, got


def test_a_column_far_from_zero_keeps_its_variance(num_workers, rng):
    """One column at 100 +- 2 among unit columns: its variance and the
    component through it to 1e-5, which second moments about zero cannot
    give in float32 (the sum of squares is 4e7 a column, one unit in its
    last place 4: a variance of 4 over 4,096 rows keeps three digits)."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import linear

    X = rng.standard_normal((4096, 8))
    X[:, 3] = 100.0 + 2.0 * X[:, 3]
    X = X.astype(np.float32)
    model = PCA(k=1, num_workers=num_workers).setInputCol("features").fit(X)
    X64 = X.astype(np.float64)
    evals, evecs = np.linalg.eigh(np.cov(X64, rowvar=False))
    top = evecs[:, -1] * np.sign(evecs[3, -1])
    assert abs(model.explained_variance_[0] / evals[-1] - 1.0) < 1e-5
    assert np.linalg.norm(model.components_[0] - top) < 1e-5
    assert abs(model.mean_[3] / X64[:, 3].mean() - 1.0) < 1e-6
    # the same rows through the same Gram with no shift
    gram, s1, sw = (np.asarray(a, np.float64) for a in linear.linreg_sufficient_stats(
        jnp.asarray(X), jnp.ones(4096, jnp.float32), None))
    cov = (gram - np.outer(s1, s1) / sw) / (sw - 1.0)
    assert abs(np.linalg.eigvalsh(cov)[-1] / evals[-1] - 1.0) > 1e-5


def test_zero_weight_rows_are_absent_from_a_resident_fit(rng):
    from spark_rapids_ml_tpu.data import DeviceDataset

    X = (_make_data(rng, n=600, d=6) + 50.0).astype(np.float32)
    w = rng.uniform(0.5, 2.0, 600).astype(np.float32)
    gone = rng.random(600) < 0.3
    w[gone] = 0.0
    X[gone] = 1e4  # what a zero weight hides
    masked = PCA(k=2).fit(DeviceDataset.from_host(X, weight=w, num_workers=2))
    subset = PCA(k=2).fit(DeviceDataset.from_host(X[~gone], weight=w[~gone], num_workers=2))
    np.testing.assert_allclose(masked.mean_, subset.mean_, rtol=1e-6)
    np.testing.assert_allclose(masked.components_, subset.components_, atol=2e-6)
    np.testing.assert_allclose(
        masked.explained_variance_, subset.explained_variance_, rtol=1e-5)


def test_auto_is_exact_on_resident_rows_whose_gram_is_cheap():
    from spark_rapids_ml_tpu.config import reset_config, set_config
    from spark_rapids_ml_tpu.ops.pca import resolve_pca_solver

    try:
        set_config(pca_solver="auto")
        # the reference's row on one chip and on four: 0.9e13 and 0.2e13 of 2^44
        assert resolve_pca_solver(3000, 3, resident_rows=1_000_000)[::3] == (
            "full", "auto:resident 1000000x3000^2<=2^44,d<=4096")
        assert resolve_pca_solver(3000, 3, resident_rows=250_000)[0] == "full"
        # rows whose Gram is seconds keep the old rule, as do rows of unknown residence
        assert resolve_pca_solver(3000, 3, resident_rows=4_000_000)[::3] == (
            "randomized", "auto:d>=208")
        assert resolve_pca_solver(3000, 3)[0] == "randomized"
        assert resolve_pca_solver(3000, 3, streamed=True)[0] == "randomized"
        set_config(pca_solver="randomized")
        assert resolve_pca_solver(3000, 3, resident_rows=1000)[::3] == ("randomized", "forced")
    finally:
        reset_config()


@pytest.mark.parametrize("rows,cols,solver", [
    (2_000, 50_000, "randomized"),    # rows x d^2 = 5e12 is cheap; a 10 GB Gram is not
    (40_000, 20_000, "randomized"),   # 1.6e13, and 1.6 GB on the device, 3.2 GB on the host
    (1_000, 4_097, "randomized"),     # the first width past the bound
    (1_000_000, 4_096, "full"),       # 1.68e13 at the bound's own width
    (1_100_000, 4_096, "randomized"),  # ... and past the Gram's budget there
    (8, 4_096, "full"),
])
def test_auto_bounds_the_width_of_an_exact_resident_fit(rows, cols, solver):
    """Nothing of the exact route's (d,d) matrices and d^3 eigensolve shrinks
    with the rows: wide resident rows, however few, keep the threshold and
    the range-finder they had."""
    from spark_rapids_ml_tpu.config import reset_config, set_config
    from spark_rapids_ml_tpu.ops.pca import resolve_pca_solver

    try:
        set_config(pca_solver="auto")
        got, _, _, reason = resolve_pca_solver(cols, 3, resident_rows=rows)
        assert got == solver
        assert reason == ("auto:d>=208" if solver == "randomized"
                          else f"auto:resident {rows}x{cols}^2<=2^44,d<=4096")
    finally:
        reset_config()


def test_moments_with_a_fractional_weight_sum_keep_their_divisor():
    """The streamed, fused and CSR routes finish through the resident fit's
    host eigensolve: the variances are still over (sum of weights - 1), also
    where that is under 1, and the ratios over the covariance's trace."""
    rng = np.random.default_rng(5)
    X = rng.normal(size=(12, 5)) * np.array([3.0, 2.0, 1.0, 0.5, 0.1]) + 7.0
    w = rng.uniform(0.5, 1.5, 12)
    w *= 1.5 / w.sum()
    st = {"S": (X * w[:, None]).T @ X, "s1": w @ X, "sw": w.sum()}
    attrs = PCA(k=2)._attrs_from_moments(st, np.float64)

    mean = st["s1"] / 1.5
    cov = (st["S"] - 1.5 * np.outer(mean, mean)) / 0.5
    evals = np.linalg.eigvalsh(cov)[::-1]
    np.testing.assert_allclose(attrs["mean_"], mean, rtol=1e-12)
    np.testing.assert_allclose(attrs["explained_variance_"], evals[:2], rtol=1e-10)
    np.testing.assert_allclose(
        attrs["explained_variance_ratio_"], evals[:2] / np.trace(cov), rtol=1e-10)
    np.testing.assert_allclose(
        attrs["singular_values_"], np.sqrt(evals[:2] * 0.5), rtol=1e-10)
