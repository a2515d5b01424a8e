#
# Fused stage-and-solve engine (fused.py), randomized PCA solver
# (ops/pca.py), and compensated-bf16 statistics accumulation
# (ops/precision.py "high_compensated") — ISSUE 8.
#
import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu.config import reset_config, set_config
from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.regression import LinearRegression


@pytest.fixture(autouse=True)
def _reset_conf():
    yield
    reset_config()


def _structured(rng, n=6000, d=24, rank=4, noise=0.05):
    """Decaying-spectrum data: top components well separated, so two
    solvers can be compared component-by-component."""
    B = rng.normal(size=(n, rank)).astype(np.float32) * (
        1.5 ** -np.arange(rank, dtype=np.float32)
    )
    return (
        B @ rng.normal(size=(rank, d)).astype(np.float32)
        + noise * rng.normal(size=(n, d)).astype(np.float32)
    )


def _assert_pca_parity(m_a, m_b, ev_rtol=1e-3, dot_min=0.999):
    np.testing.assert_allclose(m_a.mean_, m_b.mean_, atol=1e-4)
    np.testing.assert_allclose(
        m_a.explained_variance_, m_b.explained_variance_, rtol=ev_rtol
    )
    for i in range(m_a.components_.shape[0]):
        dot = abs(float(np.dot(m_a.components_[i], m_b.components_[i])))
        assert dot >= dot_min, (i, dot)


# ---------------------------------------------------------------------------
# fused vs two-phase parity
# ---------------------------------------------------------------------------


def test_fused_pca_matches_two_phase(rng):
    X = _structured(rng)
    set_config(fused_stage_solve="off", pca_solver="full")
    m_ref = PCA(k=3).setInputCol("features").fit(X)
    set_config(fused_stage_solve="on")
    assert "fused" not in m_ref.fit_report()
    m_fused = PCA(k=3).setInputCol("features").fit(X)
    # the fit report carries the fused section (overlap + solver keys)
    rep = m_fused.fit_report()
    assert rep and "fused" in rep, "fused path did not run"
    assert rep["fused"]["kind"] == "pca_moments"
    assert rep["fused"]["chunks"] >= 2
    _assert_pca_parity(m_fused, m_ref)
    assert "overlap_fraction" in rep["fused"]


def test_fused_linreg_matches_two_phase(rng):
    n, d = 6000, 12
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=d).astype(np.float32)
    y = X @ w_true + 0.1 * rng.normal(size=n).astype(np.float32)
    weights = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    df = pd.DataFrame(
        {"features": list(X), "label": y, "w": weights}
    )
    kw = dict(regParam=0.0, elasticNetParam=0.0)
    set_config(fused_stage_solve="off")
    m_ref = LinearRegression(**kw).setWeightCol("w").fit(df)
    set_config(fused_stage_solve="on")
    m_fused = LinearRegression(**kw).setWeightCol("w").fit(df)
    assert m_fused.fit_report()["fused"]["kind"] == "linreg"
    np.testing.assert_allclose(
        np.asarray(m_fused.coefficients), np.asarray(m_ref.coefficients),
        atol=1e-4,
    )
    assert m_fused.intercept == pytest.approx(m_ref.intercept, abs=1e-4)
    assert m_fused.r2_ == pytest.approx(m_ref.r2_, abs=1e-3)


def test_fused_parquet_matches_two_phase(tmp_path, rng):
    n, d = 5000, 8
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=d).astype(np.float32)
    y = X @ w_true + 0.1 * rng.normal(size=n).astype(np.float32)
    path = str(tmp_path / "fused.parquet")
    pd.DataFrame(
        {"features": list(X), "label": y.astype(np.float64)}
    ).to_parquet(path)
    kw = dict(regParam=0.0, elasticNetParam=0.0)
    set_config(fused_stage_solve="off")
    m_ref = LinearRegression(**kw).fit(path)
    m_pca_ref = PCA(k=2).setInputCol("features").fit(path)
    set_config(fused_stage_solve="on")
    m_fused = LinearRegression(**kw).fit(path)
    m_pca = PCA(k=2).setInputCol("features").fit(path)
    np.testing.assert_allclose(
        np.asarray(m_fused.coefficients), np.asarray(m_ref.coefficients),
        atol=1e-4,
    )
    _assert_pca_parity(m_pca, m_pca_ref)


def test_parallel_readers_cover_every_row_once(tmp_path, rng):
    """readers=2 splits the file's row groups between threads; the
    accumulated statistics must cover every row exactly once (sums are
    order-invariant, so parity against readers=1 is the whole
    contract)."""
    n, d = 6000, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    path = str(tmp_path / "multi_rg.parquet")
    import pyarrow as pa
    import pyarrow.parquet as pq

    t = pa.table(
        {
            "features": pa.FixedSizeListArray.from_arrays(
                pa.array(X.reshape(-1).astype(np.float64)), d
            )
        }
    )
    pq.write_table(t, path, row_group_size=1000)
    # chunk cache off: a cached replay of the readers=1 stream would
    # serve the readers=2 fit from memory and never run the reader pool
    set_config(fused_stage_solve="on", fused_parquet_readers=1,
               chunk_cache="off")
    m1 = PCA(k=2).setInputCol("features").fit(path)
    set_config(fused_parquet_readers=2)
    m2 = PCA(k=2).setInputCol("features").fit(path)
    _assert_pca_parity(m2, m1, ev_rtol=1e-4)
    # singular values encode sum-of-weights: double counting would shift
    # them far beyond f32 order noise
    np.testing.assert_allclose(
        m2.singular_values_, m1.singular_values_, rtol=1e-4
    )


# ---------------------------------------------------------------------------
# randomized solver
# ---------------------------------------------------------------------------


def test_randomized_vs_full_parity_across_settings(rng):
    X = _structured(rng, n=4000, d=256, rank=4, noise=0.02)
    models = {}
    for solver in ("full", "randomized", "auto"):
        set_config(pca_solver=solver, fused_stage_solve="off")
        models[solver] = PCA(k=3).setInputCol("features").fit(X)
    # auto at d=256, k=3, l=13, p=2: the threshold 4*13*4=208 <= 256 would
    # sketch, but resident rows whose Gram is a fraction of a second get the
    # exact answer (ops/pca.py `_EXACT_RESIDENT_GRAM`); the streamed paths
    # keep the threshold (test_pca.py's
    # test_auto_is_exact_on_resident_rows_whose_gram_is_cheap)
    decision = models["auto"].fit_report()["solver_decision"]
    assert decision["solver"] == "full"
    assert decision["reason"].startswith("auto:resident")
    assert models["randomized"].fit_report()["solver_decision"]["solver"] == "randomized"
    _assert_pca_parity(models["randomized"], models["full"], ev_rtol=0.01)
    _assert_pca_parity(models["auto"], models["full"], ev_rtol=0.01)
    # ratios stay exact: total variance comes from the true trace, not
    # the sketch
    np.testing.assert_allclose(
        models["randomized"].explained_variance_ratio_,
        models["full"].explained_variance_ratio_,
        rtol=0.01,
    )


def test_randomized_zero_weight_rows_contract(rng):
    """SUPPORTS_ZERO_WEIGHT_ROWS: a w=0 row (device-cache fold mask) is
    mathematically absent from the randomized solver too."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.pca import pca_fit_randomized

    n, d, k = 2000, 64, 2
    X = _structured(rng, n=n, d=d, rank=3, noise=0.02)
    keep = rng.random(n) > 0.3
    w = keep.astype(np.float32)
    out_masked = pca_fit_randomized(
        jnp.asarray(X), jnp.asarray(w), k, 12, 2
    )
    Xs = np.ascontiguousarray(X[keep])
    out_subset = pca_fit_randomized(
        jnp.asarray(Xs), jnp.asarray(np.ones(Xs.shape[0], np.float32)),
        k, 12, 2,
    )
    np.testing.assert_allclose(
        np.asarray(out_masked[0]), np.asarray(out_subset[0]), atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(out_masked[2]), np.asarray(out_subset[2]), rtol=1e-3
    )
    for i in range(k):
        dot = abs(float(np.dot(
            np.asarray(out_masked[1])[i], np.asarray(out_subset[1])[i]
        )))
        assert dot >= 0.999


def test_fused_randomized_stage_overlapped(rng):
    """pca_solver=randomized composes with the fused engine: the
    range-finder's passes re-stream the source and the result matches
    the resident randomized solver."""
    X = _structured(rng, n=5000, d=192, rank=4, noise=0.02)
    set_config(pca_solver="randomized", fused_stage_solve="off")
    m_res = PCA(k=3).setInputCol("features").fit(X)
    set_config(fused_stage_solve="on")
    m_fused = PCA(k=3).setInputCol("features").fit(X)
    fused = m_fused.fit_report()["fused"]
    assert fused["kind"] == "pca_projected"
    assert fused["solver"] == "randomized"
    # 2 + power_iters passes over the source
    assert fused["passes"] == 4
    _assert_pca_parity(m_fused, m_res, ev_rtol=0.01)


def test_resolve_pca_solver_rules():
    from spark_rapids_ml_tpu.ops.pca import resolve_pca_solver

    set_config(pca_solver="auto")
    # small d: full (l=13, threshold 208)
    assert resolve_pca_solver(64, 3)[0] == "full"
    assert resolve_pca_solver(3000, 3)[0] == "randomized"
    # streamed passes re-read the source: 4x stricter threshold
    assert resolve_pca_solver(300, 3, streamed=True)[0] == "full"
    assert resolve_pca_solver(3000, 3, streamed=True)[0] == "randomized"
    set_config(pca_solver="full")
    assert resolve_pca_solver(3000, 3)[0] == "full"
    set_config(pca_solver="randomized")
    assert resolve_pca_solver(8, 3)[0] == "randomized"
    set_config(pca_solver="bogus")
    with pytest.raises(ValueError, match="pca_solver"):
        resolve_pca_solver(64, 3)


# ---------------------------------------------------------------------------
# compensated bf16 accumulation
# ---------------------------------------------------------------------------


def test_compensated_accumulation_bounds_chunk_drift():
    """Adversarial chunk sequence: one huge-magnitude chunk followed by
    many small ones.  Plain f32 chunk accumulation swallows the small
    contributions (they fall below the running sum's ulp); the Kahan
    carry of `stats_precision="high_compensated"` preserves them.  On
    CPU every matmul is f32-exact, so the difference isolated here is
    exactly the chunk-level summation error the level exists to bound."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.stats import acc_to_host_f64, pca_moment_acc

    d = 4
    rng = np.random.default_rng(0)
    # the big chunk pushes the running sum to ~2.5e8 per Gram entry (its
    # own f32 representation error is only ~15 — the floor Kahan cannot
    # beat), and each small chunk contributes ~16: right at the running
    # sum's ulp, so PLAIN f32 accumulation loses a large share of all
    # 256 of them (~4e3 total drift) while the carry preserves them
    big = (2e3 * rng.standard_normal((64, d))).astype(np.float32)
    smalls = [
        (0.5 * rng.standard_normal((64, d))).astype(np.float32)
        for _ in range(256)
    ]
    w = np.ones((64,), np.float32)

    def run(level):
        set_config(stats_precision=level)
        acc, step = pca_moment_acc(d, np.float32)
        step_j = jax.jit(step, donate_argnums=0)
        acc = step_j(acc, jnp.asarray(big), jnp.asarray(w))
        for c in smalls:
            acc = step_j(acc, jnp.asarray(c), jnp.asarray(w))
        return acc_to_host_f64(acc)["S"]

    plain = run("high")
    comp = run("high_compensated")
    # exact f64 reference
    ref = np.zeros((d, d))
    for c in [big] + smalls:
        c64 = np.asarray(c, np.float64)
        ref += c64.T @ c64
    err_plain = np.abs(plain - ref).max()
    err_comp = np.abs(comp - ref).max()
    # plain accumulation must visibly drift (chunk-count-dependent);
    # the compensated level stays at the single-chunk f32 floor
    assert err_comp < err_plain / 10, (err_plain, err_comp)
    assert err_comp <= 64.0, err_comp


def test_high_compensated_end_to_end_matches_exact(rng):
    """On CPU (all-f32-exact matmuls) the compensated level must agree
    with `highest` — the knob changes accumulation structure, never
    semantics (mirror of the stats-precision invariance test)."""
    X = _structured(rng, n=4000, d=16)
    set_config(stats_precision="highest", fused_stage_solve="on")
    m_ref = PCA(k=3).setInputCol("features").fit(X)
    set_config(stats_precision="high_compensated")
    m_comp = PCA(k=3).setInputCol("features").fit(X)
    _assert_pca_parity(m_comp, m_ref)


def test_stats_precision_rejects_unknown_level():
    from spark_rapids_ml_tpu.ops.precision import (
        stats_compensated,
        stats_precision,
    )

    set_config(stats_precision="high_compensated")
    assert stats_compensated()
    import jax

    assert stats_precision() == jax.lax.Precision.HIGH
    set_config(stats_precision="high")
    assert not stats_compensated()


# ---------------------------------------------------------------------------
# routing / eligibility + resilience
# ---------------------------------------------------------------------------


def test_fused_eligibility_gates(rng):
    X = _structured(rng, n=3000, d=8)
    def fused_by(X):
        return "fused" in PCA(k=2).setInputCol("features").fit(X).fit_report()

    set_config(fused_stage_solve="off")
    assert not fused_by(X), "off must not fuse"
    # auto below the byte floor keeps the two-phase path
    set_config(fused_stage_solve="auto")
    assert not fused_by(X)
    # sparse batches keep the two-phase/CSR paths
    import scipy.sparse as sp

    set_config(fused_stage_solve="on")
    Xs = sp.random(2000, 8, density=0.2, format="csr", dtype=np.float32,
                   random_state=0)
    assert not fused_by(Xs)
    # dense + on engages
    assert fused_by(X)
    set_config(fused_stage_solve="bogus")
    from spark_rapids_ml_tpu.fused import fused_mode

    with pytest.raises(ValueError, match="fused_stage_solve"):
        fused_mode()


def test_fused_fault_restarts_pass_without_double_count(rng):
    """An injected OOM mid-accumulation (the `fused_accumulate` site)
    must RESTART the pass with fresh accumulators — never resume
    half-summed state.  Parity with the clean fused fit proves no chunk
    was double-counted (a duplicated chunk would shift the weight sum
    and every statistic)."""
    from spark_rapids_ml_tpu.resilience import fault_inject
    from spark_rapids_ml_tpu.telemetry import REGISTRY

    X = _structured(rng)
    set_config(
        fused_stage_solve="on", retry_backoff_s=0.01, retry_jitter=0.0
    )
    m_clean = PCA(k=3).setInputCol("features").fit(X)
    chunks_clean = m_clean.fit_report()["fused"]["chunks"]
    retries = REGISTRY.get("retries_total")
    before = retries.value(default=0, label="fused_fit", action="oom")
    with fault_inject("fused_accumulate", "oom", times=1, skip=2):
        m_faulted = PCA(k=3).setInputCol("features").fit(X)
    assert (
        retries.value(default=0, label="fused_fit", action="oom")
        == before + 1
    )
    # the retried pass re-ran from chunk 0: same chunk count, identical
    # statistics
    assert m_faulted.fit_report()["fused"]["chunks"] == chunks_clean
    _assert_pca_parity(m_faulted, m_clean, ev_rtol=1e-6, dot_min=0.99999)
    np.testing.assert_allclose(
        m_faulted.singular_values_, m_clean.singular_values_, rtol=1e-6
    )


def test_fused_device_loss_recovers_elastically(rng):
    """A device_lost fault mid-accumulation routes through the elastic
    recovery: the retried pass lands on the shrunken mesh and completes
    with the same statistics."""
    import jax

    if jax.device_count() < 2:
        pytest.skip("needs a multi-device mesh")
    from spark_rapids_ml_tpu.parallel.mesh import active_devices
    from spark_rapids_ml_tpu.resilience import fault_inject
    from spark_rapids_ml_tpu.resilience.elastic import reset_elastic

    X = _structured(rng)
    set_config(
        fused_stage_solve="on", retry_backoff_s=0.01, retry_jitter=0.0
    )
    m_clean = PCA(k=3).setInputCol("features").fit(X)
    n_dev0 = len(active_devices())
    try:
        with fault_inject("fused_accumulate", "device_lost", times=1, skip=1):
            m_rec = PCA(k=3).setInputCol("features").fit(X)
        assert len(active_devices()) == n_dev0 - 1
        _assert_pca_parity(m_rec, m_clean, ev_rtol=1e-5, dot_min=0.9999)
    finally:
        reset_elastic()
