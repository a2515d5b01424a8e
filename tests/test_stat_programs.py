#
# Statistic-program engine (stats/) — ISSUE 13: program-vs-reference
# parity on exact and compensated precision, sketch merge-associativity
# across chunkings, fused multi-statistic single-pass composition,
# restart-not-double-count resilience, and the migrated PCA/linreg/
# k-means|| specs bit-comparable to their pre-migration owners.
#
import importlib
import sys

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu.config import reset_config, set_config
from spark_rapids_ml_tpu.stats import (
    STAT_PROGRAMS,
    Summarizer,
    describe,
    get_program,
    iter_chunk_accs,
    merge_accs,
    register_program,
    run_program,
    run_programs,
    summarize,
)
from spark_rapids_ml_tpu.tracing import last_fact


@pytest.fixture(autouse=True)
def _reset_conf():
    yield
    reset_config()


def _chunk_accs(name, X, w=None, y=None, splits=1, dtype=np.float32,
                opts=None):
    """Fold X through one program in `splits` equal chunks, returning
    the host accumulator (device programs come back f64-folded)."""
    n = X.shape[0]
    bounds = np.linspace(0, n, splits + 1).astype(int)
    chunks = []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        cw = None if w is None else w[lo:hi]
        cy = None if y is None else np.asarray(y[lo:hi], np.float64)
        chunks.append((X[lo:hi], cy, cw, hi - lo))
    return iter_chunk_accs(
        name, chunks, X.shape[1], dtype=dtype, opts=opts
    )


# ---------------------------------------------------------------------------
# program vs numpy/scipy references (exact + compensated precision)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("precision", ["highest", "high_compensated"])
def test_moments_vs_numpy(rng, precision):
    set_config(stats_precision=precision)
    n, d = 3000, 8
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, 2] = np.round(X[:, 2])  # some exact zeros for nnz
    res = run_program("moments", X)
    assert res["count"] == n
    np.testing.assert_allclose(res["mean"], X.mean(0), atol=1e-5)
    np.testing.assert_allclose(
        res["variance"], X.var(0, ddof=1), rtol=1e-4
    )
    np.testing.assert_allclose(res["std"], X.std(0, ddof=1), rtol=1e-4)
    np.testing.assert_array_equal(res["min"], X.min(0))
    np.testing.assert_array_equal(res["max"], X.max(0))
    np.testing.assert_allclose(
        res["norm_l1"], np.abs(X).sum(0), rtol=1e-5
    )
    np.testing.assert_allclose(
        res["norm_l2"], np.linalg.norm(X, axis=0), rtol=1e-5
    )
    np.testing.assert_array_equal(
        res["num_nonzeros"], (X != 0).sum(0)
    )


def test_weighted_moments_vs_numpy(rng):
    n, d = 2500, 5
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    df = pd.DataFrame({"features": list(X), "w": w.astype(np.float64)})
    res = run_program("moments", df, weight_col="w")
    sw = w.sum()
    mean = (X * w[:, None]).sum(0) / sw
    var = ((X - mean) ** 2 * w[:, None]).sum(0) / (sw - 1.0)
    np.testing.assert_allclose(res["weight_sum"], sw, rtol=1e-5)
    np.testing.assert_allclose(res["mean"], mean, atol=1e-5)
    np.testing.assert_allclose(res["variance"], var, rtol=1e-3)


@pytest.mark.parametrize("precision", ["highest", "high_compensated"])
def test_covariance_correlation_vs_numpy(rng, precision):
    set_config(stats_precision=precision)
    n, d = 3000, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, 1] = 0.7 * X[:, 0] + 0.3 * X[:, 1]
    res = run_program("covariance", X)
    np.testing.assert_allclose(
        res["covariance"], np.cov(X.T.astype(np.float64)), atol=2e-3
    )
    np.testing.assert_allclose(
        res["correlation"], np.corrcoef(X.T.astype(np.float64)),
        atol=2e-3,
    )


def test_standardization_matches_weighted_moments(rng):
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.stats import weighted_moments

    n, d = 2000, 4
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, 3] = 1.0  # zero-variance column -> std 1.0 contract
    w = np.ones((n,), np.float32)
    res = run_program("standardization", X)
    mean, std, wsum = weighted_moments(jnp.asarray(X), jnp.asarray(w))
    np.testing.assert_allclose(res["mean"], np.asarray(mean), atol=1e-5)
    np.testing.assert_allclose(res["std"], np.asarray(std), rtol=1e-4)
    assert res["std"][3] == pytest.approx(1.0)


def test_ttest_vs_scipy(rng):
    from scipy import stats as sps

    n, d = 2500, 3
    y = (rng.random(n) > 0.4).astype(np.float64)
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, 0] += 0.3 * y.astype(np.float32)  # real group difference
    res = run_programs(["ttest"], (X, y))["ttest"]
    for j in range(d):
        t_ref, p_ref = sps.ttest_ind(
            X[y == 0, j].astype(np.float64),
            X[y == 1, j].astype(np.float64),
            equal_var=False,
        )
        assert res["t"][j] == pytest.approx(t_ref, rel=1e-3)
        assert res["p_value"][j] == pytest.approx(p_ref, rel=1e-2, abs=1e-9)
    assert res["p_value"][0] < 0.01  # the shifted column is detected


def test_chi2_vs_scipy(rng):
    from scipy.stats import chi2_contingency

    n, d = 3000, 2
    y = rng.integers(0, 3, size=n).astype(np.float64)
    X = np.empty((n, d), np.float32)
    X[:, 0] = rng.integers(0, 4, size=n)  # independent of y
    X[:, 1] = np.clip(y + (rng.random(n) > 0.7), 0, 3)  # dependent
    res = run_programs(["chi2"], (X, y))["chi2"]
    for j in range(d):
        O = np.zeros((4, 3))
        for xi, yi in zip(X[:, j].astype(int), y.astype(int)):
            O[xi, yi] += 1
        O = O[O.sum(axis=1) > 0][:, O.sum(axis=0) > 0]
        stat_ref, p_ref, dof_ref, _ = chi2_contingency(O, correction=False)
        assert res["statistic"][j] == pytest.approx(stat_ref, rel=1e-4)
        assert res["dof"][j] == dof_ref
        assert res["p_value"][j] == pytest.approx(p_ref, rel=1e-3, abs=1e-12)
    assert res["p_value"][1] < 1e-6 < res["p_value"][0]


# ---------------------------------------------------------------------------
# sketches: accuracy + merge-associativity across 1/4/8-way chunk splits
# ---------------------------------------------------------------------------


def test_quantile_sketch_accuracy_across_chunkings(rng):
    n, d = 12000, 3
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, 1] = rng.exponential(size=n)  # a skewed column too
    from spark_rapids_ml_tpu.stats.sketches import quantile_query

    sorted_X = np.sort(X.astype(np.float64), axis=0)
    for splits in (1, 4, 8):
        acc = _chunk_accs("quantile_sketch", X, splits=splits)
        est = quantile_query(acc, [0.1, 0.5, 0.9])
        for i, q in enumerate((0.1, 0.5, 0.9)):
            for j in range(d):
                # rank-space tolerance: the estimate must sit within 2%
                # of the true rank (k=256 guarantees ~0.8%)
                rank = np.searchsorted(sorted_X[:, j], est[j, i]) / n
                assert abs(rank - q) < 0.02, (splits, q, j, rank)


def test_quantile_sketch_merge_matches_stream(rng):
    """Merging 4 quarter-states must answer like one streamed state:
    same level geometry, rank error within the same bound."""
    n, d = 8000, 2
    X = rng.normal(size=(n, d)).astype(np.float32)
    parts = [
        _chunk_accs("quantile_sketch", X[i * 2000:(i + 1) * 2000])
        for i in range(4)
    ]
    p = get_program("quantile_sketch")
    merged = parts[0]
    for part in parts[1:]:
        merged = merge_accs(p, merged, part)
    assert int(merged["n"]) == n
    from spark_rapids_ml_tpu.stats.sketches import quantile_query

    est = quantile_query(merged, [0.5])
    sorted_X = np.sort(X.astype(np.float64), axis=0)
    for j in range(d):
        rank = np.searchsorted(sorted_X[:, j], est[j, 0]) / n
        assert abs(rank - 0.5) < 0.02


def test_frequent_items_heavy_hitters_across_chunkings(rng):
    n = 8000
    # zipf-ish: value v appears ~ n/2^v times
    vals = rng.geometric(0.5, size=n).astype(np.float64)
    X = vals.reshape(-1, 1).astype(np.float32)
    true_counts = {
        v: int((vals == v).sum()) for v in np.unique(vals)
    }
    cap = 64
    for splits in (1, 4, 8):
        acc = _chunk_accs(
            "frequent_items", X, splits=splits, opts={"cap": cap}
        )
        res = get_program("frequent_items").finalize(acc, {})
        found = dict(res["items"][0])
        err = int(res["error_bound"][0])
        assert err <= n // cap
        for v, c in true_counts.items():
            if c > n // cap:  # guaranteed-present heavy hitters
                assert v in found
                assert found[v] <= c <= found[v] + err


def test_distinct_count_merge_is_exact(rng):
    """HLL registers merge by max: ANY chunking folds to byte-identical
    registers, so the estimates are exactly equal across 1/4/8-way
    splits — and within the 2% design error of the truth."""
    n, d = 8000, 2
    X = np.empty((n, d), np.float32)
    X[:, 0] = rng.integers(0, 500, size=n)  # 500 distinct
    X[:, 1] = rng.normal(size=n)  # ~n distinct
    accs = {
        s: _chunk_accs("distinct_count", X, splits=s) for s in (1, 4, 8)
    }
    np.testing.assert_array_equal(accs[1]["regs"], accs[4]["regs"])
    np.testing.assert_array_equal(accs[1]["regs"], accs[8]["regs"])
    assert accs[4]["regs"].dtype == np.int64  # dtype-preserving fold
    p = get_program("distinct_count")
    merged = merge_accs(
        p,
        _chunk_accs("distinct_count", X[: n // 2]),
        _chunk_accs("distinct_count", X[n // 2:]),
    )
    np.testing.assert_array_equal(merged["regs"], accs[1]["regs"])
    est = p.finalize(accs[1], {})["distinct"]
    assert abs(est[0] - 500) / 500 < 0.06
    true1 = len(np.unique(X[:, 1]))
    assert abs(est[1] - true1) / true1 < 0.06


def test_moments_merge_across_splits(rng):
    X = rng.normal(size=(4000, 4)).astype(np.float32)
    p = get_program("moments")
    full = _chunk_accs("moments", X, splits=1)
    parts = [
        _chunk_accs("moments", X[lo:hi])
        for lo, hi in ((0, 1500), (1500, 3000), (3000, 6000))
    ]
    merged = parts[0]
    for part in parts[1:]:
        merged = merge_accs(p, merged, part)
    np.testing.assert_array_equal(merged["min"], full["min"])
    np.testing.assert_array_equal(merged["max"], full["max"])
    # f32 chunk sums re-associate across the split boundaries: value
    # parity up to reduction-order noise, never exactness
    np.testing.assert_allclose(
        merged["s1"], full["s1"], rtol=1e-5, atol=1e-4
    )
    np.testing.assert_allclose(merged["sw"], full["sw"], rtol=1e-6)


# ---------------------------------------------------------------------------
# fused composition: many statistics, ONE pass, no full staging
# ---------------------------------------------------------------------------


def test_summarize_six_plus_statistics_single_pass(rng):
    from spark_rapids_ml_tpu.parallel.mesh import STAGE_COUNTS

    n, d = 6000, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    stagings0 = STAGE_COUNTS["dataset_stagings"]
    s = summarize(
        X,
        metrics=["count", "mean", "variance", "min", "max", "normL2",
                 "quantiles", "frequentItems", "distinctCount",
                 "correlation"],
    )
    # >= 6 distinct statistics computed...
    assert len(s) == 10
    # ...in ONE fused chunked pass: no full dataset staging ran
    # (STAGE_COUNTS tracks every 2-D host->device staging), and the
    # engine reports exactly one multi-chunk pass
    assert STAGE_COUNTS["dataset_stagings"] == stagings0
    stats = last_fact("stats")
    assert stats["passes"] == 1
    assert stats["chunks"] >= 2
    assert stats["programs"] >= 5
    # spot-check the statistics came out right
    assert s["count"] == n
    np.testing.assert_allclose(s["mean"], X.mean(0), atol=1e-5)
    np.testing.assert_array_equal(s["min"], X.min(0))
    np.testing.assert_allclose(
        s["correlation"], np.corrcoef(X.T.astype(np.float64)), atol=2e-3
    )
    assert set(s["quantiles"]) == {0.25, 0.5, 0.75}


def test_summarize_parquet_single_pass(tmp_path, rng):
    n, d = 6000, 4
    X = rng.normal(size=(n, d)).astype(np.float32)
    path = str(tmp_path / "summ.parquet")
    pd.DataFrame({"features": list(X.astype(np.float64))}).to_parquet(path)
    s = summarize(
        path,
        metrics=["count", "mean", "variance", "min", "max", "median"],
    )
    assert s["count"] == n
    np.testing.assert_allclose(s["mean"], X.mean(0), atol=1e-4)
    np.testing.assert_allclose(
        s["variance"], X.var(0, ddof=1), rtol=1e-3
    )
    np.testing.assert_allclose(s["min"], X.min(0), atol=1e-6)
    # the pass left its own record on this thread
    assert last_fact("stats")["label"] == "summarize"
    assert last_fact("stats")["chunks"] >= 1


def test_describe_matches_pandas(rng):
    X = rng.normal(size=(5000, 3)).astype(np.float32)
    table = describe(X)
    ref = pd.DataFrame(X, columns=["x0", "x1", "x2"]).describe()
    np.testing.assert_allclose(
        table.loc["mean"], ref.loc["mean"], atol=1e-4
    )
    np.testing.assert_allclose(table.loc["std"], ref.loc["std"], rtol=1e-3)
    np.testing.assert_allclose(table.loc["min"], ref.loc["min"])
    np.testing.assert_allclose(table.loc["max"], ref.loc["max"])
    # quantile rows within sketch resolution
    np.testing.assert_allclose(
        table.loc["50%"], ref.loc["50%"], atol=0.1
    )
    assert Summarizer.metrics("mean").summary(X)["mean"].shape == (3,)


def test_summarize_unknown_metric_rejected(rng):
    with pytest.raises(ValueError, match="unknown summarizer metrics"):
        summarize(np.ones((10, 2), np.float32), metrics=["bogus"])
    with pytest.raises(KeyError, match="unknown statistic program"):
        run_program("not_registered", np.ones((10, 2), np.float32))


# ---------------------------------------------------------------------------
# resilience: restart-not-double-count + stale-gauge end-marking
# ---------------------------------------------------------------------------


def test_fault_restarts_pass_without_double_count(rng):
    from spark_rapids_ml_tpu.resilience import fault_inject
    from spark_rapids_ml_tpu.telemetry import REGISTRY

    X = rng.normal(size=(5000, 5)).astype(np.float32)
    set_config(retry_backoff_s=0.01, retry_jitter=0.0)
    clean = summarize(
        X, metrics=["count", "mean", "sum", "min", "max", "distinctCount"]
    )
    retries = REGISTRY.get("retries_total")
    before = retries.value(default=0, label="stat_programs", action="oom")
    with fault_inject("stat_program_step", "oom", times=1, skip=2):
        faulted = summarize(
            X,
            metrics=["count", "mean", "sum", "min", "max",
                     "distinctCount"],
        )
    assert (
        retries.value(default=0, label="stat_programs", action="oom")
        == before + 1
    )
    # the retried pass re-ran from chunk 0 with fresh accumulators:
    # bit-identical statistics (a double-counted chunk would shift the
    # count and every sum)
    assert faulted["count"] == clean["count"]
    np.testing.assert_array_equal(faulted["sum"], clean["sum"])
    np.testing.assert_array_equal(faulted["min"], clean["min"])
    np.testing.assert_array_equal(
        faulted["distinctCount"], clean["distinctCount"]
    )


def test_device_loss_recovers_elastically(rng):
    import jax

    if jax.device_count() < 2:
        pytest.skip("needs a multi-device mesh")
    from spark_rapids_ml_tpu.parallel.mesh import active_devices
    from spark_rapids_ml_tpu.resilience import fault_inject
    from spark_rapids_ml_tpu.resilience.elastic import reset_elastic

    X = rng.normal(size=(5000, 5)).astype(np.float32)
    set_config(retry_backoff_s=0.01, retry_jitter=0.0)
    clean = summarize(X, metrics=["count", "mean", "min"])
    n_dev0 = len(active_devices())
    try:
        with fault_inject(
            "stat_program_step", "device_lost", times=1, skip=1
        ):
            rec = summarize(X, metrics=["count", "mean", "min"])
        assert len(active_devices()) == n_dev0 - 1
        assert rec["count"] == clean["count"]
        np.testing.assert_allclose(rec["mean"], clean["mean"], atol=1e-6)
        np.testing.assert_array_equal(rec["min"], clean["min"])
    finally:
        reset_elastic()


def test_describe_closes_heartbeat_gauges(rng):
    """Ad-hoc describe()/summarize() calls end-mark their solver gauges
    (Heartbeat.close): a scrape after the run shows NO live
    stat_programs series."""
    from spark_rapids_ml_tpu.telemetry import REGISTRY

    describe(rng.normal(size=(2000, 2)).astype(np.float32))
    sentinel = object()
    assert (
        REGISTRY.get("solver_iteration").value(
            default=sentinel, solver="stat_programs"
        )
        is sentinel
    )


def test_concurrent_describes_do_not_cross_contaminate(rng):
    """Satellite (ISSUE 14): two threads running describe()
    simultaneously must each get THEIR OWN correct summary and leave
    THEIR OWN pass's record on their thread, marked `concurrent_passes`
    — never the other's, nor a mix of both (the PR-5 concurrent-fits
    report guard, mirrored)."""
    import threading

    X1 = rng.normal(size=(48_000, 6)).astype(np.float32)
    X2 = rng.normal(size=(16_000, 3)).astype(np.float32) + 4.0
    ref1 = describe(X1)
    chunks1 = int(last_fact("stats")["chunks"])
    ref2 = describe(X2)
    chunks2 = int(last_fact("stats")["chunks"])
    results = {}
    snaps = {}
    errors = []
    barrier = threading.Barrier(2)

    def run(key, X):
        try:
            barrier.wait(timeout=30)
            results[key] = describe(X)
            snaps[key] = last_fact("stats")
        except Exception as e:  # pragma: no cover - diagnostic
            errors.append(e)

    threads = [
        threading.Thread(target=run, args=("a", X1)),
        threading.Thread(target=run, args=("b", X2)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    pd.testing.assert_frame_equal(results["a"], ref1)
    pd.testing.assert_frame_equal(results["b"], ref2)
    # each thread's record is its own pass's: the (bytes, chunks) pair of
    # its own input, not the other's
    for key, want in (("a", (X1.nbytes, chunks1)), ("b", (X2.nbytes, chunks2))):
        snap = snaps[key]
        assert snap["label"] == "summarize"
        assert snap["programs"] == 2  # moments + quantile_sketch
        assert (int(snap["bytes"]), int(snap["chunks"])) == want, snap
        # both passes overlapped: the record says so, and the report-side
        # consumers (FitTelemetry stats section) know the engine counters
        # around it are process-level
        assert snap.get("concurrent_passes") is True


def test_fit_report_carries_stats_section(rng):
    """A statistic pass run inside a fit lands as the report's `stats`
    section: its `stats` fact carries the fit's run id."""
    from spark_rapids_ml_tpu.telemetry.report import FitTelemetry

    ft = FitTelemetry("SummarizerRun")
    with ft.span():
        summarize(
            rng.normal(size=(4000, 3)).astype(np.float32),
            metrics=["mean", "min", "quantiles"],
        )
    rep = ft.build()
    assert rep and "stats" in rep
    assert rep["stats"]["passes"] == 1
    # mean+min share `moments`; quantiles adds the sketch -> 2 programs
    assert rep["stats"]["programs"] == 2
    assert rep["stats"]["chunks"] >= 1
    assert "overlap_fraction" in rep["stats"]


def test_stat_program_families_scrapeable(rng):
    from spark_rapids_ml_tpu.telemetry import REGISTRY
    from spark_rapids_ml_tpu.telemetry.exporters import dump_prometheus

    runs = REGISTRY.get("stat_program_runs_total")
    before = runs.value(default=0, program="moments")
    summarize(
        rng.normal(size=(2000, 2)).astype(np.float32), metrics=["mean"]
    )
    assert runs.value(default=0, program="moments") == before + 1
    text = dump_prometheus()
    assert "stat_program_runs_total" in text
    assert "stat_program_pass_seconds" in text


# ---------------------------------------------------------------------------
# migrated specs: registry == pre-migration owners, bit-for-bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["pca_moments", "linreg"])
def test_migrated_specs_byte_compare(rng, kind):
    """The registered program and the original ops/stats.py spec (the
    pre-migration owner fused.py/streaming.py called directly) must
    fold identical chunks to BYTE-identical accumulators."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.stats import (
        acc_to_host_f64,
        linreg_acc,
        pca_moment_acc,
    )

    n, d = 3000, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = np.ones((n,), np.float32)
    y = rng.normal(size=n).astype(np.float32)
    legacy_builder = pca_moment_acc if kind == "pca_moments" else linreg_acc
    acc_old, step_old = legacy_builder(d, np.float32)
    step_old = jax.jit(step_old, donate_argnums=0)
    p = get_program(kind)
    acc_new = p.init(d, np.float32, {})
    step_new, _ = p.make_step(d, np.float32, {})
    step_new = jax.jit(step_new, donate_argnums=0)
    for lo in range(0, n, 1000):
        cX = jnp.asarray(X[lo:lo + 1000])
        cw = jnp.asarray(w[lo:lo + 1000])
        args = (cX, cw) if kind == "pca_moments" else (
            cX, cw, jnp.asarray(y[lo:lo + 1000])
        )
        acc_old = step_old(acc_old, *args)
        acc_new = step_new(acc_new, *args)
    old = acc_to_host_f64(acc_old)
    new = acc_to_host_f64(acc_new)
    assert set(old) == set(new)
    for k in old:
        np.testing.assert_array_equal(old[k], new[k])


def test_kmeans_sample_program_byte_parity(rng):
    """The `kmeans_sample` program reproduces the pre-migration strided
    collection loop byte-for-byte, under ANY chunking, and its merge is
    slot-disjoint-exact."""
    from spark_rapids_ml_tpu.ops.kmeans import seed_sample_stride

    n, d = 3500, 4
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=n).astype(np.float64)
    stride = seed_sample_stride(n, 700)
    cap = (n - 1) // stride + 1
    opts = {"stride": stride, "cap": cap}
    ref_X = X[::stride]  # the pre-migration sample, byte-for-byte
    ref_w = w[::stride].astype(np.float32)  # engine weights are f32
    p = get_program("kmeans_sample")
    for splits in (1, 3, 8):
        acc = _chunk_accs(
            "kmeans_sample", X, w=w.astype(np.float64), splits=splits,
            opts=opts,
        )
        res = p.finalize(acc, {})
        assert res["count"] == cap
        np.testing.assert_array_equal(
            res["X"].astype(np.float32), ref_X
        )
        np.testing.assert_array_equal(
            res["w"].astype(np.float32), ref_w
        )
    # slot-disjoint merge: two half-range accs reassemble the sample
    a = iter_chunk_accs(
        "kmeans_sample", [(X[:2500], None, w[:2500], 2500)], d,
        opts=opts, offset0=0,
    )
    b = iter_chunk_accs(
        "kmeans_sample", [(X[2500:], None, w[2500:], 2500)], d,
        opts=opts, offset0=2500,
    )
    merged = p.finalize(merge_accs(p, a, b, opts), {})
    np.testing.assert_array_equal(merged["X"].astype(np.float32), ref_X)


def test_streaming_kmeans_parquet_unchanged(tmp_path, rng):
    """End-to-end: the migrated seeding sample leaves the epoch-
    streaming kmeans trajectory intact (clusters recovered on separated
    blobs)."""
    from spark_rapids_ml_tpu.streaming import kmeans_streaming_fit

    centers_true = np.array(
        [[0.0, 0.0], [8.0, 8.0], [-8.0, 8.0]], np.float32
    )
    n = 1200
    X = np.concatenate([
        c + rng.normal(scale=0.4, size=(n // 3, 2)).astype(np.float32)
        for c in centers_true
    ])
    rng.shuffle(X)
    path = str(tmp_path / "km.parquet")
    pd.DataFrame({"features": list(X.astype(np.float64))}).to_parquet(path)
    out = kmeans_streaming_fit(
        path, "features", (), None, k=3, seed=0, max_iter=8,
        init_rows=256,
    )
    got = np.asarray(out["centers"])
    for c in centers_true:
        assert np.min(np.linalg.norm(got - c, axis=1)) < 0.5


# ---------------------------------------------------------------------------
# contract plumbing: registration validation, int-preserving fold, shim
# ---------------------------------------------------------------------------


def test_program_declaration_verified_on_first_use():
    from spark_rapids_ml_tpu.stats.programs import Field, StatProgram

    def bad_shapes(d, opts):
        return {"s": Field((d, d))}

    def bad_init(d, dtype, opts):
        return {"s": np.zeros((d,), np.float32)}  # shape mismatch

    register_program(StatProgram(
        name="_bogus_shape", kind="host", shapes=bad_shapes,
        init=bad_init, make_step=lambda d, dt, o: None,
        finalize=lambda a, c: a,
    ))
    try:
        # registration is import-light; the probe-init verification
        # fires on first fetch
        with pytest.raises(ValueError, match="shape"):
            get_program("_bogus_shape")
    finally:
        STAT_PROGRAMS.pop("_bogus_shape", None)
    # duplicate registration is rejected
    moments = STAT_PROGRAMS["moments"]
    with pytest.raises(ValueError, match="already registered"):
        register_program(moments)


def test_package_import_does_not_init_backend():
    """Bare `import spark_rapids_ml_tpu` must leave the XLA backend
    uninitialized — `init_distributed()` is rejected once a backend
    exists (parallel/context.py), so program registration cannot build
    accelerator arrays at import."""
    import subprocess
    import sys as _sys

    out = subprocess.run(
        [_sys.executable, "-c",
         "import spark_rapids_ml_tpu\n"
         "from jax._src import xla_bridge as xb\n"
         "raise SystemExit(1 if xb._backends else 0)\n"],
        capture_output=True,
    )
    assert out.returncode == 0, out.stderr.decode()[-500:]


def test_conf_geometry_change_retraces(rng):
    """A `set_config` sketch-geometry change between runs must rebuild
    the compiled step (the resolved opts ride the cache key): register
    counts follow the new `summarizer_hll_bits`, no stale-shape
    scatter."""
    X = rng.normal(size=(2000, 2)).astype(np.float32)
    set_config(summarizer_hll_bits=8)
    a = _chunk_accs("distinct_count", X)
    assert a["regs"].shape == (2, 256)
    set_config(summarizer_hll_bits=10)
    b = _chunk_accs("distinct_count", X)
    assert b["regs"].shape == (2, 1024)
    p = get_program("distinct_count")
    est_a = p.finalize(a, {})["distinct"]
    est_b = p.finalize(b, {})["distinct"]
    true1 = len(np.unique(X[:, 1]))
    assert abs(est_a[1] - true1) / true1 < 0.15  # 8 bits: ~6.5% design err
    assert abs(est_b[1] - true1) / true1 < 0.08


def test_extra_args_programs_rejected_by_engine(rng):
    """`pca_projected` needs the range-finder's omega per pass: the
    generic engine refuses it with a typed error instead of crashing
    inside the combined jitted step."""
    with pytest.raises(ValueError, match="extra step arguments"):
        run_program(
            "pca_projected", rng.normal(size=(100, 4)).astype(np.float32)
        )


def test_frequent_items_ignores_nan(rng):
    """NaN doubles as the empty-slot sentinel: real NaN data is
    excluded from the table instead of minting never-matching entries
    that evict genuine frequent items."""
    n = 4000
    vals = rng.geometric(0.5, size=n).astype(np.float64)
    vals[rng.random(n) < 0.3] = np.nan
    X = vals.reshape(-1, 1).astype(np.float32)
    acc = _chunk_accs("frequent_items", X, splits=4, opts={"cap": 32})
    res = get_program("frequent_items").finalize(acc, {})
    found = dict(res["items"][0])
    assert not any(np.isnan(k) for k in found)
    live = vals[~np.isnan(vals)]
    top = 1.0  # the most frequent geometric value
    assert found[top] <= (live == top).sum() <= found[top] + int(
        res["error_bound"][0]
    )


def test_acc_to_host_preserves_integer_fields():
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.stats import acc_to_host_f64

    # a value above 2^53 would corrupt through a float64 round-trip
    big = 2 ** 60 + 1
    acc = {
        "regs": jnp.asarray(np.array([1, 7, 31], np.int32)),
        "sum": jnp.asarray(np.array([1.5, 2.5], np.float32)),
    }
    out = acc_to_host_f64(acc)
    assert out["regs"].dtype == np.int64
    np.testing.assert_array_equal(out["regs"], [1, 7, 31])
    assert out["sum"].dtype == np.float64
    host = acc_to_host_f64({"n": np.asarray(big, np.int64)})
    assert int(host["n"]) == big


def test_distance_shim_deprecated():
    """`ops/distance.py` survives as a deprecation shim over the
    consolidated `ops/distances.py` module."""
    sys.modules.pop("spark_rapids_ml_tpu.ops.distance", None)
    with pytest.warns(DeprecationWarning, match="ops.distances"):
        shim = importlib.import_module("spark_rapids_ml_tpu.ops.distance")
    from spark_rapids_ml_tpu.ops import distances

    assert shim.sqdist is distances.sqdist
    assert shim.sqdist_gathered is distances.sqdist_gathered


def test_program_registry_documented():
    """Every registered program appears in docs/statistics.md (the
    static half of this check is the graft-lint stat-program rule)."""
    import os

    doc = open(
        os.path.join(os.path.dirname(__file__), "..", "docs",
                     "statistics.md")
    ).read()
    for name in STAT_PROGRAMS:
        assert f"`{name}`" in doc, name
