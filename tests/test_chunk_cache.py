#
# Chunk-cache tests (parallel/device_cache.py ChunkCache + the
# streaming/fused consumers): spill/evict/re-serve byte parity across
# dtypes, layouts and codecs, checksum-verified restore with source
# fallback, restart-not-double-count under `chunk_cache_spill` fault
# injection, device-loss invalidation (spill survives), the parallel
# staging readers' byte parity, and DuHL-sampled convergence parity.
#
import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu.config import reset_config, set_config
from spark_rapids_ml_tpu.parallel.device_cache import (
    CHUNK_METRICS,
    clear_chunk_cache,
    clear_device_cache,
    get_chunk_cache,
    invalidate_for_devices,
)


@pytest.fixture(autouse=True)
def _clean():
    reset_config()
    clear_chunk_cache()
    clear_device_cache()
    yield
    clear_chunk_cache()
    clear_device_cache()
    reset_config()


def _write(tmp_path, X, y=None, w=None, name="d.parquet", **kw):
    df = pd.DataFrame({"features": list(np.asarray(X))})
    if y is not None:
        df["label"] = y
    if w is not None:
        df["w"] = w
    path = str(tmp_path / name)
    df.to_parquet(path, **kw)
    return path


def _scan(path, label_col=None, weight_col=None, chunk_rows=256,
          dtype=np.float32, features_cols=(), device_ok=False):
    from spark_rapids_ml_tpu.streaming import iter_chunks

    out = []
    for cX, cy, cw, n in iter_chunks(
        path, None if features_cols else "features", features_cols,
        label_col, weight_col, chunk_rows, np.dtype(dtype),
        device_ok=device_ok,
    ):
        out.append((
            np.asarray(cX).copy(),
            None if cy is None else np.asarray(cy).copy(),
            None if cw is None else np.asarray(cw).copy(),
            n,
        ))
    return out


def _assert_scans_equal(a, b):
    assert len(a) == len(b)
    for (x1, y1, w1, n1), (x2, y2, w2, n2) in zip(a, b):
        assert n1 == n2
        assert x1.dtype == x2.dtype and x1.shape == x2.shape
        np.testing.assert_array_equal(x1, x2)
        for u, v in ((y1, y2), (w1, w2)):
            assert (u is None) == (v is None)
            if u is not None:
                assert u.dtype == v.dtype
                np.testing.assert_array_equal(u, v)


# ---------------------------------------------------------------------------
# replay parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("with_cols", [False, True])
def test_replay_byte_parity_dtypes_and_layouts(tmp_path, rng, dtype, with_cols):
    n, d = 700, 5
    X = rng.normal(size=(n, d)).astype(dtype)
    y = rng.integers(0, 3, n).astype(np.float64)
    w = rng.uniform(0.5, 2.0, n)
    if with_cols:
        cols = [f"c{i}" for i in range(d)]
        df = pd.DataFrame({c: X[:, i] for i, c in enumerate(cols)})
        df["label"] = y
        df["w"] = w
        path = str(tmp_path / "cols.parquet")
        df.to_parquet(path)
        kw = dict(features_cols=tuple(cols))
    else:
        path = _write(tmp_path, X, y, w)
        kw = {}
    a = _scan(path, "label", "w", chunk_rows=128, dtype=dtype, **kw)
    misses = CHUNK_METRICS["misses"]
    b = _scan(path, "label", "w", chunk_rows=128, dtype=dtype, **kw)
    _assert_scans_equal(a, b)
    assert CHUNK_METRICS["misses"] == misses  # pass 2 never re-read
    assert CHUNK_METRICS["hits"] >= 1


@pytest.mark.parametrize("codec", ["none", "zlib"])
def test_spill_restore_byte_parity(tmp_path, rng, codec):
    # compressible data so zlib actually shrinks under the tight budget
    X = np.tile(np.arange(8, dtype=np.float32), (1500, 1))
    X[:, 0] = np.arange(1500, dtype=np.float32)
    path = _write(tmp_path, X)
    # budget far below the decoded working set: LRU chunks must spill
    set_config(chunk_cache_host_bytes=16_000, chunk_cache_codec=codec)
    a = _scan(path, chunk_rows=256)
    assert CHUNK_METRICS["spills"] >= 1
    b = _scan(path, chunk_rows=256)
    _assert_scans_equal(a, b)
    assert CHUNK_METRICS["checksum_failures"] == 0
    if codec == "zlib":
        # serving from spill decompresses without re-warming
        assert CHUNK_METRICS["restores"] >= 1


def test_eviction_falls_back_to_source(tmp_path, rng):
    X1 = rng.normal(size=(1200, 8)).astype(np.float32)
    X2 = rng.normal(size=(1200, 8)).astype(np.float32)
    p1 = _write(tmp_path, X1, name="a.parquet")
    p2 = _write(tmp_path, X2, name="b.parquet")
    # budget holds roughly ONE stream: scanning both alternately evicts
    set_config(chunk_cache_host_bytes=45_000, chunk_cache_codec="none")
    a1 = _scan(p1, chunk_rows=256)
    a2 = _scan(p2, chunk_rows=256)
    b1 = _scan(p1, chunk_rows=256)
    b2 = _scan(p2, chunk_rows=256)
    _assert_scans_equal(a1, b1)
    _assert_scans_equal(a2, b2)
    assert CHUNK_METRICS["evictions"] >= 1


def test_checksum_failure_falls_back_to_source(tmp_path, rng):
    X = np.tile(np.arange(16, dtype=np.float32), (2000, 1))
    path = _write(tmp_path, X)
    set_config(chunk_cache_host_bytes=16_000, chunk_cache_codec="zlib")
    a = _scan(path, chunk_rows=256)
    cache = get_chunk_cache()
    # corrupt one spilled blob in place
    poked = 0
    with cache._mu:
        for st in cache._streams.values():
            for c in st.chunks:
                for arr in c.arrays():
                    if arr.spill is not None and not poked:
                        blob = bytearray(arr.spill.blob)
                        blob[len(blob) // 2] ^= 0xFF
                        arr.spill.blob = bytes(blob)
                        poked += 1
    assert poked == 1
    b = _scan(path, chunk_rows=256)
    _assert_scans_equal(a, b)  # served correctly FROM THE SOURCE
    assert CHUNK_METRICS["checksum_failures"] >= 1


def test_path_rewrite_invalidates_stream(tmp_path, rng):
    X1 = rng.normal(size=(400, 4)).astype(np.float32)
    path = _write(tmp_path, X1)
    a = _scan(path, chunk_rows=128)
    X2 = rng.normal(size=(400, 4)).astype(np.float32)
    import os
    import time as _time

    _time.sleep(0.01)
    _write(tmp_path, X2)
    os.utime(path)  # ensure a fresh stamp even on coarse filesystems
    b = _scan(path, chunk_rows=128)
    np.testing.assert_array_equal(
        np.concatenate([c[0][: c[3]] for c in b]), X2
    )
    assert not np.array_equal(a[0][0], b[0][0])


def test_served_chunks_are_read_only(tmp_path, rng):
    X = rng.normal(size=(300, 4)).astype(np.float32)
    path = _write(tmp_path, X)
    from spark_rapids_ml_tpu.streaming import iter_chunks

    for cX, _, _, n in iter_chunks(
        path, "features", (), None, None, 128, np.dtype(np.float32)
    ):
        with pytest.raises(ValueError):
            np.asarray(cX)[0, 0] = 1.0
        break


# ---------------------------------------------------------------------------
# fault injection / device loss
# ---------------------------------------------------------------------------


def test_spill_fault_restart_not_double_count(tmp_path, rng):
    """An injected OOM at the `chunk_cache_spill` site mid-epoch fails
    the pass; the fit-level retry restarts with fresh accumulators and
    a dropped (half-recorded) stream — the retried statistics must
    match a clean fit exactly (no chunk double-counted)."""
    from spark_rapids_ml_tpu.regression import LinearRegression
    from spark_rapids_ml_tpu.resilience import fault_inject

    X = rng.normal(size=(900, 6))
    yv = X @ rng.normal(size=6) + rng.normal(scale=0.1, size=900)
    path = _write(tmp_path, X.astype(np.float32), yv)
    set_config(
        force_streaming_stats=True, host_batch_bytes=8192,
        retry_backoff_s=0.01, retry_jitter=0.0,
    )
    m_clean = LinearRegression().fit(path)
    clear_chunk_cache()
    # tiny budget arms real spills; the first one fires the fault
    set_config(chunk_cache_host_bytes=10_000)
    with fault_inject("chunk_cache_spill", "oom", times=1):
        m_faulted = LinearRegression().fit(path)
    np.testing.assert_allclose(
        np.asarray(m_faulted.coefficients),
        np.asarray(m_clean.coefficients), rtol=1e-5,
    )


def test_device_loss_invalidates_device_tier_spill_survives(tmp_path, rng):
    import jax

    # stream B first: fully spilled under a tiny budget
    X2 = np.tile(np.arange(8, dtype=np.float32), (1500, 1))
    p2 = _write(tmp_path, X2, name="s.parquet")
    set_config(chunk_cache_host_bytes=16_000, chunk_cache_codec="zlib")
    b = _scan(p2, chunk_rows=256)
    spilled_before = CHUNK_METRICS["spilled_bytes"]
    assert spilled_before > 0
    # stream A second, under a budget that keeps it resident:
    # device-mirrored feature blocks (device_ok fill pass)
    set_config(chunk_cache_host_bytes=64 * 1024 * 1024)
    X = rng.normal(size=(600, 8)).astype(np.float32)
    path = _write(tmp_path, X)
    a = _scan(path, chunk_rows=128, device_ok=True)
    assert CHUNK_METRICS["device_bytes"] > 0

    dev_id = int(jax.devices()[0].id)
    invalidate_for_devices([dev_id])
    assert CHUNK_METRICS["invalidations"] >= 1
    assert CHUNK_METRICS["device_bytes"] == 0
    # spilled stream survives and replays byte-identically
    misses = CHUNK_METRICS["misses"]
    b2 = _scan(p2, chunk_rows=256)
    _assert_scans_equal(b, b2)
    assert CHUNK_METRICS["misses"] == misses
    # the device tier is a MIRROR of the host copy: losing the chip
    # costs only the mirror — the stream keeps serving from host with
    # no re-read (and may re-promote under the post-loss ledger)
    a2 = _scan(path, chunk_rows=128, device_ok=True)
    _assert_scans_equal(a, a2)
    assert CHUNK_METRICS["misses"] == misses


def test_chunk_ledger_claims_are_budget_visible(tmp_path, rng):
    """The device tier books through the SAME external-reservation
    ledger serving pins use, and never evicts dataset entries to make
    room (evict=False claims free headroom only)."""
    from spark_rapids_ml_tpu.parallel.device_cache import (
        cache_resident_bytes,
        get_device_cache,
    )

    X = rng.normal(size=(600, 8)).astype(np.float32)
    path = _write(tmp_path, X)
    base = cache_resident_bytes()
    _scan(path, chunk_rows=128, device_ok=True)
    dev = CHUNK_METRICS["device_bytes"]
    assert dev > 0
    assert cache_resident_bytes() == base + dev
    assert get_device_cache()._external.get("chunk_cache") == dev
    clear_chunk_cache()
    assert cache_resident_bytes() == base
    assert get_device_cache()._external.get("chunk_cache") is None


# ---------------------------------------------------------------------------
# parallel staging readers
# ---------------------------------------------------------------------------


def test_parallel_stage_parquet_byte_parity(tmp_path, rng):
    """readers=3 range readers writing at global offsets must assemble
    the exact buffer the single in-order scan does."""
    from spark_rapids_ml_tpu.parallel.mesh import fetch_replicated
    from spark_rapids_ml_tpu.streaming import stage_parquet
    from spark_rapids_ml_tpu.tracing import last_fact

    n, d = 3203, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    yv = rng.normal(size=n)
    path = _write(tmp_path, X, yv, row_group_size=400)

    set_config(fused_parquet_readers=1, chunk_cache="off")
    ds1 = stage_parquet(path, label_col="label", dtype=np.float32)
    assert last_fact("staging")["engine"] == "per-device"
    set_config(fused_parquet_readers=3)
    ds3 = stage_parquet(path, label_col="label", dtype=np.float32)
    assert last_fact("staging")["engine"] == "per-device-parallel"
    assert last_fact("staging")["readers"] == 3
    for a, b in ((ds1.X, ds3.X), (ds1.y, ds3.y), (ds1.weight, ds3.weight)):
        np.testing.assert_array_equal(
            fetch_replicated(a, ds1.mesh), fetch_replicated(b, ds3.mesh)
        )


def test_auto_readers_resolve_and_report(tmp_path, rng):
    """`fused_parquet_readers=auto` resolves from the host probe,
    explicit ints still pin, and the decision lands in the fit report's
    solver_decision section."""
    import os

    from spark_rapids_ml_tpu.fused import resolve_parquet_readers
    from spark_rapids_ml_tpu.tracing import last_fact

    n = resolve_parquet_readers()
    assert 1 <= n <= 16
    decision = last_fact("parquet_readers")
    assert decision["parquet_readers_mode"] == "auto"
    assert f"cpu_count={os.cpu_count() or 1}" in (
        decision["parquet_readers_reason"]
    )
    set_config(fused_parquet_readers=5)
    assert resolve_parquet_readers() == 5
    assert last_fact("parquet_readers")["parquet_readers_mode"] == "explicit"
    set_config(fused_parquet_readers="auto")

    from spark_rapids_ml_tpu.regression import LinearRegression

    X = rng.normal(size=(800, 6))
    yv = X @ rng.normal(size=6)
    path = _write(tmp_path, X.astype(np.float32), yv)
    set_config(fused_stage_solve="on")
    m = LinearRegression().fit(path)
    rep = m.fit_report()
    sd = rep.get("solver_decision", {})
    assert sd.get("parquet_readers") >= 1
    assert sd.get("parquet_readers_mode") == "auto"


def test_prefetch_depth_conf(tmp_path, rng):
    X = rng.normal(size=(500, 4)).astype(np.float32)
    path = _write(tmp_path, X)
    from spark_rapids_ml_tpu.streaming import iter_chunks_prefetch

    outs = []
    for depth in (1, 4):
        set_config(streaming_prefetch_depth=depth)
        clear_chunk_cache()
        outs.append([
            (np.asarray(cX).copy(), n)
            for cX, _, _, n in iter_chunks_prefetch(
                path, "features", (), None, None, 128, np.dtype(np.float32)
            )
        ])
    for (x1, n1), (x2, n2) in zip(*outs):
        np.testing.assert_array_equal(x1, x2)
        assert n1 == n2


# ---------------------------------------------------------------------------
# epoch economics + DuHL convergence parity
# ---------------------------------------------------------------------------


def test_epoch2_serves_from_cache_not_disk(tmp_path, rng):
    """The epoch-streaming contract this PR exists for: epoch 1 decodes
    parquet, epochs 2..n replay the cache (zero further misses) with
    bit-identical statistics."""
    from spark_rapids_ml_tpu.streaming import linreg_streaming_stats

    X = rng.normal(size=(2000, 8))
    yv = X @ rng.normal(size=8)
    path = _write(tmp_path, X.astype(np.float32), yv)
    set_config(host_batch_bytes=16_384)
    st1 = linreg_streaming_stats(path, "features", (), "label", None)
    misses = CHUNK_METRICS["misses"]
    st2 = linreg_streaming_stats(path, "features", (), "label", None)
    assert CHUNK_METRICS["misses"] == misses
    assert CHUNK_METRICS["hits"] >= 1
    for k in st1:
        np.testing.assert_array_equal(np.asarray(st1[k]), np.asarray(st2[k]))


def test_duhl_logreg_convergence_parity(tmp_path, rng):
    from spark_rapids_ml_tpu.streaming import logreg_streaming_fit

    n, d = 12000, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=d).astype(np.float32)
    yv = (X @ w_true > 0).astype(np.float64)
    path = _write(tmp_path, X, yv)
    set_config(host_batch_bytes=64 * 1024)
    full = logreg_streaming_fit(
        path, "features", (), "label", None, l2=1e-3, max_iter=60,
    )
    clear_chunk_cache()
    set_config(
        streaming_chunk_sampling="duhl",
        streaming_chunk_sample_fraction=0.5,
    )
    duhl = logreg_streaming_fit(
        path, "features", (), "label", None, l2=1e-3, max_iter=60,
    )
    assert duhl["converged"] and full["converged"]
    assert duhl["sampled_epochs"] > 0
    assert duhl["chunk_visits_saved"] > 0
    cf, cd = full["coef"].ravel(), duhl["coef"].ravel()
    # convergence parity: same optimum within f32-streaming noise (the
    # tail runs EXACT passes, so the sampled trajectory cannot park at
    # the stale-compensation bias floor)
    assert np.linalg.norm(cf - cd) / np.linalg.norm(cf) < 5e-3
    np.testing.assert_allclose(full["intercept"], duhl["intercept"], atol=5e-3)


def test_duhl_kmeans_convergence_parity(tmp_path, rng):
    from spark_rapids_ml_tpu.streaming import kmeans_streaming_fit

    # overlapping clusters: Lloyd needs enough passes for sampling to
    # engage past its warmup
    X = np.concatenate([
        rng.normal(loc=c, scale=2.0, size=(4000, 5))
        for c in (0.0, 1.5, -1.5, 3.0)
    ]).astype(np.float32)
    path = _write(tmp_path, X)
    set_config(host_batch_bytes=64 * 1024)
    kw = dict(k=4, seed=3, max_iter=30, tol=1e-4)
    full = kmeans_streaming_fit(path, "features", (), None, **kw)
    clear_chunk_cache()
    set_config(
        streaming_chunk_sampling="duhl",
        streaming_chunk_sample_fraction=0.5,
    )
    duhl = kmeans_streaming_fit(path, "features", (), None, **kw)
    assert duhl["sampled_epochs"] > 0
    assert duhl["chunk_visits_saved"] > 0
    # final cost is computed by an EXACT full pass in both fits
    assert abs(duhl["cost"] - full["cost"]) / full["cost"] < 0.02


def test_sampling_off_is_exact_default(tmp_path, rng):
    """`streaming_chunk_sampling=off` (the default) keeps the exact
    accumulate path: trajectories identical with the cache on or off."""
    from spark_rapids_ml_tpu.streaming import logreg_streaming_fit

    X = rng.normal(size=(3000, 5)).astype(np.float32)
    yv = (X[:, 0] > 0).astype(np.float64)
    path = _write(tmp_path, X, yv)
    set_config(host_batch_bytes=32 * 1024)
    a = logreg_streaming_fit(path, "features", (), "label", None, max_iter=15)
    set_config(chunk_cache="off")
    b = logreg_streaming_fit(path, "features", (), "label", None, max_iter=15)
    np.testing.assert_array_equal(a["coef"], b["coef"])
    assert a["epochs"] == b["epochs"]
