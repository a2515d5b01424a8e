#
# Native staging library tests: the C++ paths must produce bit-identical
# results to the numpy fallbacks (incl. duplicate-entry CSR semantics),
# and the fallbacks must engage cleanly.  _FORCE_NATIVE overrides the
# size/thread-count gates so the C kernels really run on single-core CI.
#
import numpy as np
import pytest
import scipy.sparse as sp

import spark_rapids_ml_tpu.native as native


@pytest.fixture
def force_native(monkeypatch):
    if not native.available():
        pytest.skip("native staging library unavailable")
    monkeypatch.setattr(native, "_FORCE_NATIVE", True)
    monkeypatch.setattr(native, "_MIN_NATIVE_BYTES", 0)
    monkeypatch.setattr(native, "_MIN_PACK_ROWS", 0)
    return native._load()


def test_build_and_threads():
    if not native.available():
        pytest.skip("native staging library unavailable")
    assert native._load().staging_num_threads() >= 1


def test_pad_cast_matches_numpy(force_native, rng):
    for src_dt, dst_dt in [
        (np.float64, np.float32), (np.float32, np.float32),
        (np.float64, np.float64), (np.float32, np.float64),
    ]:
        arr = rng.normal(size=(37, 5)).astype(src_dt)
        got = native.pad_cast(arr, 40, np.dtype(dst_dt))
        want = np.zeros((40, 5), dst_dt)
        want[:37] = arr.astype(dst_dt)
        assert got.dtype == np.dtype(dst_dt)
        np.testing.assert_array_equal(got, want)


def test_gather_rows_strided_matches_numpy(force_native, rng):
    """The fused interleave-permutation slice of the staging engine: the
    native kernel must match the numpy strided slice + cast exactly, for
    both the round-robin (step=n_dev) and contiguous (step=1) layouts.
    (A missing `d` argument in the ctypes call shipped once — caught only
    at >= _MIN_NATIVE_BYTES piece sizes, which is why this runs forced.)"""
    for src_dt, dst_dt in [
        (np.float64, np.float32), (np.float32, np.float32),
        (np.float64, np.float64), (np.float32, np.float64),
    ]:
        arr = rng.normal(size=(101, 7)).astype(src_dt)
        for start, step, count in [(3, 8, 12), (0, 1, 101), (40, 1, 30),
                                   (6, 8, 0)]:
            got = native.gather_rows_strided(
                arr, start, step, count, np.dtype(dst_dt)
            )
            want = np.ascontiguousarray(
                arr[start : start + count * step : step], dtype=dst_dt
            )
            assert got.dtype == np.dtype(dst_dt)
            np.testing.assert_array_equal(got, want)
            # into a buffer the caller reuses: the same rows, in place
            buf = np.full((count + 2, 7), -1.0, dst_dt)
            into = native.gather_rows_strided(
                arr, start, step, count, np.dtype(dst_dt), out=buf[:count]
            )
            assert np.shares_memory(into, buf) or count == 0
            np.testing.assert_array_equal(into, want)
            assert (buf[count:] == -1.0).all()


@pytest.mark.parametrize("stored", ["F", "strided"])
def test_gather_rows_strided_into_buffer_numpy_fallback(stored, rng):
    """Rows the native kernels do not take (F order, a strided view) go
    through numpy, into the caller's buffer too."""
    arr = rng.normal(size=(60, 5))
    arr = np.asfortranarray(arr) if stored == "F" else np.tile(arr, (2, 2))[::2, ::2]
    buf = np.zeros((10, 5), np.float32)
    into = native.gather_rows_strided(arr, 3, 4, 10, np.dtype(np.float32), out=buf)
    assert into is buf
    np.testing.assert_array_equal(buf, arr[3:43:4].astype(np.float32))


def test_pack_rows_matches_stack(force_native, rng):
    for src_dt, dst_dt in [
        (np.float64, np.float32), (np.float32, np.float32),
        (np.float64, np.float64),
    ]:
        rows = np.empty(23, object)
        for i in range(23):
            rows[i] = rng.normal(size=7).astype(src_dt)
        got = native.pack_rows(rows, 24, np.dtype(dst_dt))
        want = np.zeros((24, 7), dst_dt)
        want[:23] = np.stack(list(rows)).astype(dst_dt)
        np.testing.assert_array_equal(got, want)


def test_pack_rows_list_fallback(force_native, rng):
    # lists (not ndarrays) use the numpy fallback regardless of gating
    rows = np.empty(5, object)
    for i in range(5):
        rows[i] = [float(i), float(i + 1)]
    got = native.pack_rows(rows, 5, np.float32)
    assert got.shape == (5, 2)
    np.testing.assert_array_equal(got[:, 0], [0, 1, 2, 3, 4])


def test_csr_densify_matches_toarray(force_native, rng):
    dense = rng.normal(size=(50, 12))
    dense[rng.random((50, 12)) < 0.8] = 0.0
    for dt in (np.float32, np.float64):
        csr = sp.csr_matrix(dense.astype(dt))
        got = native.densify_csr(csr, 52, np.float32)
        want = np.zeros((52, 12), np.float32)
        want[:50] = csr.toarray().astype(np.float32)
        np.testing.assert_array_equal(got, want)


def test_csr_duplicate_entries_sum(force_native):
    # scipy toarray() SUMS duplicates; the native path must match
    data = np.array([1.0, 2.0, 5.0], np.float32)
    indices = np.array([0, 0, 2], np.int32)
    indptr = np.array([0, 2, 3], np.int64)
    csr = sp.csr_matrix((data, indices, indptr), shape=(2, 3))
    assert not csr.has_canonical_format
    got = native.densify_csr(csr, 2, np.float32)
    np.testing.assert_array_equal(got, [[3.0, 0.0, 0.0], [0.0, 0.0, 5.0]])


def test_no_padding_shortcircuit(monkeypatch, rng):
    # fallback with n_pad == n returns the stacked matrix directly
    monkeypatch.setattr(native, "_load", lambda: None)
    rows = np.empty(4, object)
    for i in range(4):
        rows[i] = rng.normal(size=3)
    got = native.pack_rows(rows, 4, np.float64)
    np.testing.assert_array_equal(got, np.stack(list(rows)))

    dense = rng.normal(size=(6, 4)).astype(np.float32)
    got2 = native.densify_csr(sp.csr_matrix(dense), 6, np.float32)
    np.testing.assert_array_equal(got2, dense)


def test_staging_used_by_data_plane(rng):
    # end to end: pandas array-column extraction goes through pack_rows
    import pandas as pd

    from spark_rapids_ml_tpu.data import extract_arrays

    X = rng.normal(size=(30, 4)).astype(np.float32)
    df = pd.DataFrame({"features": list(X)})
    batch = extract_arrays(df, features_col="features")
    np.testing.assert_array_equal(batch.X, X)


def test_sparse_input_densifies(rng):
    from spark_rapids_ml_tpu.data import _ensure_dense

    dense = rng.normal(size=(20, 6)).astype(np.float32)
    dense[dense < 0] = 0
    got = _ensure_dense(sp.csr_matrix(dense))
    np.testing.assert_array_equal(got, dense)
