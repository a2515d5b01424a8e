#
# Sparse FIT staging tests — the analog of the reference's sparse fit
# coverage (cuML UMAP `_sparse_fit` umap.py:904-969 keeps CSR end-to-end;
# kNN staging core.py:183-265): CSR fit inputs must produce the same
# models/results as their dense form, while the host only ever densifies
# one bounded chunk at a time (RowStager.stage_sparse /
# data.densify_to_device), and CSR model attributes must survive
# save/load (core.py CSR component-array encoding).
#
import numpy as np
import pytest
import scipy.sparse as sp

from spark_rapids_ml_tpu import native
from spark_rapids_ml_tpu.config import reset_config, set_config


def _make_sparse(rng, n, d=24, density=0.3):
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[rng.random((n, d)) < 1.0 - density] = 0.0
    return sp.csr_matrix(X), X


@pytest.fixture
def sparse_rows(rng):
    return _make_sparse(rng, 500)


@pytest.fixture
def sparse_rows_big(rng):
    # chunk_rows_for floors chunks at 1024 rows, so bounded-densify
    # assertions need n comfortably above one chunk
    return _make_sparse(rng, 2500)


@pytest.fixture
def densify_spy(monkeypatch):
    """Record the row count of every blocked densify call."""
    seen = []
    real = native.densify_csr

    def spy(csr, n_pad, dtype):
        seen.append(int(csr.shape[0]))
        return real(csr, n_pad, dtype)

    monkeypatch.setattr(native, "densify_csr", spy)
    return seen


def _umap(**kw):
    from spark_rapids_ml_tpu.umap import UMAP

    kw.setdefault("n_neighbors", 10)
    kw.setdefault("n_epochs", 30)
    kw.setdefault("random_state", 7)
    kw.setdefault("init", "random")
    return UMAP(**kw)


def test_sparse_umap_fit_matches_dense(sparse_rows):
    csr, X = sparse_rows
    emb_s = _umap().fit(csr).embedding_
    emb_d = _umap().fit(X).embedding_
    np.testing.assert_allclose(emb_s, emb_d, rtol=1e-4, atol=1e-5)


def test_sparse_umap_fit_never_whole_densifies(sparse_rows_big, densify_spy):
    csr, _ = sparse_rows_big
    n = csr.shape[0]
    set_config(host_batch_bytes=8 * 1024)  # 1024-row floor chunks
    try:
        model = _umap().fit(csr)
    finally:
        reset_config()
    assert sp.issparse(model.raw_data_), "sparse fit must keep CSR raw data"
    assert densify_spy, "sparse fit never reached the blocked densify"
    assert max(densify_spy) < n, f"whole-matrix densify happened: {densify_spy}"


def test_sparse_umap_transform_bounded_and_matches_dense(sparse_rows_big,
                                                         densify_spy):
    csr, X = sparse_rows_big
    model = _umap(n_epochs=10).fit(csr)
    n_q = 120
    set_config(host_batch_bytes=8 * 1024)
    try:
        densify_spy.clear()
        out_s = model.transform(csr[:n_q])
    finally:
        reset_config()
    assert densify_spy and max(densify_spy) < csr.shape[0]
    out_d = model.transform(X[:n_q])
    np.testing.assert_allclose(out_s, out_d, rtol=1e-4, atol=1e-5)


def test_sparse_umap_spectral_init(sparse_rows):
    csr, X = sparse_rows
    m = _umap(init="spectral").fit(csr)
    emb = m.embedding_
    assert emb.shape == (csr.shape[0], 2)
    assert np.isfinite(emb).all()


def test_sparse_umap_supervised(sparse_rows):
    csr, X = sparse_rows
    y = (np.asarray(csr.sum(axis=1)).ravel() > 0).astype(np.float64)
    emb_s = _umap(labelCol="label").fit((csr, y)).embedding_
    emb_d = _umap(labelCol="label").fit((X, y)).embedding_
    np.testing.assert_allclose(emb_s, emb_d, rtol=1e-4, atol=1e-5)


def test_sparse_umap_jaccard(sparse_rows):
    # the reference supports jaccard ONLY for sparse input
    # (umap.py:1145-1146); here the tiled elementwise kernel serves the
    # chunk-densified sparse rows end-to-end
    csr, X = sparse_rows
    m = _umap(metric="jaccard", n_epochs=10).fit(csr)
    emb = m.embedding_
    assert emb.shape == (csr.shape[0], 2)
    assert np.isfinite(emb).all()
    # dense input agrees (a superset of the reference, which raises)
    emb_d = _umap(metric="jaccard", n_epochs=10).fit(X).embedding_
    np.testing.assert_allclose(emb, emb_d, rtol=1e-4, atol=1e-5)


def test_sparse_umap_save_load_roundtrip(sparse_rows, tmp_path):
    from spark_rapids_ml_tpu.umap import UMAPModel

    csr, X = sparse_rows
    model = _umap().fit(csr)
    path = str(tmp_path / "umap_sparse")
    model.save(path)
    loaded = UMAPModel.load(path)
    assert sp.issparse(loaded.raw_data_)
    assert (loaded.raw_data_ != model.raw_data_).nnz == 0
    np.testing.assert_allclose(loaded.embedding_, model.embedding_)
    np.testing.assert_allclose(
        loaded.transform(X[:50]), model.transform(X[:50]),
        rtol=1e-5, atol=1e-6,
    )


def test_sparse_knn_fit_bounded_and_matches_dense(sparse_rows_big,
                                                  densify_spy):
    from spark_rapids_ml_tpu.knn import NearestNeighbors

    csr, X = sparse_rows_big
    set_config(host_batch_bytes=8 * 1024)
    try:
        model = NearestNeighbors(k=5).fit(csr)
        assert sp.issparse(model.item_features), (
            "sparse kNN fit must keep the item set CSR"
        )
        _, _, knn_s = model.kneighbors(csr[:80])
    finally:
        reset_config()
    assert densify_spy, "sparse kNN search never reached the blocked densify"
    assert max(densify_spy) < csr.shape[0], (
        f"whole-matrix densify happened: {densify_spy}"
    )
    _, _, knn_d = NearestNeighbors(k=5).fit(X).kneighbors(X[:80])
    np.testing.assert_array_equal(
        np.asarray(list(knn_s["indices"])), np.asarray(list(knn_d["indices"]))
    )
    np.testing.assert_allclose(
        np.asarray(list(knn_s["distances"])),
        np.asarray(list(knn_d["distances"])),
        rtol=1e-5, atol=1e-6,
    )


def test_sparse_knn_save_load(sparse_rows, tmp_path):
    from spark_rapids_ml_tpu.knn import NearestNeighbors, NearestNeighborsModel

    csr, _ = sparse_rows
    model = NearestNeighbors(k=4).fit(csr)
    path = str(tmp_path / "knn_sparse")
    model.save(path)
    loaded = NearestNeighborsModel.load(path)
    assert sp.issparse(loaded.item_features)
    _, _, knn_a = model.kneighbors(csr[:40])
    _, _, knn_b = loaded.kneighbors(csr[:40])
    np.testing.assert_array_equal(
        np.asarray(list(knn_a["indices"])), np.asarray(list(knn_b["indices"]))
    )


@pytest.mark.parametrize("algorithm", ["ivfflat", "cagra"])
def test_sparse_ann_matches_dense(sparse_rows, algorithm):
    # CSR ANN input fits through the same staging as dense input and
    # returns identical neighbors
    from spark_rapids_ml_tpu.knn import ApproximateNearestNeighbors

    csr, X = sparse_rows
    kw = dict(k=5, algorithm=algorithm)
    if algorithm == "ivfflat":
        kw["algoParams"] = {"nlist": 4, "nprobe": 4}
    m_s = ApproximateNearestNeighbors(**kw).fit(csr)
    m_d = ApproximateNearestNeighbors(**kw).fit(X)
    _, _, knn_s = m_s.kneighbors(X[:60])
    _, _, knn_d = m_d.kneighbors(X[:60])
    np.testing.assert_array_equal(
        np.asarray(list(knn_s["indices"])), np.asarray(list(knn_d["indices"]))
    )


def test_stage_sparse_matches_dense_stage(rng):
    # unit contract: stage_sparse produces byte-identical device layout to
    # stage() on the densified matrix, including padding rows
    import jax

    from spark_rapids_ml_tpu.parallel.mesh import RowStager, get_mesh

    X = rng.normal(size=(137, 9)).astype(np.float32)
    X[rng.random((137, 9)) < 0.6] = 0.0
    csr = sp.csr_matrix(X)
    mesh = get_mesh(None)
    set_config(host_batch_bytes=2 * 1024)  # force several chunks
    try:
        st = RowStager.for_replicated(137, mesh, bucketing=False)
        dense_staged = np.asarray(jax.device_get(st.stage(X, np.float32)))
        sparse_staged = np.asarray(
            jax.device_get(st.stage_sparse(csr, np.float32))
        )
    finally:
        reset_config()
    np.testing.assert_array_equal(dense_staged, sparse_staged)
