#
# Sparse logistic regression tests — the analog of the reference's sparse
# LogReg coverage (test_logistic_regression.py sparse cases): the ELL
# sparse kernel must match the dense kernel on identical data, and match
# sklearn on real sparse datasets.
#
import numpy as np
import pytest
import scipy.sparse as sp

from spark_rapids_ml_tpu.classification import LogisticRegression


@pytest.fixture
def sparse_binary(rng):
    n, d = 400, 30
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[rng.random((n, d)) < 0.8] = 0.0
    true_w = rng.normal(size=d).astype(np.float32)
    y = (X @ true_w > 0).astype(np.float64)
    return sp.csr_matrix(X), X, y


def _coef(model):
    return np.asarray(model.coef_), np.asarray(model.intercept_)


def test_sparse_matches_dense_binary(sparse_binary, num_workers):
    csr, X, y = sparse_binary
    kw = dict(regParam=0.01, maxIter=200, tol=1e-10, num_workers=num_workers)
    m_sparse = LogisticRegression(**kw).fit((csr, y))
    m_dense = LogisticRegression(
        enable_sparse_data_optim=False, **kw
    ).fit((csr, y))
    cs, bs = _coef(m_sparse)
    cd, bd = _coef(m_dense)
    np.testing.assert_allclose(cs, cd, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(bs, bd, rtol=1e-3, atol=1e-4)


def test_sparse_matches_dense_multinomial(rng):
    n, d, C = 300, 20, 4
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[rng.random((n, d)) < 0.7] = 0.0
    W = rng.normal(size=(C, d)).astype(np.float32)
    y = np.argmax(X @ W.T, axis=1).astype(np.float64)
    csr = sp.csr_matrix(X)
    kw = dict(regParam=0.05, maxIter=200, tol=1e-10)
    cs, _ = _coef(LogisticRegression(**kw).fit((csr, y)))
    cd, _ = _coef(
        LogisticRegression(enable_sparse_data_optim=False, **kw).fit((csr, y))
    )
    np.testing.assert_allclose(cs, cd, rtol=2e-3, atol=2e-4)


def test_sparse_standardization(sparse_binary):
    csr, X, y = sparse_binary
    # scale columns so standardization matters
    scale = np.linspace(0.1, 20.0, X.shape[1]).astype(np.float32)
    Xs = X * scale
    csr_s = sp.csr_matrix(Xs)
    kw = dict(regParam=0.01, maxIter=300, tol=1e-10, standardization=True)
    m_sparse = LogisticRegression(**kw).fit((csr_s, y))
    m_dense = LogisticRegression(enable_sparse_data_optim=False, **kw).fit(
        (csr_s, y)
    )
    # same predictions; coefficients close (sparse standardizes without
    # centering — same optimum given the intercept)
    ps = m_sparse._transform_array(Xs)["prediction"]
    pd_ = m_dense._transform_array(Xs)["prediction"]
    assert (ps == pd_).mean() > 0.99


def test_sparse_vs_sklearn(sparse_binary):
    csr, X, y = sparse_binary
    reg = 0.01
    model = LogisticRegression(
        regParam=reg, maxIter=500, tol=1e-10, standardization=False
    ).fit((csr, y))
    from sklearn.linear_model import LogisticRegression as SkLR

    sk = SkLR(C=1.0 / (reg * len(y)), max_iter=5000, tol=1e-10).fit(
        csr, y.astype(int)
    )
    # same objective up to scaling: Spark normalizes by sum of weights
    cs, bs = _coef(model)
    np.testing.assert_allclose(cs.ravel(), sk.coef_.ravel(), rtol=2e-2,
                               atol=2e-3)
    np.testing.assert_allclose(bs, sk.intercept_, rtol=2e-2, atol=2e-3)


def test_sparse_l1_sparsity(sparse_binary):
    csr, X, y = sparse_binary
    model = LogisticRegression(
        regParam=0.1, elasticNetParam=1.0, maxIter=300, standardization=False
    ).fit((csr, y))
    coef, _ = _coef(model)
    assert (np.abs(coef) < 1e-8).mean() > 0.2  # L1 zeroes coefficients


def test_force_sparse_on_dense_input(sparse_binary):
    # enable_sparse_data_optim=True forces ELL staging even for dense input
    _, X, y = sparse_binary
    kw = dict(regParam=0.01, maxIter=200, tol=1e-10)
    m_forced = LogisticRegression(enable_sparse_data_optim=True, **kw).fit(
        (X, y)
    )
    m_dense = LogisticRegression(enable_sparse_data_optim=False, **kw).fit(
        (X, y)
    )
    np.testing.assert_allclose(
        m_forced.coef_, m_dense.coef_, rtol=1e-3, atol=1e-4
    )


def test_no_intercept_standardization_matches(sparse_binary):
    # without an intercept the dense path must scale-only like the sparse
    # path (centering would change the optimum)
    csr, X, y = sparse_binary
    kw = dict(regParam=0.01, maxIter=300, tol=1e-10, fitIntercept=False,
              standardization=True)
    cs, _ = _coef(LogisticRegression(**kw).fit((csr, y)))
    cd, _ = _coef(
        LogisticRegression(enable_sparse_data_optim=False, **kw).fit((csr, y))
    )
    np.testing.assert_allclose(cs, cd, rtol=1e-3, atol=1e-4)


def test_ell_conversion(rng):
    from spark_rapids_ml_tpu.ops.sparse import ell_from_csr

    dense = np.zeros((4, 6), np.float32)
    dense[0, [1, 3]] = [1.0, 2.0]
    dense[2, [0, 2, 5]] = [3.0, 4.0, 5.0]
    vals, cols = ell_from_csr(sp.csr_matrix(dense))
    assert vals.shape == (4, 3)  # max nnz/row = 3
    # reconstruct
    rec = np.zeros_like(dense)
    for i in range(4):
        for k in range(3):
            rec[i, cols[i, k]] += vals[i, k]
    np.testing.assert_array_equal(rec, dense)


# ---------------------------------------------------------------------------
# Sparse breadth beyond LogReg: blocked-densify
# sufficient statistics for PCA / LinearRegression, chunked sparse
# transform, sparse kNN, and the int64-index CSR story.
# ---------------------------------------------------------------------------


def _sparse_blobs(rng, n=3000, d=40, density=0.08):
    import scipy.sparse as sp

    X = sp.random(
        n, d, density=density, format="csr", dtype=np.float64,
        random_state=np.random.RandomState(7),
    )
    return X


def test_sparse_pca_blocked_stats_match_dense(rng):
    from spark_rapids_ml_tpu.config import reset_config, set_config
    from spark_rapids_ml_tpu.feature import PCA

    Xs = _sparse_blobs(rng)
    dense = np.asarray(Xs.todense())
    m_dense = PCA(k=4).fit(dense)
    # force the blocked-CSR streamed-statistics path with a tiny chunk
    set_config(force_streaming_stats=True, host_batch_bytes=64 * 1024)
    try:
        m_sparse = PCA(k=4).fit(Xs)
    finally:
        reset_config()
    np.testing.assert_allclose(
        np.abs(m_sparse.components_), np.abs(m_dense.components_),
        rtol=2e-3, atol=2e-4,
    )
    np.testing.assert_allclose(
        m_sparse.explained_variance_, m_dense.explained_variance_,
        rtol=2e-3, atol=1e-6,
    )


def test_sparse_linreg_blocked_stats_match_dense(rng):
    from spark_rapids_ml_tpu.config import reset_config, set_config
    from spark_rapids_ml_tpu.regression import LinearRegression

    Xs = _sparse_blobs(rng)
    beta = rng.normal(size=(40,))
    y = np.asarray(Xs @ beta) + 0.01 * rng.normal(size=(3000,))
    dense = np.asarray(Xs.todense())
    m_dense = LinearRegression(regParam=1e-3).fit((dense, y))
    set_config(force_streaming_stats=True, host_batch_bytes=64 * 1024)
    try:
        m_sparse = LinearRegression(regParam=1e-3).fit((Xs, y))
    finally:
        reset_config()
    np.testing.assert_allclose(
        m_sparse.coefficients, m_dense.coefficients, rtol=1e-3, atol=1e-4
    )
    np.testing.assert_allclose(
        m_sparse.intercept, m_dense.intercept, rtol=1e-3, atol=1e-4
    )


def test_sparse_chunked_transform_matches_dense(rng):
    from spark_rapids_ml_tpu.config import reset_config, set_config
    from spark_rapids_ml_tpu.feature import PCA

    Xs = _sparse_blobs(rng)
    dense = np.asarray(Xs.todense())
    model = PCA(k=3).fit(dense)
    out_dense = np.asarray(model.transform(dense))
    # tiny chunks force several densify-stage-transform rounds
    set_config(host_batch_bytes=64 * 1024)
    try:
        out_sparse = np.asarray(model.transform(Xs))
    finally:
        reset_config()
    np.testing.assert_allclose(out_sparse, out_dense, rtol=1e-4, atol=1e-5)


def test_sparse_knn_matches_dense(rng):
    from spark_rapids_ml_tpu.knn import NearestNeighbors

    Xs = _sparse_blobs(rng, n=800, d=24, density=0.15)
    dense = np.asarray(Xs.todense())
    _, _, knn_s = NearestNeighbors(k=5).fit(Xs).kneighbors(Xs[:100])
    _, _, knn_d = NearestNeighbors(k=5).fit(dense).kneighbors(dense[:100])
    np.testing.assert_array_equal(
        np.asarray(list(knn_s["indices"])), np.asarray(list(knn_d["indices"]))
    )


def test_int64_index_csr_fit(rng):
    # the analog of the reference's >1e9-nnz int64 switch
    # (classification.py:960-966): a CSR whose indices/indptr are int64
    # must stage and fit identically to the int32 form
    import scipy.sparse as sp

    from spark_rapids_ml_tpu.classification import LogisticRegression

    Xs = _sparse_blobs(rng, n=2000, d=30, density=0.1).astype(np.float32)
    y = (np.asarray(Xs.sum(axis=1)).ravel() > Xs.sum() / 2000).astype(np.float64)
    X64 = Xs.copy()
    # scipy's ctor downcasts small indices; assign the arrays directly so
    # the int64 layout (what a >2^31-nnz matrix is forced into) survives
    X64.indices = X64.indices.astype(np.int64)
    X64.indptr = X64.indptr.astype(np.int64)
    assert X64.indices.dtype == np.int64
    m32 = LogisticRegression(regParam=1e-3, maxIter=30).fit((Xs, y))
    m64 = LogisticRegression(regParam=1e-3, maxIter=30).fit((X64, y))
    np.testing.assert_allclose(
        np.asarray(m32.coefficients), np.asarray(m64.coefficients),
        rtol=1e-5, atol=1e-6,
    )


def test_sparse_transform_never_whole_densifies(rng, monkeypatch):
    # the chunked path must be REACHABLE through the public transform():
    # every densify call is bounded by the chunk size, never the full n
    from spark_rapids_ml_tpu import native
    from spark_rapids_ml_tpu.config import reset_config, set_config
    from spark_rapids_ml_tpu.feature import PCA

    Xs = _sparse_blobs(rng, n=4000, d=32)
    model = PCA(k=3).fit(np.asarray(Xs.todense()))

    seen = []
    real = native.densify_csr

    def spy(csr, n_pad, dtype):
        seen.append(csr.shape[0])
        return real(csr, n_pad, dtype)

    monkeypatch.setattr(native, "densify_csr", spy)
    set_config(host_batch_bytes=64 * 1024)  # ~512-row chunks at d=32
    try:
        model.transform(Xs)
    finally:
        reset_config()
    assert seen, "sparse transform never reached the blocked densify"
    assert max(seen) < 4000, f"whole-matrix densify happened: {seen}"


def test_sparse_host_dispatched_lbfgs_matches_fused(rng):
    # the dispatch-budget gate covers the ELL sparse path too: a tiny
    # budget routes through host-driven L-BFGS with the same
    # gather-contract margin, matching the fused sparse solver
    from spark_rapids_ml_tpu.config import reset_config, set_config

    n, d = 2000, 24
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[rng.random((n, d)) < 0.75] = 0.0
    y = (X @ rng.normal(size=d) > 0).astype(np.float64)
    csr = sp.csr_matrix(X)
    kw = dict(regParam=0.01, maxIter=150, tol=1e-10)
    m_fused = LogisticRegression(**kw).fit((csr, y))
    set_config(dispatch_flops_limit=1e5)
    try:
        m_host = LogisticRegression(**kw).fit((csr, y))
    finally:
        reset_config()
    np.testing.assert_allclose(
        np.asarray(m_host.coef_), np.asarray(m_fused.coef_),
        rtol=2e-3, atol=2e-4,
    )
