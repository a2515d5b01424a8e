#
# Slow scale tests (--runslow) — the analog of the reference's tests_large
# tier (tests_large/test_large_logistic_regression.py:39-60): each test
# drives a path at a size where the scaling machinery (budget routing,
# tiled recompute, streamed epochs) actually engages, not just the unit
# shapes.
#
import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu.config import reset_config, set_config


@pytest.fixture(autouse=True)
def _clean_config():
    reset_config()
    yield
    reset_config()


@pytest.mark.slow
def test_budget_triggered_streamed_stats_pca(tmp_path, rng):
    """A dataset past the (artificially lowered) HBM budget must route
    PCA through streamed second moments WITHOUT force_streaming_stats,
    and match the in-memory fit."""
    from spark_rapids_ml_tpu.feature import PCA

    n, d = 150_000, 32
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, 0] *= 5.0  # give the spectrum structure
    path = str(tmp_path / "pca.parquet")
    pd.DataFrame({"features": list(X)}).to_parquet(path)
    # dataset: n*d*4 = 19.2 MB; set per-device budget so need > budget
    set_config(hbm_bytes=1024 * 1024, host_batch_bytes=4 * 1024 * 1024)
    m_stream = PCA(k=3).setInputCol("features").setOutputCol("o").fit(path)
    reset_config()
    m_mem = PCA(k=3).setInputCol("features").setOutputCol("o").fit(
        pd.DataFrame({"features": list(X)})
    )
    np.testing.assert_allclose(
        np.abs(m_stream.components_), np.abs(m_mem.components_),
        rtol=1e-3, atol=1e-4,
    )


@pytest.mark.slow
def test_beyond_budget_epoch_streaming_logreg(tmp_path, rng):
    """300k-row LogReg through the epoch-streaming path (budget-triggered),
    objective parity with an in-memory fit on the same data."""
    from spark_rapids_ml_tpu.classification import LogisticRegression

    n, d = 300_000, 16
    X = rng.normal(size=(n, d)).astype(np.float32)
    beta = rng.normal(size=d).astype(np.float32)
    y = (X @ beta + 0.3 * rng.normal(size=n).astype(np.float32) > 0).astype(
        np.float64
    )
    path = str(tmp_path / "lr.parquet")
    pd.DataFrame({"features": list(X), "label": y}).to_parquet(path)
    set_config(hbm_bytes=4 * 1024 * 1024, host_batch_bytes=16 * 1024 * 1024)
    m_stream = LogisticRegression(regParam=0.01, tol=1e-6, maxIter=12).fit(path)
    reset_config()
    m_mem = LogisticRegression(regParam=0.01, tol=1e-6, maxIter=12).fit(
        pd.DataFrame({"features": list(X), "label": y})
    )
    assert abs(m_stream.objective - m_mem.objective) < 5e-4, (
        m_stream.objective, m_mem.objective,
    )
    np.testing.assert_allclose(
        m_stream.coef_, m_mem.coef_, rtol=2e-2, atol=2e-3
    )


@pytest.mark.slow
def test_dbscan_tiled_path_at_scale(rng):
    """60k rows with a small max_mbytes_per_batch forces the tiled
    adjacency recompute (the N^2/p working set would be ~11 GB untiled);
    cluster structure must survive.  Scaled for the CPU-mesh nightly —
    the same path covers 1M+ rows on chip."""
    from sklearn.datasets import make_blobs

    from spark_rapids_ml_tpu.clustering import DBSCAN

    X, y_true = make_blobs(
        n_samples=60_000, n_features=4, centers=5, cluster_std=0.3,
        center_box=(-20, 20), random_state=11,
    )
    X = X.astype(np.float32)
    model = DBSCAN(eps=0.5, min_samples=10, max_mbytes_per_batch=16).fit(X)
    labels = model._transform_array(X)[model.getOrDefault("predictionCol")]
    labels = np.asarray(labels)
    # well-separated blobs: 5 clusters, few noise points
    found = np.unique(labels[labels >= 0])
    assert len(found) == 5, found
    assert (labels == -1).mean() < 0.01

    from sklearn.metrics import adjusted_rand_score

    sample = rng.choice(len(X), 20_000, replace=False)
    assert adjusted_rand_score(y_true[sample], labels[sample]) > 0.99


@pytest.mark.slow
def test_epoch_streaming_beyond_budget_kmeans(tmp_path, rng):
    """Budget-triggered epoch-streaming Lloyd at 400k rows: inertia must be
    competitive with an in-memory fit on the same data."""
    from sklearn.datasets import make_blobs

    from spark_rapids_ml_tpu.clustering import KMeans

    X, _ = make_blobs(
        n_samples=400_000, n_features=16, centers=8, random_state=4
    )
    X = X.astype(np.float32)
    path = str(tmp_path / "km.parquet")
    pd.DataFrame({"features": list(X)}).to_parquet(path)
    set_config(hbm_bytes=4 * 1024 * 1024, host_batch_bytes=32 * 1024 * 1024)
    m_stream = KMeans(k=8, seed=1, maxIter=10).fit(path)
    reset_config()
    m_mem = KMeans(k=8, seed=1, maxIter=10).fit(
        pd.DataFrame({"features": list(X)})
    )
    assert m_stream.inertia_ <= m_mem.inertia_ * 1.05


@pytest.mark.slow
def test_ann_recall_on_skewed_clusters(rng):
    """IVF recall when cluster populations are heavily skewed (a few
    giant lists + many tiny ones stress nprobe and list truncation)."""
    from sklearn.neighbors import NearestNeighbors as SkNN

    from spark_rapids_ml_tpu.knn import ApproximateNearestNeighbors

    sizes = [60_000, 20_000, 10_000] + [1_000] * 10
    centers = rng.normal(size=(len(sizes), 32)) * 10.0
    parts = [
        centers[i] + rng.normal(size=(s, 32))
        for i, s in enumerate(sizes)
    ]
    X = np.concatenate(parts).astype(np.float32)
    rng.shuffle(X)
    q = X[:1000]
    k = 10
    model = ApproximateNearestNeighbors(
        k=k, algorithm="ivfflat", algoParams={"nlist": 64, "nprobe": 16}
    ).fit(X)
    _, _, knn_df = model.kneighbors(q)
    got = np.stack(knn_df["indices"].to_numpy())
    _, want = SkNN(n_neighbors=k, algorithm="brute").fit(X).kneighbors(q)
    hits = sum(
        len(set(g.tolist()) & set(w.tolist())) for g, w in zip(got, want)
    )
    recall = hits / want.size
    assert recall > 0.9, f"skewed-cluster recall {recall}"
