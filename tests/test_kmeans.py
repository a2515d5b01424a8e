#
# KMeans tests — CPU-reference equivalence vs sklearn (SURVEY.md §4), the
# analog of reference tests/test_kmeans.py.
#
import numpy as np
import pandas as pd
import pytest
from sklearn.cluster import KMeans as SkKMeans
from sklearn.datasets import make_blobs

from spark_rapids_ml_tpu.clustering import KMeans, KMeansModel


def _blobs(n=1000, d=8, k=5, seed=0):
    X, y = make_blobs(n_samples=n, n_features=d, centers=k, cluster_std=1.0,
                      random_state=seed)
    return X.astype(np.float64), y


def test_kmeans_quality_vs_sklearn(num_workers):
    X, _ = _blobs()
    k = 5
    model = (
        KMeans(k=k, seed=7, maxIter=100, num_workers=num_workers)
        .setFeaturesCol("features")
        .fit(X)
    )
    sk = SkKMeans(n_clusters=k, n_init=10, random_state=0).fit(X)
    # same clustering quality within 2%
    assert model.inertia_ <= sk.inertia_ * 1.02
    assert model.cluster_centers_.shape == (k, X.shape[1])


def test_kmeans_doctest_example(num_workers):
    df = pd.DataFrame({"features": [[0.0, 0.0], [1.0, 1.0], [9.0, 8.0], [8.0, 9.0]]})
    model = KMeans(k=2, seed=1, num_workers=num_workers).setFeaturesCol("features").fit(df)
    out = model.transform(df)["prediction"].tolist()
    assert out[0] == out[1] and out[2] == out[3] and out[0] != out[2]


def test_kmeans_weighted(num_workers):
    rng = np.random.default_rng(0)
    X = np.concatenate([rng.normal(0, 0.1, (50, 2)), rng.normal(5, 0.1, (200, 2))])
    df = pd.DataFrame({"features": list(X), "w": [1.0] * 50 + [1.0] * 200})
    model = (
        KMeans(k=2, seed=3, num_workers=num_workers)
        .setFeaturesCol("features")
        .setWeightCol("w")
        .fit(df)
    )
    centers = sorted(model.clusterCenters(), key=lambda c: c[0])
    assert np.allclose(centers[0], [0, 0], atol=0.2)
    assert np.allclose(centers[1], [5, 5], atol=0.2)


def test_kmeans_random_init(num_workers):
    X, _ = _blobs(n=300, d=4, k=3)
    model = (
        KMeans(k=3, seed=1, initMode="random", maxIter=100, num_workers=num_workers)
        .setFeaturesCol("features")
        .fit(X)
    )
    sk = SkKMeans(n_clusters=3, n_init=10, random_state=0).fit(X)
    assert model.inertia_ <= sk.inertia_ * 1.05


def test_kmeans_save_load(tmp_path):
    X, _ = _blobs(n=200, d=4, k=3)
    model = KMeans(k=3, seed=5).setFeaturesCol("features").fit(X)
    path = str(tmp_path / "kmeans_model")
    model.write().save(path)
    loaded = KMeansModel.load(path)
    np.testing.assert_allclose(loaded.cluster_centers_, model.cluster_centers_)
    assert loaded.getK() == 3
    preds1 = model.transform(X)
    preds2 = loaded.transform(X)
    np.testing.assert_array_equal(preds1, preds2)


def test_kmeans_unsupported_param():
    with pytest.raises(ValueError, match="not supported"):
        KMeans(k=2, distanceMeasure="cosine")


def test_kmeans_cpu_model():
    X, _ = _blobs(n=200, d=4, k=3)
    model = KMeans(k=3, seed=5).setFeaturesCol("features").fit(X)
    sk = model.cpu()
    sk_preds = sk.predict(X)
    tpu_preds = model.transform(X)
    # same partition structure (labels may permute)
    from sklearn.metrics import adjusted_rand_score

    assert adjusted_rand_score(sk_preds, tpu_preds) == pytest.approx(1.0)


def test_kmeans_parallel_init_quality(rng):
    """k-means|| init must reach the same solution quality as sequential
    k-means++ at moderate k (the cost after Lloyd convergence is the
    quality contract, cuML scalable-k-means++ analog)."""
    from sklearn.datasets import make_blobs

    X, _ = make_blobs(n_samples=3000, n_features=8, centers=20,
                      cluster_std=0.5, random_state=0)
    X = X.astype(np.float32)
    df = pd.DataFrame({"features": list(X)})
    m_par = KMeans(k=20, seed=7, initMode="k-means||", maxIter=50).fit(df)
    m_seq = KMeans(k=20, seed=7, initMode="k-means++", maxIter=50).fit(df)
    # both should be within 10% of each other's converged cost
    assert m_par.inertia_ <= 1.1 * m_seq.inertia_ + 1e-6


def test_kmeans_init_steps_param(rng):
    from sklearn.datasets import make_blobs

    X, _ = make_blobs(n_samples=500, n_features=4, centers=5, random_state=2)
    df = pd.DataFrame({"features": list(X.astype(np.float32))})
    m = KMeans(k=5, seed=3, initSteps=4).fit(df)
    assert m.cluster_centers_.shape == (5, 4)
    # initSteps must reach the backend params
    est = KMeans(k=5, initSteps=4)
    assert est._tpu_params["init_steps"] == 4


def test_kmeans_summary_training_cost(rng):
    """pyspark parity: model.summary.trainingCost == inertia."""
    from sklearn.datasets import make_blobs

    X, _ = make_blobs(n_samples=300, n_features=4, centers=3, random_state=0)
    m = KMeans(k=3, seed=1).fit(X.astype(np.float32))
    assert m.hasSummary
    s = m.summary
    assert s.trainingCost == m.inertia_
    assert s.k == 3 and s.numIter == m.n_iter_


def test_single_sample_predict(rng):
    from sklearn.datasets import make_blobs

    X, _ = make_blobs(n_samples=300, n_features=4, centers=3, random_state=1)
    X = X.astype(np.float32)
    m = KMeans(k=3, seed=0).fit(pd.DataFrame({"features": list(X)}))
    batch = np.asarray(m._transform_array(X[:10])["prediction"])
    for i in range(10):
        assert m.predict(X[i]) == int(batch[i])
    with pytest.raises(ValueError, match="expects"):
        m.predict(np.zeros(7))


def test_stepwise_lloyd_matches_fused(rng):
    # kmeans_fit_stepwise (host-dispatched blocks, the route of rows a
    # device cannot hold twice) must reproduce the fused while_loop fit.  The
    # contract is "same update math, trajectories match up to f32
    # reduction order" (the stepwise docstring) — asserted in two parts.
    # The old form of this test compared full 50-iteration trajectories
    # on structure-free gaussian noise: BOTH fits hit max_iter still
    # moving (tol never reached), and the blocked path's different f32
    # summation order drifts chaotically through Lloyd's discrete
    # assignment flips — costs agreed to ~1e-4 while individual centers
    # differed by 1.5x, an artifact of comparing non-converged chaos,
    # not a blocking bug.
    import jax.numpy as jnp
    from sklearn.datasets import make_blobs

    from spark_rapids_ml_tpu.ops.kmeans import (
        _lloyd_block_step,
        _pairwise_sqdist,
        kmeans_fit,
        kmeans_fit_stepwise,
        kmeans_init,
    )

    Xh, _ = make_blobs(n_samples=3000, n_features=8, centers=5,
                       cluster_std=1.0, random_state=2)
    X = jnp.asarray(Xh.astype(np.float32))
    w = jnp.ones((3000,), jnp.float32)

    # (1) the math contract: one pass of blocked partial sums (three
    # blocks, uneven tail) equals one fused assignment+update from
    # IDENTICAL centers, up to f32 summation order
    C0 = kmeans_init(X, w, 5, 0, "random")
    acc = (jnp.zeros((5, 8), X.dtype), jnp.zeros((5,), X.dtype),
           jnp.zeros((), X.dtype))
    for s, rows in ((0, 1250), (1250, 1250), (2500, 500)):
        acc = _lloyd_block_step(
            acc, C0, X, w, jnp.asarray(s, jnp.int32), rows, 5
        )
    d2 = _pairwise_sqdist(X, C0)
    onehot = jnp.zeros((3000, 5), X.dtype).at[
        jnp.arange(3000), jnp.argmin(d2, axis=1)
    ].set(1.0) * w[:, None]
    np.testing.assert_allclose(
        np.asarray(acc[0]), np.asarray(onehot.T @ X), rtol=1e-4, atol=1e-3
    )
    np.testing.assert_allclose(
        np.asarray(acc[1]), np.asarray(onehot.sum(axis=0)), rtol=1e-6
    )
    np.testing.assert_allclose(
        float(acc[2]), float((jnp.min(d2, axis=1) * w).sum()), rtol=1e-4
    )

    # (2) end to end on clusterable data: both fits CONVERGE (the old
    # noise dataset never did) and land on the same centers and cost;
    # the small block forces multiple Lloyd blocks per pass (uneven
    # tail) while the "random" init (no D2 passes) keeps the seeding identical
    c_f, cost_f, it_f = kmeans_fit(
        X, w, k=5, seed=0, max_iter=50, tol=1e-4, init="random"
    )
    c_s, cost_s, it_s = kmeans_fit_stepwise(
        X, w, k=5, seed=0, max_iter=50, tol=1e-4, init="random",
        block_rows=1250,
    )
    assert int(it_f) < 50 and int(it_s) < 50, (it_f, it_s)
    np.testing.assert_allclose(
        np.sort(np.asarray(c_s), axis=0), np.sort(np.asarray(c_f), axis=0),
        rtol=1e-3, atol=1e-3,
    )
    np.testing.assert_allclose(float(cost_s), float(cost_f), rtol=1e-4)


def _routes(model):
    def walk(nodes):
        for n in nodes:
            yield n
            yield from walk(n.get("children", []))

    return [n["name"] for n in walk(model.fit_report()["spans"])
            if n["name"].startswith("kmeans_route[")]


def test_stepwise_dispatch_through_estimator(rng):
    # force the estimator's stepwise path via a device budget that cannot
    # hold the rows twice and check it agrees with the fused path end to end
    from spark_rapids_ml_tpu.config import reset_config, set_config
    from spark_rapids_ml_tpu.models.clustering import KMeans

    X = rng.normal(size=(2000, 6)).astype(np.float32)
    m_fused = KMeans(k=4, seed=1, maxIter=40, initMode="random").fit(X)
    assert _routes(m_fused) == ["kmeans_route[fused]"]
    # a device holds 256 rows: 6 KB, twice, beside 17 KB of temporaries
    set_config(hbm_bytes=20_000)
    try:
        m_step = KMeans(k=4, seed=1, maxIter=40, initMode="random").fit(X)
    finally:
        reset_config()
    assert _routes(m_step) == ["kmeans_route[stepwise]"]
    np.testing.assert_allclose(
        np.sort(m_step.cluster_centers_, axis=0),
        np.sort(m_fused.cluster_centers_, axis=0),
        rtol=1e-3, atol=1e-3,
    )
