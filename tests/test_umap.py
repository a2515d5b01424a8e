#
# UMAP tests — the analog of reference tests/test_umap.py, which scores
# embeddings with sklearn trustworthiness rather than exact equality
# (stochastic optimizer).
#
import numpy as np
import pandas as pd
import pytest
from sklearn.datasets import make_blobs
from sklearn.manifold import trustworthiness

from spark_rapids_ml_tpu.umap import UMAP, UMAPModel


@pytest.fixture(scope="module")
def blobs():
    X, y = make_blobs(
        n_samples=400, n_features=10, centers=5, cluster_std=0.8,
        random_state=10,
    )
    return X.astype(np.float32), y


def test_fit_embedding_trustworthy(blobs):
    X, _ = blobs
    model = UMAP(n_neighbors=12, random_state=0, n_epochs=150).fit(X)
    assert model.embedding_.shape == (400, 2)
    t = trustworthiness(X, model.embedding_, n_neighbors=12)
    assert t > 0.85, f"trustworthiness {t}"


def test_blob_separation(blobs):
    # well-separated blobs should stay separated in the embedding
    X, y = blobs
    model = UMAP(n_neighbors=10, random_state=0, n_epochs=200).fit(X)
    emb = model.embedding_
    centroids = np.stack([emb[y == c].mean(axis=0) for c in range(5)])
    spread = np.stack([emb[y == c].std(axis=0).mean() for c in range(5)])
    from scipy.spatial.distance import pdist

    assert pdist(centroids).min() > 2.0 * spread.mean()


def test_transform_new_points(blobs, num_workers):
    X, y = blobs
    model = UMAP(
        n_neighbors=10, random_state=0, n_epochs=100, num_workers=num_workers
    ).fit(X[:300])
    df = pd.DataFrame({"features": list(X[300:])})
    out = model.transform(df)
    emb_new = np.stack(out["embedding"].to_numpy())
    assert emb_new.shape == (100, 2)
    # new points of a class land near the training embedding of that class
    train_emb = model.embedding_
    for c in range(5):
        tr = train_emb[y[:300] == c].mean(axis=0)
        nw = emb_new[y[300:] == c].mean(axis=0)
        assert np.linalg.norm(tr - nw) < 3.0


def test_random_init_and_components(blobs):
    X, _ = blobs
    model = UMAP(
        n_components=3, init="random", n_neighbors=8, random_state=1,
        n_epochs=80,
    ).fit(X)
    assert model.embedding_.shape == (400, 3)
    t = trustworthiness(X, model.embedding_, n_neighbors=8)
    assert t > 0.8


def test_sample_fraction(blobs):
    X, _ = blobs
    model = UMAP(
        n_neighbors=8, sample_fraction=0.5, random_state=7, n_epochs=60
    ).fit(X)
    # roughly half the rows used for the fit (reference umap.py:926-948)
    assert 120 < model.raw_data_.shape[0] < 280
    assert model.embedding_.shape[0] == model.raw_data_.shape[0]


def test_cosine_metric(blobs):
    X, _ = blobs
    model = UMAP(
        metric="cosine", n_neighbors=8, random_state=2, n_epochs=60
    ).fit(X)
    t = trustworthiness(
        X / np.linalg.norm(X, axis=1, keepdims=True),
        model.embedding_, n_neighbors=8,
    )
    assert t > 0.75


def test_bad_params(blobs):
    X, _ = blobs
    with pytest.raises(ValueError, match="n_neighbors"):
        UMAP(n_neighbors=1000).fit(X)
    with pytest.raises(ValueError, match="not supported"):
        UMAP(metric="mahalanobis")
    with pytest.raises(ValueError, match="not supported"):
        UMAP(init="pca")


def test_save_load(tmp_path, blobs):
    X, _ = blobs
    model = UMAP(n_neighbors=8, random_state=0, n_epochs=50).fit(X)
    path = str(tmp_path / "umap")
    model.save(path)
    loaded = UMAPModel.load(path)
    np.testing.assert_allclose(loaded.embedding_, model.embedding_)
    a = model._transform_array(X[:20])["embedding"]
    b = loaded._transform_array(X[:20])["embedding"]
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_supervised_umap_improves_separation(rng):
    """labelCol threads into the fuzzy-set intersection (reference
    umap.py:812-813): with labels, same-class points pull together and
    cross-class edges are suppressed, so class separation in the embedding
    must improve over the unsupervised fit."""
    import pandas as pd

    n = 150
    # two heavily-overlapping gaussians: unsupervised UMAP cannot separate
    X = np.concatenate([
        rng.normal(0.0, 1.0, size=(n, 6)),
        rng.normal(0.4, 1.0, size=(n, 6)),
    ]).astype(np.float32)
    y = np.concatenate([np.zeros(n), np.ones(n)])
    df = pd.DataFrame({"features": list(X), "label": y})

    def sep(emb):
        a, b = emb[:n], emb[n:]
        inter = np.linalg.norm(a.mean(0) - b.mean(0))
        intra = 0.5 * (a.std(0).mean() + b.std(0).mean())
        return inter / max(intra, 1e-9)

    common = dict(n_neighbors=10, random_state=5, n_epochs=100)
    m_uns = UMAP(**common).setFeaturesCol("features").fit(df)
    m_sup = (
        UMAP(**common).setFeaturesCol("features").setLabelCol("label").fit(df)
    )
    assert sep(m_sup.embedding_) > 2.0 * sep(m_uns.embedding_)


def test_supervised_umap_unknown_labels(rng):
    """NaN labels are 'unknown' (-1): the fit must run and produce finite
    embeddings (umap-learn unknown-label semantics)."""
    import pandas as pd

    X = rng.normal(size=(120, 5)).astype(np.float32)
    y = rng.integers(0, 3, size=120).astype(np.float64)
    y[::7] = np.nan
    df = pd.DataFrame({"features": list(X), "label": y})
    m = (
        UMAP(n_neighbors=8, random_state=2, n_epochs=50)
        .setFeaturesCol("features").setLabelCol("label").fit(df)
    )
    assert np.isfinite(m.embedding_).all()


def test_supervised_umap_regression_target_rejected(rng):
    import pandas as pd

    X = rng.normal(size=(60, 4)).astype(np.float32)
    y = rng.normal(size=60)
    df = pd.DataFrame({"features": list(X), "label": y})
    est = (
        UMAP(n_neighbors=5, target_metric="euclidean")
        .setFeaturesCol("features").setLabelCol("label")
    )
    with pytest.raises(ValueError, match="target_metric"):
        est.fit(df)


# ---------------------------------------------------------------------------
# Metric zoo (ops/distances.py — the full cuML metric list; jaccard, which
# cuML limits to sparse inputs, runs on the same tiled kernel here)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "metric,kw",
    [("manhattan", {}), ("chebyshev", {}), ("canberra", {}),
     ("minkowski", {"p": 3}), ("hamming", {}), ("jaccard", {})],
)
def test_elementwise_knn_matches_sklearn(rng, metric, kw):
    import jax.numpy as jnp
    from sklearn.neighbors import NearestNeighbors as SkNN

    from spark_rapids_ml_tpu.ops.distances import knn_topk_metric

    X = rng.normal(size=(300, 6)).astype(np.float32)
    if metric in ("hamming", "jaccard"):
        X = (X > 0).astype(np.float32)
    Q = X[:40]
    k = 5
    d, i = knn_topk_metric(
        jnp.asarray(X), jnp.ones((300,), jnp.float32),
        jnp.arange(300, dtype=jnp.int32), jnp.asarray(Q),
        k=k, metric=metric, p=float(kw.get("p", 2.0)),
        qblock=16, iblock=64,  # force real tiling
    )
    sk = SkNN(n_neighbors=k, algorithm="brute", metric=metric,
              p=kw.get("p", 2)).fit(X)
    want_d, _ = sk.kneighbors(Q)
    np.testing.assert_allclose(np.asarray(d), want_d, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("metric", ["correlation", "hellinger"])
def test_matmul_metric_preprocess(rng, metric):
    from scipy.spatial.distance import cdist

    from spark_rapids_ml_tpu.ops.distances import (
        finalize_sqdist, preprocess_rows,
    )

    X = rng.normal(size=(50, 8)).astype(np.float64)
    if metric == "hellinger":
        X = np.abs(X)
    Xp = preprocess_rows(X, metric)
    d2 = (
        (Xp * Xp).sum(1)[:, None] - 2 * Xp @ Xp.T + (Xp * Xp).sum(1)[None, :]
    )
    got = np.asarray(finalize_sqdist(np.maximum(d2, 0), metric))
    if metric == "correlation":
        want = cdist(X, X, metric="correlation")
    else:
        want = cdist(np.sqrt(X), np.sqrt(X), metric="euclidean") / np.sqrt(2)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


def test_umap_manhattan_fit_transform(rng):
    from sklearn.datasets import make_blobs

    X, y = make_blobs(n_samples=600, n_features=8, centers=4, random_state=2)
    X = X.astype(np.float32)
    um = UMAP(n_neighbors=10, n_epochs=50, random_state=0, metric="manhattan")
    model = um.fit(X)
    emb = model._transform_array(X)[model.getOrDefault("outputCol")]
    emb = np.asarray(emb)
    assert emb.shape == (600, 2)
    # blob structure survives: same-cluster points embed closer than
    # cross-cluster on average
    from sklearn.metrics import silhouette_score

    assert silhouette_score(emb, y) > 0.3


def test_umap_minkowski_kwds(rng):
    X = rng.normal(size=(300, 5)).astype(np.float32)
    um = UMAP(n_neighbors=8, n_epochs=20, random_state=0,
              metric="minkowski", metric_kwds={"p": 3})
    model = um.fit(X)
    emb = model._transform_array(X[:10])[model.getOrDefault("outputCol")]
    assert np.asarray(emb).shape == (10, 2)


def test_umap_rejects_unknown_metric():
    with pytest.raises(ValueError):
        UMAP(metric="mahalanobis").fit(np.zeros((30, 3), np.float32))


def test_build_algo_nn_descent_matches_brute(blobs):
    """build_algo='nn_descent' (reference umap.py:362-370) must produce an
    embedding of the same quality class as the brute-force graph."""
    from sklearn.manifold import trustworthiness

    X, _ = blobs
    m_nnd = UMAP(
        n_neighbors=10, random_state=0, n_epochs=100,
        build_algo="nn_descent",
        build_kwds={"nnd_graph_degree": 24, "nnd_max_iterations": 6},
    ).fit(X)
    t = trustworthiness(X, m_nnd.embedding_, n_neighbors=10)
    assert t > 0.95


def test_build_algo_validation(blobs):
    import pytest as _pt

    with _pt.raises(ValueError):
        UMAP(build_algo="hnsw").fit(blobs[0])


def test_build_algo_nn_descent_elementwise_metric_falls_back(blobs):
    # manhattan cannot ride the euclidean NN-descent scorer; the fit must
    # warn and fall back to brute force, not fail
    X, _ = blobs
    m = UMAP(
        n_neighbors=8, random_state=0, n_epochs=50,
        metric="manhattan", build_algo="nn_descent",
    ).fit(X)
    assert m.embedding_.shape == (len(X), 2)


def test_estimator_save_load_roundtrips_build_params(tmp_path, blobs):
    """build_algo/build_kwds survive estimator persistence (JSON param
    metadata, reference _CumlEstimatorWriter core.py:268-307)."""
    est = UMAP(
        n_neighbors=6, build_algo="nn_descent",
        build_kwds={"nnd_graph_degree": 12, "nnd_max_iterations": 4},
    )
    path = str(tmp_path / "umap_est")
    est.save(path)
    loaded = UMAP.load(path)
    assert loaded._tpu_params["build_algo"] == "nn_descent"
    assert loaded._tpu_params["build_kwds"] == {
        "nnd_graph_degree": 12, "nnd_max_iterations": 4,
    }
    X, _ = blobs
    m = loaded.fit(X)
    assert m.embedding_.shape == (len(X), 2)


def test_structured_kernel_matches_generic_first_epoch(rng):
    # the scatter-free TPU kernel and the generic scatter kernel are the
    # same algorithm: bitwise-equal after one epoch (later epochs diverge
    # only by f32 reduction order, which the SGD dynamics amplify)
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import umap as uops

    n, k = 500, 8
    knn = np.stack(
        [rng.choice(n, size=k, replace=False) for _ in range(n)]
    ).astype(np.int32)
    heads = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    tails = jnp.asarray(knn.reshape(-1))
    w = jnp.asarray(rng.uniform(0.1, 1.0, n * k).astype(np.float32))
    emb0 = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
    key = jax.random.PRNGKey(3)
    perm = jnp.argsort(tails)
    out_s, _ = uops._optimize_epoch_chunk_structured(
        emb0, key, tails.reshape(n, k), w.reshape(n, k), perm,
        tails[perm], 0, 1, 50, 1.58, 0.9, 1.0, k, 5, 1.0,
    )
    out_g, _ = uops._optimize_epoch_chunk(
        emb0, key, heads, tails, w, 0, 1, 50, 1.58, 0.9, 1.0, 5, 1.0,
    )
    np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_g))


def test_structured_kernel_full_fit_quality(blobs):
    # force the structured kernel through the whole public fit on CPU and
    # require the same embedding quality bar as the generic kernel
    from spark_rapids_ml_tpu.config import reset_config, set_config

    X, _ = blobs
    set_config(umap_kernel="structured")
    try:
        model = UMAP(n_neighbors=12, random_state=0, n_epochs=150).fit(X)
    finally:
        reset_config()
    t = trustworthiness(X, model.embedding_, n_neighbors=12)
    assert t > 0.85, f"trustworthiness {t}"


def test_umap_kernel_auto_probes_by_measurement(rng):
    """auto mode with enough epochs must time BOTH kernels and commit to
    the faster one (platform heuristics once shipped a 1.7x CPU
    slowdown unmeasured) — and the probe's epochs are real fit epochs, so
    the result must equal a forced run of the winning kernel only when
    the kernels agree; here we just pin the decision bookkeeping."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.config import reset_config, set_config
    from spark_rapids_ml_tpu.ops import umap as uops

    n, k = 400, 6
    knn = np.stack(
        [rng.choice(n, size=k, replace=False) for _ in range(n)]
    ).astype(np.int32)
    heads = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    tails = jnp.asarray(knn.reshape(-1))
    w = jnp.asarray(rng.uniform(0.1, 1.0, n * k).astype(np.float32))
    emb0 = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))

    try:
        set_config(umap_kernel="auto")
        uops.optimize_embedding(emb0, heads, tails, w, 0, 20, 1.58, 0.9, 1.0)
        dec = uops.LAST_KERNEL_DECISION
        assert dec["decided_by"] in (
            "measured", "measured-tie-platform-prior"
        )
        assert dec["kernel"] in ("structured", "generic")
        tg = dec["warm_epoch_sec_generic"]
        ts = dec["warm_epoch_sec_structured"]
        assert tg is not None and ts is not None
        if dec["decided_by"] == "measured":
            want = "structured" if ts < tg else "generic"
            assert dec["kernel"] == want

        # forced modes must skip the probe
        set_config(umap_kernel="generic")
        uops.optimize_embedding(emb0, heads, tails, w, 0, 20, 1.58, 0.9, 1.0)
        assert uops.LAST_KERNEL_DECISION["decided_by"] == "forced"
        assert uops.LAST_KERNEL_DECISION["kernel"] == "generic"

        # too few epochs to amortize a probe: platform prior, no timings
        set_config(umap_kernel="auto")
        uops.optimize_embedding(emb0, heads, tails, w, 0, 4, 1.58, 0.9, 1.0)
        assert uops.LAST_KERNEL_DECISION["decided_by"] == "platform-prior"

        # deterministic (model fits with random_state set): reproducibility
        # outranks the probe — same-seed fits must never diverge because
        # timing noise flipped the kernel
        set_config(umap_kernel="auto")
        out_a = uops.optimize_embedding(
            emb0, heads, tails, w, 0, 20, 1.58, 0.9, 1.0,
            deterministic=True,
        )
        assert (uops.LAST_KERNEL_DECISION["decided_by"]
                == "random-state-platform-prior")
        out_b = uops.optimize_embedding(
            emb0, heads, tails, w, 0, 20, 1.58, 0.9, 1.0,
            deterministic=True,
        )
        np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))

        # non-head-major edge list can never take the structured kernel
        set_config(umap_kernel="auto")
        uops.optimize_embedding(
            emb0, tails, heads, w, 0, 20, 1.58, 0.9, 1.0
        )
        assert uops.LAST_KERNEL_DECISION["decided_by"] == "structure-missing"
        assert uops.LAST_KERNEL_DECISION["kernel"] == "generic"
    finally:
        reset_config()
