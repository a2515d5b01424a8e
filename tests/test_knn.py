#
# Exact k-NN tests — the analog of reference tests/test_nearest_neighbors.py:
# equivalence vs sklearn brute force across mesh sizes, feature layouts, and
# id columns.
#
import numpy as np
import pandas as pd
import pytest
from sklearn.neighbors import NearestNeighbors as SkNN

from spark_rapids_ml_tpu.knn import NearestNeighbors, NearestNeighborsModel


def _make_data(rng, n_items=80, n_queries=23, d=8):
    items = rng.normal(size=(n_items, d)).astype(np.float32)
    queries = rng.normal(size=(n_queries, d)).astype(np.float32)
    return items, queries


def test_kneighbors_matches_sklearn(rng, num_workers):
    items, queries = _make_data(rng)
    k = 7
    model = NearestNeighbors(k=k, num_workers=num_workers).fit(items)
    _, _, knn_df = model.kneighbors(queries)
    got_idx = np.stack(knn_df["indices"].to_numpy())
    got_dist = np.stack(knn_df["distances"].to_numpy())

    sk = SkNN(n_neighbors=k, algorithm="brute").fit(items)
    want_dist, want_idx = sk.kneighbors(queries)

    np.testing.assert_allclose(got_dist, want_dist, rtol=1e-4, atol=1e-4)
    # index ties can differ; distances must agree exactly per slot
    same = got_idx == want_idx
    tie = np.isclose(got_dist, want_dist, rtol=1e-4, atol=1e-4)
    assert np.all(same | tie)


def test_kneighbors_pandas_and_id_col(rng):
    items, queries = _make_data(rng, n_items=30, n_queries=5, d=4)
    item_df = pd.DataFrame(
        {"features": list(items), "id": np.arange(100, 130)}
    )
    query_df = pd.DataFrame({"features": list(queries)})
    model = (
        NearestNeighbors(k=3)
        .setFeaturesCol("features")
        .setIdCol("id")
        .fit(item_df)
    )
    _, _, knn_df = model.kneighbors(query_df)
    # ids come from the user id column, offset by 100
    all_ids = np.concatenate(knn_df["indices"].to_numpy())
    assert all_ids.min() >= 100 and all_ids.max() < 130

    sk = SkNN(n_neighbors=3, algorithm="brute").fit(items)
    _, want_idx = sk.kneighbors(queries)
    got_idx = np.stack(knn_df["indices"].to_numpy()) - 100
    assert np.array_equal(got_idx, want_idx)


def test_multi_col_features(rng):
    items, queries = _make_data(rng, n_items=20, n_queries=4, d=3)
    cols = ["c0", "c1", "c2"]
    item_df = pd.DataFrame(items, columns=cols)
    query_df = pd.DataFrame(queries, columns=cols)
    model = NearestNeighbors(k=2).setFeaturesCols(cols).fit(item_df)
    _, _, knn_df = model.kneighbors(query_df)
    sk = SkNN(n_neighbors=2, algorithm="brute").fit(items)
    _, want_idx = sk.kneighbors(queries)
    assert np.array_equal(np.stack(knn_df["indices"].to_numpy()), want_idx)


def test_exact_nearest_neighbors_join(rng):
    items, queries = _make_data(rng, n_items=15, n_queries=3, d=4)
    model = NearestNeighbors(k=2).fit(items)
    join_df = model.exactNearestNeighborsJoin(queries, distCol="dc")
    assert list(join_df.columns) == ["item_id", "query_id", "dc"]
    assert len(join_df) == 3 * 2


def test_k_exceeds_items_raises(rng):
    items, queries = _make_data(rng, n_items=4, n_queries=2, d=3)
    model = NearestNeighbors(k=10).fit(items)
    with pytest.raises(ValueError, match="exceeds"):
        model.kneighbors(queries)


def test_transform_unsupported(rng):
    items, _ = _make_data(rng, n_items=5, n_queries=1, d=2)
    model = NearestNeighbors(k=2).fit(items)
    with pytest.raises(NotImplementedError):
        model.transform(items)


def test_save_load(tmp_path, rng):
    items, queries = _make_data(rng, n_items=25, n_queries=6, d=5)
    model = NearestNeighbors(k=4).fit(items)
    path = str(tmp_path / "nn_model")
    model.save(path)
    loaded = NearestNeighborsModel.load(path)
    _, _, a = model.kneighbors(queries)
    _, _, b = loaded.kneighbors(queries)
    np.testing.assert_allclose(
        np.stack(a["distances"].to_numpy()), np.stack(b["distances"].to_numpy())
    )
    assert np.array_equal(
        np.stack(a["indices"].to_numpy()), np.stack(b["indices"].to_numpy())
    )


def test_single_dispatch_routes_big_n_to_coltiled(monkeypatch):
    """knn_topk_single must route to the double-tiled kernel once one
    (qblock, n) blocked tile would exceed the byte limit — at 10M items
    a blocked tile is 40 GB and fails TPU compile RESOURCE_EXHAUSTED
    (BASELINE-scale ANN run).  Forcing a tiny limit must keep results
    exact-equivalent."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import knn as knn_ops

    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.standard_normal((2000, 8), dtype=np.float32))
    Q = jnp.asarray(rng.standard_normal((100, 8), dtype=np.float32))
    v = jnp.ones((2000,), jnp.float32)
    ids = jnp.arange(2000, dtype=jnp.int32)
    d_ref, i_ref = knn_ops.knn_topk_blocked(X, v, ids, Q, k=5)
    calls = []
    real = knn_ops.knn_topk_coltiled

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(knn_ops, "knn_topk_coltiled", spy)
    monkeypatch.setattr(knn_ops, "_BLOCKED_TILE_LIMIT_BYTES", 1024)
    d, i = knn_ops.knn_topk_single(X, v, ids, Q, k=5)
    assert calls, "big-n dispatch did not route to the coltiled kernel"
    np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))


def test_coltiled_kernel_matches_blocked():
    """knn_topk_coltiled (sort-narrowing column-tiled merge) must be
    exact-equivalent to knn_topk_blocked, including invalid-item masking
    and uneven tail tiles."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.knn import knn_topk_blocked, knn_topk_coltiled

    rng = np.random.default_rng(7)
    X = jnp.asarray(rng.standard_normal((3001, 12), dtype=np.float32))
    Q = jnp.asarray(rng.standard_normal((257, 12), dtype=np.float32))
    v = jnp.ones((3001,), jnp.float32).at[50:80].set(0.0)
    ids = jnp.arange(3001, dtype=jnp.int32)
    d1, i1 = knn_topk_blocked(X, v, ids, Q, k=7)
    d2, i2 = knn_topk_coltiled(X, v, ids, Q, k=7, block=100, cblock=777)
    np.testing.assert_allclose(
        np.asarray(d1), np.asarray(d2), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    # k > n_valid edge: unfillable tail slots are id -1 in BOTH kernels
    # (the documented contract; blocked used to leak invalid-item ids)
    Xs = X[:5]
    vs = jnp.asarray([1.0, 1.0, 0.0, 0.0, 0.0], jnp.float32)
    ib, pb = knn_topk_blocked(Xs, vs, ids[:5], Q[:3], k=4)
    ic, pc = knn_topk_coltiled(Xs, vs, ids[:5], Q[:3], k=4, cblock=3)
    np.testing.assert_array_equal(np.asarray(pb)[:, 2:], -1)
    np.testing.assert_array_equal(np.asarray(pb), np.asarray(pc))


# -- the single-device kernel against a float64 brute force ----------------


def _brute_force(X, valid, ids, Q, k):
    """float64 squared distances over the valid items, the k nearest in
    order; unfillable slots are (inf, -1)."""
    d2 = ((Q[:, None, :].astype(np.float64) - X[None, :, :]) ** 2).sum(-1)
    d2[:, valid <= 0] = np.inf
    pos = np.argsort(d2, axis=1, kind="stable")[:, :k]
    dist = np.take_along_axis(d2, pos, axis=1)
    return dist, np.where(np.isinf(dist), -1, ids[pos])


@pytest.mark.parametrize("n,d,q,k", [(700, 24, 130, 7), (64, 8, 64, 5),
                                     (1500, 40, 33, 20)])
@pytest.mark.parametrize("kernel", ["blocked", "coltiled"])
def test_kernels_match_brute_force(kernel, n, d, q, k):
    """Both single-device kernels, with padded items masked; the
    column-tiled one with column tiles that leave a ragged tail."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import knn as knn_ops

    rng = np.random.default_rng(n + q)
    X = rng.normal(size=(n, d)).astype(np.float32)
    Q = rng.normal(size=(q, d)).astype(np.float32)
    valid = np.ones(n, np.float32)
    valid[-max(1, n // 16):] = 0.0
    ids = np.arange(n, dtype=np.int32)
    args = (jnp.asarray(X), jnp.asarray(valid), jnp.asarray(ids),
            jnp.asarray(Q))
    if kernel == "blocked":
        d2, idx = knn_ops.knn_topk_blocked(*args, k=k, block=64)
    else:
        d2, idx = knn_ops.knn_topk_coltiled(
            *args, k=k, block=64, cblock=max(k, n // 3 + 1)
        )
    want_d2, want_idx = _brute_force(X, valid, ids, Q, k)
    np.testing.assert_allclose(np.asarray(d2), want_d2, rtol=1e-4, atol=1e-4)
    # identical neighbor sets; order can swap only between exact ties
    assert (np.asarray(idx) == want_idx).mean() > 0.999


def test_single_dispatch_keeps_blocked_under_the_tile_limit(monkeypatch):
    """Under the tile limit knn_topk_single runs the blocked kernel and
    never the column-tiled one."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import knn as knn_ops

    def refuse(*a, **kw):
        raise AssertionError("small n routed to the coltiled kernel")

    monkeypatch.setattr(knn_ops, "knn_topk_coltiled", refuse)
    rng = np.random.default_rng(5)
    X = rng.normal(size=(500, 8)).astype(np.float32)
    Q = rng.normal(size=(40, 8)).astype(np.float32)
    valid = np.ones(500, np.float32)
    ids = np.arange(500, dtype=np.int32)
    args = (jnp.asarray(X), jnp.asarray(valid), jnp.asarray(ids),
            jnp.asarray(Q))
    d2, idx = knn_ops.knn_topk_single(*args, k=6)
    d2_ref, idx_ref = knn_ops.knn_topk_blocked(*args, k=6)
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(d2_ref))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))


def test_blocked_tail_when_k_exceeds_valid():
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.knn import knn_topk_blocked

    rng = np.random.default_rng(0)
    X = rng.normal(size=(300, 6)).astype(np.float32)
    Q = rng.normal(size=(10, 6)).astype(np.float32)
    valid = np.zeros(300, np.float32)
    valid[:4] = 1.0
    ids = np.arange(300, dtype=np.int32)
    d2, idx = knn_topk_blocked(
        jnp.asarray(X), jnp.asarray(valid), jnp.asarray(ids),
        jnp.asarray(Q), k=7, block=8,
    )
    idx, d2 = np.asarray(idx), np.asarray(d2)
    want_d2, want_idx = _brute_force(X, valid, ids, Q, 7)
    assert all(set(row[:4]) == {0, 1, 2, 3} for row in idx)
    assert (idx[:, 4:] == -1).all()
    assert np.isinf(d2[:, 4:]).all()
    np.testing.assert_allclose(d2[:, :4], want_d2[:, :4], rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_array_equal(idx, want_idx)


def test_blocked_global_id_mapping():
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.knn import knn_topk_blocked

    rng = np.random.default_rng(1)
    X = rng.normal(size=(200, 12)).astype(np.float32)
    Q = X[:15]  # self-queries: nearest id must be the row's own global id
    valid = np.ones(200, np.float32)
    gids = (np.arange(200, dtype=np.int32) * 3 + 100)  # non-contiguous
    d2, ids = knn_topk_blocked(
        jnp.asarray(X), jnp.asarray(valid), jnp.asarray(gids),
        jnp.asarray(Q), k=3,
    )
    assert (np.asarray(ids)[:, 0] == gids[:15]).all()
    np.testing.assert_allclose(np.asarray(d2)[:, 0], 0.0, atol=1e-4)
    _, want_ids = _brute_force(X, valid, gids, Q, 3)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)


def test_exact_knn_end_to_end_parity():
    """NearestNeighbors with an id column on one device returns sklearn's
    brute-force neighbours and distances."""
    rng = np.random.default_rng(7)
    X = rng.normal(size=(400, 16)).astype(np.float32)
    Q = rng.normal(size=(25, 16)).astype(np.float32)
    item_df = pd.DataFrame({"features": list(X), "id": np.arange(400)})
    qdf = pd.DataFrame({"features": list(Q),
                        "id": np.arange(25) + 1000})
    m = NearestNeighbors(k=5, num_workers=1).setIdCol("id").fit(item_df)
    _, _, knn_df = m.kneighbors(qdf)
    got_idx = np.stack([np.asarray(r) for r in knn_df["indices"]])
    got_dist = np.stack([np.asarray(r) for r in knn_df["distances"]])
    want_dist, want_idx = (
        SkNN(n_neighbors=5, algorithm="brute").fit(X).kneighbors(Q)
    )
    # near-ties at the k boundary may swap under f32 rounding; sets agree
    assert (got_idx == want_idx).mean() > 0.99
    assert all(set(a) == set(b) for a, b in zip(got_idx, want_idx))
    np.testing.assert_allclose(got_dist, want_dist, atol=1e-3)


def test_umap_graph_dispatch_parity():
    """umap_knn_graph (the UMAP fit/transform kNN) on one device routes
    through knn_topk_single and returns sklearn's brute-force graph."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.distances import umap_knn_graph

    rng = np.random.default_rng(3)
    X = rng.normal(size=(350, 10)).astype(np.float32)
    valid = np.ones(350, np.float32)
    ids = np.arange(350, dtype=np.int32)
    d, i = umap_knn_graph(
        jnp.asarray(X), jnp.asarray(valid), jnp.asarray(ids),
        jnp.asarray(X), k=8, metric="euclidean",
    )
    want_d, want_i = SkNN(n_neighbors=8, algorithm="brute").fit(X).kneighbors(X)
    # sqrt amplifies the f32 cancellation noise of ~0 self-distances to
    # ~2e-3
    np.testing.assert_allclose(np.asarray(d), want_d, atol=5e-3)
    assert (np.asarray(i) == want_i).mean() > 0.999
