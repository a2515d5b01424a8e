#
# Fused Pallas distance+top-k kernel (ops/pallas_knn.py) — exactness vs the
# XLA materialize-then-top_k kernels, tail/padding semantics, and the
# config-flag dispatch.  On the CPU test mesh the kernel runs in Pallas
# interpret mode; on a real TPU the same tests exercise the compiled path.
#
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.config import reset_config, set_config
from spark_rapids_ml_tpu.ops.knn import knn_topk_blocked
from spark_rapids_ml_tpu.ops.pallas_knn import (
    fused_topk_sqdist,
    knn_topk_fused,
    pallas_knn_eligible,
)


@pytest.fixture(autouse=True)
def _clean_config():
    reset_config()
    yield
    reset_config()


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@pytest.mark.parametrize("n,d,q,k", [(700, 24, 130, 7), (64, 8, 64, 5),
                                     (1500, 40, 33, 20)])
def test_fused_matches_xla(n, d, q, k):
    rng = np.random.default_rng(n + q)
    X = rng.normal(size=(n, d)).astype(np.float32)
    Q = rng.normal(size=(q, d)).astype(np.float32)
    valid = np.ones(n, np.float32)
    valid[-max(1, n // 16):] = 0.0
    ids = np.arange(n, dtype=np.int32)
    d2p, ip = fused_topk_sqdist(
        jnp.asarray(X), jnp.asarray(valid), jnp.asarray(Q), k,
        bq=64, bn=128, interpret=_interpret(),
    )
    d2r, ir = knn_topk_blocked(
        jnp.asarray(X), jnp.asarray(valid), jnp.asarray(ids),
        jnp.asarray(Q), k=k,
    )
    np.testing.assert_allclose(np.asarray(d2p), np.asarray(d2r), atol=1e-4)
    # identical neighbor sets; order can swap only between exact ties
    assert (np.asarray(ip) == np.asarray(ir)).mean() > 0.999


def test_fused_tail_when_k_exceeds_valid():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(300, 6)).astype(np.float32)
    Q = rng.normal(size=(10, 6)).astype(np.float32)
    valid = np.zeros(300, np.float32)
    valid[:4] = 1.0
    d2, idx = fused_topk_sqdist(
        jnp.asarray(X), jnp.asarray(valid), jnp.asarray(Q), 7,
        bq=8, bn=128, interpret=_interpret(),
    )
    idx = np.asarray(idx)
    d2 = np.asarray(d2)
    assert set(idx[0, :4]) == {0, 1, 2, 3}
    assert (idx[:, 4:] == -1).all()
    assert np.isinf(d2[:, 4:]).all()
    assert np.isfinite(d2[:, :4]).all()


def test_fused_global_id_mapping():
    rng = np.random.default_rng(1)
    X = rng.normal(size=(200, 12)).astype(np.float32)
    Q = X[:15]  # self-queries: nearest id must be the row's own global id
    valid = np.ones(200, np.float32)
    gids = (np.arange(200, dtype=np.int32) * 3 + 100)  # non-contiguous
    d2, ids = knn_topk_fused(
        jnp.asarray(X), jnp.asarray(valid), jnp.asarray(gids),
        jnp.asarray(Q), k=3,
    )
    assert (np.asarray(ids)[:, 0] == gids[:15]).all()
    np.testing.assert_allclose(np.asarray(d2)[:, 0], 0.0, atol=1e-4)


def test_eligibility_guards():
    """Shape/dtype guards the dispatch (knn_topk_single) applies before
    any mode/probe logic: the fused kernel may never see rows too wide
    for VMEM or f64 inputs (it computes in f32, which would silently
    change the results the XLA path preserves)."""
    assert pallas_knn_eligible(64)
    assert not pallas_knn_eligible(8192)  # VMEM guard
    assert pallas_knn_eligible(64, np.float32)
    assert not pallas_knn_eligible(64, np.float64)


def test_measured_auto_decision(monkeypatch):
    """pallas_knn=auto on a probe backend measures both kernels once per
    shape bucket, commits to the faster (the fused kernel has measured
    0.21-0.38x XLA on chip: auto must never pin a fit to the slower
    kernel), and reuses
    the cached verdict without re-probing."""
    from spark_rapids_ml_tpu.ops import knn as knn_mod
    from spark_rapids_ml_tpu.ops.knn import knn_topk_single

    monkeypatch.setattr(knn_mod, "_AUTO_PROBE_BACKENDS",
                        (jax.default_backend(),))
    knn_mod._KERNEL_DECISION_CACHE.clear()
    set_config(pallas_knn="auto")
    rng = np.random.default_rng(11)
    X = rng.normal(size=(96, 8)).astype(np.float32)
    Q = rng.normal(size=(16, 8)).astype(np.float32)
    valid = np.ones(96, np.float32)
    ids = np.arange(96, dtype=np.int32)
    args = (jnp.asarray(X), jnp.asarray(valid), jnp.asarray(ids),
            jnp.asarray(Q))
    d2, i = knn_topk_single(*args, k=4)
    dec = dict(knn_mod.LAST_KERNEL_DECISION)
    assert dec["decided_by"] in (
        "measured", "measured-tie-platform-prior", "pallas-error"
    )
    assert dec["kernel"] in ("xla", "pallas")
    assert dec["warm_sec_xla"] is not None
    # probe results are REAL results: exact match with the XLA kernel
    d2r, ir = knn_topk_blocked(*args, k=4)
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d2r), atol=1e-4)
    assert (np.asarray(i) == np.asarray(ir)).mean() > 0.99
    # second call at the same shape bucket: cached verdict, no re-probe
    knn_topk_single(*args, k=4)
    assert knn_mod.LAST_KERNEL_DECISION["decided_by"] == "measured-cached"


def test_measured_auto_decision_sliced_probe(monkeypatch):
    """Query sets past the probe bound measure on a `_QUERY_BLOCK` slice
    (bounded probe cost), then dispatch the winner over the FULL query
    set — results must match the straight XLA kernel exactly."""
    from spark_rapids_ml_tpu.ops import knn as knn_mod
    from spark_rapids_ml_tpu.ops.knn import knn_topk_single

    monkeypatch.setattr(knn_mod, "_AUTO_PROBE_BACKENDS",
                        (jax.default_backend(),))
    monkeypatch.setattr(knn_mod, "_QUERY_BLOCK", 8)
    knn_mod._KERNEL_DECISION_CACHE.clear()
    set_config(pallas_knn="auto")
    rng = np.random.default_rng(13)
    X = rng.normal(size=(80, 8)).astype(np.float32)
    Q = rng.normal(size=(32, 8)).astype(np.float32)  # > the probe bound
    valid = np.ones(80, np.float32)
    ids = np.arange(80, dtype=np.int32)
    args = (jnp.asarray(X), jnp.asarray(valid), jnp.asarray(ids),
            jnp.asarray(Q))
    d2, i = knn_topk_single(*args, k=4)
    assert d2.shape == (32, 4)  # full queries answered, not the slice
    dec = dict(knn_mod.LAST_KERNEL_DECISION)
    assert dec["decided_by"] in (
        "measured", "measured-tie-platform-prior", "pallas-error"
    )
    d2r, ir = knn_topk_blocked(*args, k=4)
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d2r), atol=1e-4)
    assert (np.asarray(i) == np.asarray(ir)).mean() > 0.99


def test_fused_runtime_failure_invalidates_cached_verdict(monkeypatch):
    """A cached use_pallas=True verdict (won on the bounded probe slice)
    must be overwritten when the full-shape fused dispatch fails — else
    every later call in the bucket re-pays the failed Mosaic compile
    before falling back."""
    from spark_rapids_ml_tpu.ops import knn as knn_mod
    from spark_rapids_ml_tpu.ops import pallas_knn as pk
    from spark_rapids_ml_tpu.ops.knn import knn_topk_single

    monkeypatch.setattr(knn_mod, "_AUTO_PROBE_BACKENDS",
                        (jax.default_backend(),))
    knn_mod._KERNEL_DECISION_CACHE.clear()
    set_config(pallas_knn="auto")
    rng = np.random.default_rng(14)
    X = rng.normal(size=(64, 8)).astype(np.float32)
    Q = rng.normal(size=(16, 8)).astype(np.float32)
    valid = np.ones(64, np.float32)
    ids = np.arange(64, dtype=np.int32)
    key = knn_mod._decision_key(X, Q, 3)
    knn_mod._KERNEL_DECISION_CACHE[key] = True  # probe said pallas

    def boom(*a, **kw):
        raise RuntimeError("Mosaic lowering failed at the full shape")

    monkeypatch.setattr(pk, "knn_topk_fused", boom)
    args = (jnp.asarray(X), jnp.asarray(valid), jnp.asarray(ids),
            jnp.asarray(Q))
    d2, i = knn_topk_single(*args, k=3)  # must not raise
    assert knn_mod._KERNEL_DECISION_CACHE[key] is False
    assert knn_mod.LAST_KERNEL_DECISION["decided_by"] == "pallas-fallback"
    d2r, ir = knn_topk_blocked(*args, k=3)
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d2r), atol=1e-5)
    assert np.array_equal(np.asarray(i), np.asarray(ir))


def test_auto_off_probe_backend_keeps_xla(monkeypatch):
    """auto on a NON-probe backend (the CPU default) never runs the
    interpreter probe — the XLA kernel dispatches outright."""
    from spark_rapids_ml_tpu.ops import knn as knn_mod
    from spark_rapids_ml_tpu.ops.knn import knn_topk_single

    monkeypatch.setattr(knn_mod, "_AUTO_PROBE_BACKENDS", ())
    knn_mod._KERNEL_DECISION_CACHE.clear()
    set_config(pallas_knn="auto")
    rng = np.random.default_rng(12)
    X = rng.normal(size=(64, 6)).astype(np.float32)
    valid = np.ones(64, np.float32)
    ids = np.arange(64, dtype=np.int32)
    knn_topk_single(jnp.asarray(X), jnp.asarray(valid), jnp.asarray(ids),
                    jnp.asarray(X[:8]), k=3)
    assert knn_mod.LAST_KERNEL_DECISION["kernel"] == "xla"
    assert knn_mod.LAST_KERNEL_DECISION["decided_by"] == "config"
    assert not knn_mod._KERNEL_DECISION_CACHE


def test_exact_knn_end_to_end_parity():
    """NearestNeighbors results are identical with the fused kernel forced
    on (interpret mode on CPU) and forced off."""
    import pandas as pd

    from spark_rapids_ml_tpu.knn import NearestNeighbors

    rng = np.random.default_rng(7)
    X = rng.normal(size=(400, 16)).astype(np.float32)
    Q = rng.normal(size=(25, 16)).astype(np.float32)
    item_df = pd.DataFrame({"features": list(X), "id": np.arange(400)})
    qdf = pd.DataFrame({"features": list(Q),
                        "id": np.arange(25) + 1000})

    outs = {}
    for mode in ("off", "on"):
        set_config(pallas_knn=mode)
        m = NearestNeighbors(k=5, num_workers=1).setIdCol("id").fit(item_df)
        _, _, knn_df = m.kneighbors(qdf)
        outs[mode] = knn_df
    a, b = outs["off"], outs["on"]
    ia = np.stack([np.asarray(r) for r in a["indices"]])
    ib = np.stack([np.asarray(r) for r in b["indices"]])
    # near-ties at the k boundary may legitimately swap between the two
    # kernels' rounding (compiled MXU vs one-fusion XLA); sets must agree
    assert (ia == ib).mean() > 0.99
    assert all(set(ra) == set(rb) for ra, rb in zip(ia, ib))
    da = np.stack([np.asarray(r) for r in a["distances"]])
    db = np.stack([np.asarray(r) for r in b["distances"]])
    np.testing.assert_allclose(da, db, atol=1e-3)


def test_umap_graph_dispatch_parity():
    """umap_knn_graph (the UMAP fit/transform kNN) routes through the fused
    kernel when enabled and returns identical graphs."""
    from spark_rapids_ml_tpu.ops.distances import umap_knn_graph

    rng = np.random.default_rng(3)
    X = rng.normal(size=(350, 10)).astype(np.float32)
    valid = np.ones(350, np.float32)
    ids = np.arange(350, dtype=np.int32)
    outs = {}
    for mode in ("off", "on"):
        set_config(pallas_knn=mode)
        d, i = umap_knn_graph(
            jnp.asarray(X), jnp.asarray(valid), jnp.asarray(ids),
            jnp.asarray(X), k=8, metric="euclidean",
        )
        outs[mode] = (np.asarray(d), np.asarray(i))
    # sqrt amplifies the f32 cancellation noise of ~0 self-distances to
    # ~2e-3 (and the two kernels associate the identity differently there)
    np.testing.assert_allclose(outs["off"][0], outs["on"][0], atol=5e-3)
    assert (outs["off"][1] == outs["on"][1]).mean() > 0.999
