#
# The PCA family's own tests: CPU, toy shapes.
#   python -m pytest chipbench/tests -q -p no:cacheprovider
#
import hashlib

import numpy as np
import pytest

from chipbench import datagen, roofline
from chipbench import manifest as mf

LOW_RANK = {"model": "low_rank", "effective_rank": 10, "tail_strength": 0.5}


def test_pca_work_by_hand():
    w = mf.adapter("pca").work(1_000_000, 3_000, 1, {"k": 3})
    assert w["kernels"]["gram"] == {"flops": 2 * 1e6 * 3000 * 3000, "bytes": 1.2e10}
    peaks = roofline.peaks_for("TPU v5 lite")
    seconds, bound = roofline.least_seconds(w["kernels"]["gram"], peaks)
    assert bound == "flops" and seconds == pytest.approx(1.8e13 / 197e12)
    # the eigensolve's least: 4/3 d^3 FLOP (0.18 ms) against one read of d^2 floats
    assert w["fit"][1] == {"flops": 3.6e10, "bytes": 3.6e7, "count": 1}
    assert roofline.fit_least_seconds(w, peaks) == pytest.approx(
        1.8e13 / 197e12 + 3.6e10 / 197e12)
    four = mf.adapter("pca").work(1_000_000, 3_000, 4, {"k": 3})
    assert four["kernels"]["gram"]["bytes"] == 3e9 and four["fit"][1] == w["fit"][1]


def test_pca_limits_each_have_their_readings():
    cfg = mf.cell(mf.load_manifest(), "pca_fit_cached")["config_file"]
    assert set(cfg["limits"]) == set(cfg["limits_why"]) == {
        "mean_gap", "variance_gap", "ratio_gap", "component_gap", "residual"}
    assert cfg["reduced"] == [] and cfg["data"] == LOW_RANK
    assert {"effective_rank, tail_strength", "U", "labels"} <= set(cfg["assumed"])


def test_sign_rule_and_eigen_answer():
    a = mf.adapter("pca")
    flipped = a.flip_signs(np.array([[0.1, -0.9, 0.2], [0.5, 0.4, 0.0]]))
    assert np.array_equal(flipped, [[-0.1, 0.9, -0.2], [0.5, 0.4, 0.0]])
    scatter = np.diag([1.0, 9.0, 4.0]) * 9.0
    got = a.eigen_answer(scatter, np.zeros(3), 10, 2)
    assert np.allclose(got["variance"], [9.0, 4.0]) and np.allclose(got["ratio"], [9 / 14, 4 / 14])
    assert np.allclose(got["components"], [[0, 1, 0], [0, 0, 1]])


# -- the data model: chipbench/data_models/low_rank.py --------------------------

@pytest.mark.parametrize("data,says", [
    ({"model": "low_rank"}, "lacks ['effective_rank', 'tail_strength']"),
    ({"model": "low_rank", "effective_rank": 10}, "lacks ['tail_strength']"),
    (dict(LOW_RANK, effective_rank=0), "effective_rank 0"),
    (dict(LOW_RANK, tail_strength=1.5), "tail_strength 1.5"),
    (dict(LOW_RANK, effective_rank="wide"), "unreadable"),
])
def test_low_rank_check_refuses_a_bad_block(data, says):
    assert any(says in p for p in mf.data_problems(data)), mf.data_problems(data)
    with pytest.raises(ValueError, match="data model"):
        datagen.make_rows(None, 1024, 8, 1, data)
    assert mf.data_problems(LOW_RANK) == []


def test_low_rank_profile_is_sklearns():
    from sklearn.datasets import make_low_rank_matrix

    s = mf.data_model("low_rank").singular_profile(40, LOW_RANK)
    X = make_low_rank_matrix(200, 40, effective_rank=10, tail_strength=0.5, random_state=0)
    assert np.allclose(np.linalg.svd(X, compute_uv=False), s, atol=1e-12)
    # the spectrum the configuration's `assumed` block quotes
    s2 = mf.data_model("low_rank").singular_profile(3000, LOW_RANK) ** 2
    assert np.allclose(s2[:4], [1.0, 0.980, 0.942, 0.888], atol=5e-4)
    assert s2[13] == pytest.approx(0.282, abs=1e-3) and s2.sum() == pytest.approx(18.76, abs=0.01)


def _spectrum(X):
    X = np.asarray(X, np.float64)
    return np.linalg.eigvalsh(X.T @ X / len(X))[::-1]


@pytest.mark.parametrize("path", ["device-1", "device-4", "host"])
def test_low_rank_twins_agree_in_spectrum(path, monkeypatch):
    """Rows on the devices and their numpy twin are other streams of one
    distribution: zero mean, covariance V diag(s^2) V^T, whose eigenvalues a
    sample of 20,000 rows of 24 columns shows to a few per cent."""
    import jax

    from spark_rapids_ml_tpu.parallel import get_mesh

    monkeypatch.setattr(datagen, "BLOCK_ROWS", 500)
    rows, cols, seed = 20_000, 24, 2**31 + 21
    if path == "host":
        X, y = datagen.host_rows(rows, cols, seed, LOW_RANK, workers=3)
    else:
        n_dev = int(path.split("-")[1])
        if len(jax.devices()) < n_dev:
            pytest.skip(f"needs {n_dev} devices")
        X, y, w = datagen.make_rows(get_mesh(n_dev), rows, cols, seed, LOW_RANK, 500)
        assert X.dtype == np.float32 and float(w.min()) == float(w.max()) == 1.0
    X, y = np.asarray(X), np.asarray(y)
    assert X.shape == (rows, cols) and not y.any()
    want = mf.data_model("low_rank").singular_profile(cols, LOW_RANK) ** 2
    assert np.allclose(_spectrum(X), want, rtol=0.06)
    assert np.abs(X.mean(axis=0)).max() < 4 * np.sqrt(want[0] / rows)
    assert len(np.unique(X[:, 0])) > 0.99 * rows  # no block drawn twice


GOLDEN_SEED = 2**31 + 7
# sha256 (first 16 hex digits) of the bytes of X, as
# `datagen.make_rows(get_mesh(n), 2048, 24, GOLDEN_SEED, LOW_RANK, 128)` and
# `datagen.host_rows(2000, 24, GOLDEN_SEED, LOW_RANK, workers=3)` at BLOCK_ROWS
# 256 gave them when the model was added (jax 0.9.0, the CPU backend): the
# same seed draws the same bits, so the cell's readings stay the limits'.
GOLDEN = {
    ("device", 1): "b033a3ae544e8cc8",
    ("device", 4): "38604b1092ecf6bd",
    ("host", 0): "d8804564ae3f64b9",
}


def _digest(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(a)).tobytes()).hexdigest()[:16]


def _golden_rows(path, n_dev, monkeypatch):
    import jax

    from spark_rapids_ml_tpu.parallel import get_mesh

    if path == "device":
        if len(jax.devices()) < n_dev:
            pytest.skip(f"needs {n_dev} devices")
        return datagen.make_rows(get_mesh(n_dev), 2048, 24, GOLDEN_SEED, LOW_RANK, 128)[0]
    monkeypatch.setattr(datagen, "BLOCK_ROWS", 256)  # a last block of 208 rows
    return datagen.host_rows(2000, 24, GOLDEN_SEED, LOW_RANK, workers=3)[0]


@pytest.mark.parametrize("path,n_dev", sorted(GOLDEN))
def test_low_rank_rows_are_the_same_bits_per_seed(path, n_dev, monkeypatch):
    assert _digest(_golden_rows(path, n_dev, monkeypatch)) == GOLDEN[path, n_dev]
