#
# The multinomial logistic family's own tests: CPU, toy shapes.
#   python -m pytest chipbench/tests -q -p no:cacheprovider
#
import numpy as np
import pytest

from chipbench import datagen
from chipbench import manifest as mf
from chipbench import roofline

CELL = "mlogreg_fit_cached"
DATA = {"model": "softmax_labels", "classes": 10, "logit_std": 4.0}
PARAMS = {"maxIter": 20, "tol": 1e-30, "regParam": 1e-5, "elasticNetParam": 0.0,
          "standardization": False, "family": "auto", "numClasses": 10}


def test_mlogreg_work_by_hand():
    w = mf.adapter("mlogreg").work(1_281_167, 2_048, 1, {"maxIter": 20, "numClasses": 1000})
    ev = w["kernels"]["lbfgs_eval"]
    assert ev == {"flops": 4.0 * 1_281_167 * 2_048 * 1000, "bytes": 1_281_167 * 2_048 * 4.0}
    assert [p["count"] for p in w["fit"]] == [21]
    seconds, bound = roofline.least_seconds(ev, roofline.peaks_for("TPU v5 lite"))
    # FLOP bound it: 53.3 ms an evaluation at the bf16 peak against 12.8 ms of bytes
    assert bound == "flops" and seconds == pytest.approx(0.0533, abs=2e-4)


def test_manifest_has_the_new_cell_on_one_chip():
    manifest = mf.load_manifest()
    assert mf.problems(manifest) == []
    cells = manifest["workloads"]
    assert len(cells) == 9 and sum(1 for w in cells if w["chips"] == 4) == 1
    c = mf.cell(manifest, CELL)
    cfg = c["config_file"]
    assert c["chips"] == 1 and cfg["adapter"] == "mlogreg"
    # the class count the adapter's work reads is the one the data model draws
    assert cfg["params"]["numClasses"] == cfg["data"]["classes"] == 1000
    assert (cfg["rows"], cfg["cols"]) == (1_281_167, 2_048)
    assert set(cfg["reduced"]) == set(cfg["reduced_why"]) == {"maxIter"}
    for m in ("mlogreg_evals_per_fit", "mlogreg_eval_ms", "lbfgs_eval_roofline", "fit_mfu"):
        assert m in [x["name"] for x in mf.metrics_of(manifest, "per_layer", CELL)]


@pytest.mark.parametrize("path", ["device", "host"])
def test_softmax_labels_are_seed_determined_and_in_range(path):
    import jax

    from spark_rapids_ml_tpu.parallel import get_mesh

    def draw(seed):
        if path == "host":
            return datagen.host_rows(3_000, 16, seed, DATA)
        X, y, _ = datagen.make_rows(get_mesh(1), 3_000, 16, seed, DATA, block_rows=500)
        return jax.device_get((X, y))

    X, y = draw(7)
    X2, y2 = draw(7)
    X3, y3 = draw(8)
    assert np.array_equal(X, X2) and np.array_equal(y, y2)
    assert not np.array_equal(X, X3)
    assert y.min() >= 0 and y.max() < 10 and np.array_equal(y, np.round(y))
    # a draw from a softmax, not its argmax: every class turns up, none holds most rows
    counts = np.bincount(np.asarray(y, np.int64), minlength=10)
    assert counts.min() > 0 and counts.max() < 0.5 * len(y)


def _rows(seed=3, n=2_000, d=16):
    from spark_rapids_ml_tpu.parallel import get_mesh

    X, y, w = datagen.make_rows(get_mesh(1), n, d, seed, DATA, block_rows=500)
    return X, y, w


def test_reference_agrees_with_the_program_and_its_control_does_not(monkeypatch):
    """At a small size (100 classes, 80 rows a class, the configuration's
    depth): the program's blocked evaluation (its plan forced on the CPU)
    and the adapter's reference agree inside the configuration's limits; the
    reference with one bfloat16 pass a product does not.  (With 10 classes
    the control too stays inside: the separation grows with the classes.)"""
    from spark_rapids_ml_tpu.data import DeviceDataset
    from spark_rapids_ml_tpu.ops import logistic
    from spark_rapids_ml_tpu.parallel import get_mesh

    ad = mf.adapter("mlogreg")
    cfg = mf.cell(mf.load_manifest(), CELL)["config_file"]
    limits = cfg["limits"]
    params = dict(cfg["params"], numClasses=100)
    X, y, w = datagen.make_rows(get_mesh(1), 8_000, 64, 3, dict(DATA, classes=100),
                                block_rows=500)
    monkeypatch.setattr(logistic, "_on_tpu", lambda X: True)
    model = ad.build(params, 1).fit(DeviceDataset(X.sharding.mesh, X, X.shape[0], y=y, weight=w))
    ans = ad.answer(model)
    assert ans["fact"]["evaluation"] == "softmax_blocks" and ans["fact"]["classes"] == 100
    ref = ad.reference(X, y, params)
    got = ad.compare(ans, ref)
    assert all(got[k] <= limits[k] for k in limits), got
    assert ans["fact"]["evaluations"] == ref["n_evals"]
    low = ad.compare(ad.reference(X, y, params, lowered=True), ref)
    assert all(low[k] > limits[k] for k in ("coef_gap", "history_gap")), low


def test_reference_blocks_need_not_divide_the_rows(monkeypatch):
    """The reference's last block overlaps the one before it and weighs the
    rows it took zero: the same fit for blocks that divide the rows or not."""
    ad = mf.adapter("mlogreg")
    X, y, _ = _rows(n=1_999)
    params = dict(PARAMS, maxIter=3)
    monkeypatch.setattr(ad, "BLOCK_ROWS", 1_999)
    whole = ad.reference(X, y, params)
    monkeypatch.setattr(ad, "BLOCK_ROWS", 300)
    ragged = ad.reference(X, y, params)
    assert ad.compare(ragged, whole)["coef_gap"] < 1e-6
    assert whole["n_evals"] == ragged["n_evals"]


def test_the_fit_state_leads_with_the_objective():
    """What the fault test alters first is the objective the fit ended at;
    the model made from that state is the fit's own."""
    ad = mf.adapter("mlogreg")
    X, y, w = _rows()
    est = ad.build(PARAMS, 1)
    from spark_rapids_ml_tpu.data import DeviceDataset

    model = est.fit(DeviceDataset(X.sharding.mesh, X, X.shape[0], y=y, weight=w))
    ans = ad.answer(model)
    assert ans["history"][-1] == pytest.approx(model.summary.objectiveHistory[-1])
    assert model.coefficientMatrix.shape == (10, 16) and ans["theta"].shape == (10 * 17,)
    seen = {}
    fit_array = est._fit_array

    def keep(fit_input):
        seen.update(fit_array(fit_input))
        return dict(seen)

    est._fit_array = keep
    est.fit(DeviceDataset(X.sharding.mesh, X, X.shape[0], y=y, weight=w))
    assert seen["coef_"][0] == np.float32(seen["objective"])
    np.testing.assert_array_equal(seen["coef_"][1:], model.coefficientMatrix.ravel())


def test_objective_gap_reads_the_reported_objective_at_the_fits_own_coefficients():
    """`objective_gap` holds the objective a fit reports against the
    reference's objective at that fit's own coefficients: an answer that
    ended elsewhere but reports its objective right reads float32's
    rounding, one that reports it 1 % off reads 1 %."""
    ad = mf.adapter("mlogreg")
    X, y, _ = _rows(n=1_000)
    ref = ad.reference(X, y, dict(PARAMS, maxIter=3))
    assert ad.compare(ref, ref)["objective_gap"] < 1e-6
    theta = 0.5 * ref["theta"]
    f = ref["objective_at"](theta)
    elsewhere = dict(ref, theta=theta, history=ref["history"][:-1] + [f])
    got = ad.compare(elsewhere, ref)
    assert got["objective_gap"] < 1e-6 and got["coef_gap"] == pytest.approx(0.5)
    off = dict(elsewhere, history=ref["history"][:-1] + [1.01 * f])
    assert ad.compare(off, ref)["objective_gap"] == pytest.approx(0.01, rel=1e-6)
