#
# The regression forest family's own tests: CPU, no rows.
#   python -m pytest chipbench/tests -q -p no:cacheprovider
#
import pytest

from chipbench import manifest as mf
from chipbench import roofline

PARAMS = {"numTrees": 15, "maxDepth": 6, "featureSubsetStrategy": "auto"}


def test_rfr_work_by_hand():
    w = mf.adapter("rfr").work(500_000, 3_000, 1, PARAMS)
    # a level: 1,000 bin ids and 12 bytes of row state per row; three adds per id
    assert w["kernels"]["forest_level"] == {"flops": 500_000 * 3_000.0, "bytes": 500_000 * 1_012.0}
    # the bins: the f32 rows read once, one byte a value written
    assert w["kernels"]["forest_bin"] == {"flops": 0.0, "bytes": 500_000 * 3_000 * 5.0}
    assert w["levels"] == 15 * 6
    assert [p["count"] for p in w["fit"]] == [90, 1]
    peaks = roofline.peaks_for("TPU v5 lite")
    seconds, bound = roofline.least_seconds(w["kernels"]["forest_level"], peaks)
    assert bound == "bytes" and seconds == pytest.approx(506e6 / 819e9)  # 0.62 ms a level
    assert roofline.fit_least_seconds(w, peaks) == pytest.approx(
        90 * 506e6 / 819e9 + 7.5e9 / 819e9)
    # two chips: each its half of the rows and of the trees (8 of 15)
    two = mf.adapter("rfr").work(1_000_000, 3_000, 2, PARAMS)
    assert two["kernels"] == w["kernels"] and two["levels"] == 8 * 6


def test_rfr_features_a_node_are_a_third_of_the_columns():
    rfr = mf.adapter("rfr")
    assert rfr.features_per_node(3_000, {}) == 1_000 and rfr.features_per_node(48, PARAMS) == 16
    assert rfr.features_per_node(2, {"featureSubsetStrategy": "onethird"}) == 1
    with pytest.raises(ValueError, match="auto/onethird"):
        rfr.features_per_node(3_000, {"featureSubsetStrategy": "sqrt"})


def test_rfr_configuration_and_the_audit_agree():
    cfg = mf.cell(mf.load_manifest(), "rfr_fit_cached")["config_file"]
    rfr = mf.adapter("rfr")
    assert cfg["audit"]["regret_trees"] == rfr.REGRET_TREES <= cfg["params"]["numTrees"]
    assert set(cfg["limits"]) == set(cfg["limits_why"]) == {
        "edges_off", "leaf_weight_off", "leaf_stat_gap", "split_regret", "stopped_early",
        "fits_differ", "trees_off"}
    assert cfg["limits"]["split_regret"] <= rfr.REGRET_TOL
    assert set(cfg["reduced"]) == set(cfg["reduced_why"]) == {"rows", "numTrees"}
    # no width differs from the source's
    for key in ("cols", "maxDepth", "maxBins", "featureSubsetStrategy", "impurity"):
        assert {"cols": cfg["cols"], **cfg["params"]}[key] == cfg["published"][key]


def test_rfr_names_the_classifiers_programs_and_the_shift():
    rfc, rfr = mf.adapter("rfc"), mf.adapter("rfr")
    assert rfr.PROGRAMS["forest_grow"] == rfc.PROGRAMS["forest_grow"]
    assert set(rfr.PROGRAMS["forest_bin"]) == set(rfc.PROGRAMS["forest_bin"]) | {
        "_forest_label_shift"}
    assert rfr.LABELS == "linear"
