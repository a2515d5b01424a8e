#
# The forest family's own tests: CPU, no rows.
#   python -m pytest chipbench/tests -q -p no:cacheprovider
#
import pytest

from chipbench import manifest as mf
from chipbench import roofline

PARAMS = {"numTrees": 25, "maxDepth": 13, "featureSubsetStrategy": "auto"}


def test_rfc_work_by_hand():
    w = mf.adapter("rfc").work(500_000, 3_000, 1, PARAMS)
    # a level: 54 bin ids and 12 bytes of row state per row; one add per id
    assert w["kernels"]["forest_level"] == {"flops": 500_000 * 54, "bytes": 500_000 * 66.0}
    # the bins: the f32 rows read once, one byte a value written
    assert w["kernels"]["forest_bin"] == {"flops": 0.0, "bytes": 500_000 * 3_000 * 5.0}
    assert w["levels"] == 25 * 13
    assert [p["count"] for p in w["fit"]] == [325, 1]
    peaks = roofline.peaks_for("TPU v5 lite")
    seconds, bound = roofline.least_seconds(w["kernels"]["forest_level"], peaks)
    assert bound == "bytes" and seconds == pytest.approx(33e6 / 819e9)
    assert roofline.fit_least_seconds(w, peaks) == pytest.approx(
        325 * 33e6 / 819e9 + 7.5e9 / 819e9)
    # two chips: each its half of the rows and of the trees (13 of 25)
    two = mf.adapter("rfc").work(1_000_000, 3_000, 2, PARAMS)
    assert two["kernels"] == w["kernels"] and two["levels"] == 13 * 13


def test_rfc_limits_and_the_audits_own_tolerance_agree():
    cfg = mf.cell(mf.load_manifest(), "rfc_fit_cached")["config_file"]
    assert cfg["limits"]["split_regret"] == mf.adapter("rfc").REGRET_TOL
    assert set(cfg["limits"]) == set(cfg["limits_why"])
