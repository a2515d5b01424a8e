#
# JVM-plugin protocol conformance — the Scala PythonWorkerRunner
# (jvm/src/main/scala/com/tpurapids/ml/PythonWorkerRunner.scala) and the
# Python worker (connect_plugin.py) must agree on the wire format.  These
# tests drive the REAL worker with requests shaped exactly as the Scala
# side sends them (field-for-field), and statically check the Scala source
# uses only fields the worker understands.
#
import json
import os
import re

import numpy as np
import pandas as pd

_SCALA = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "jvm", "src", "main", "scala", "com", "tpurapids", "ml",
    "PythonWorkerRunner.scala",
)


def _scala_request_fields():
    """JSON keys the Scala runner writes, parsed from its source."""
    with open(_SCALA) as f:
        src = f.read()
    return set(re.findall(r'"(\w+)" -> J', src))


def test_scala_fields_are_understood():
    fields = _scala_request_fields()
    # every field the Scala side sends is consumed by handle_request
    import inspect

    from spark_rapids_ml_tpu import connect_plugin

    handler_src = inspect.getsource(connect_plugin.handle_request)
    assert fields, "no request fields found in the Scala source"
    for f in fields:
        assert f'"{f}"' in handler_src, (
            f"Scala sends field '{f}' the Python worker never reads"
        )


def test_fit_request_shaped_like_scala(tmp_path):
    """The exact fit request PythonWorkerRunner.fit constructs (incl.
    inline_arrays) round-trips through the worker and returns the inline
    coefficient arrays ModelBuilder.logisticRegression parses."""
    from spark_rapids_ml_tpu.connect_plugin import handle_request

    rng = np.random.default_rng(0)
    X = rng.normal(size=(300, 4)).astype(np.float32)
    y = (X[:, 0] - X[:, 1] > 0).astype(np.float64)
    data = str(tmp_path / "fit.parquet")
    pd.DataFrame({"features": list(X), "label": y}).to_parquet(data)
    model_path = str(tmp_path / "model")
    req = {
        "op": "fit",
        "operator": "LogisticRegression",
        "params": {"regParam": 0.01, "maxIter": 50},
        "data": data,
        "model_path": model_path,
        "inline_arrays": True,
    }
    resp = handle_request(json.loads(json.dumps(req)))
    assert resp["status"] == "ok"
    attrs = resp["attributes"]
    # what ModelBuilder.logisticRegression reads:
    coef = np.asarray(attrs["coef_"], np.float64)
    intercept = np.asarray(attrs["intercept_"], np.float64)
    assert coef.shape == (1, 4) and intercept.shape == (1,)
    assert len(attrs["classes_"]) == 2
    assert os.path.isdir(model_path)


def test_transform_request_shaped_like_scala(tmp_path):
    from spark_rapids_ml_tpu.connect_plugin import handle_request

    rng = np.random.default_rng(1)
    X = rng.normal(size=(200, 3)).astype(np.float32)
    data = str(tmp_path / "fit.parquet")
    pd.DataFrame({"features": list(X)}).to_parquet(data)
    model_path = str(tmp_path / "km")
    fit = handle_request({
        "op": "fit", "operator": "KMeans",
        "params": {"k": 2, "seed": 1},
        "data": data, "model_path": model_path, "inline_arrays": True,
    })
    assert fit["status"] == "ok"
    assert np.asarray(fit["attributes"]["cluster_centers_"]).shape == (2, 3)
    out_path = str(tmp_path / "out.parquet")
    resp = handle_request({
        "op": "transform", "operator": "KMeansModel",
        "params": {},
        "data": data, "model_path": model_path, "output_path": out_path,
    })
    assert resp["status"] == "ok"
    assert resp["num_rows"] == 200
    out = pd.read_parquet(out_path)
    assert "prediction" in out.columns


def test_rf_model_operator_resolution(tmp_path):
    """'RandomForestClassificationModel' must resolve to the
    RandomForestClassifier registry entry (model names do not all strip
    to their estimator's name)."""
    from spark_rapids_ml_tpu.connect_plugin import handle_request

    rng = np.random.default_rng(2)
    X = rng.normal(size=(200, 3)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    data = str(tmp_path / "rf.parquet")
    pd.DataFrame({"features": list(X), "label": y}).to_parquet(data)
    model_path = str(tmp_path / "rf_model")
    fit = handle_request({
        "op": "fit", "operator": "RandomForestClassifier",
        "params": {"numTrees": 4, "maxDepth": 4, "seed": 0},
        "data": data, "model_path": model_path,
    })
    assert fit["status"] == "ok"
    out_path = str(tmp_path / "rf_out.parquet")
    resp = handle_request({
        "op": "transform", "operator": "RandomForestClassificationModel",
        "params": {}, "data": data, "model_path": model_path,
        "output_path": out_path,
    })
    assert resp["status"] == "ok", resp.get("error")
    assert resp["num_rows"] == 200


# ---------------------------------------------------------------------------
# Field-by-field golden tests covering every wrapper in Wrappers.scala /
# TpuModels.scala: for each algorithm the Scala
# ModelBuilder reconstructs, run the REAL worker fit and assert every
# `attrs \ "field"` it reads is present and shaped as the builder expects.
# ---------------------------------------------------------------------------

_TPU_MODELS = os.path.join(
    os.path.dirname(_SCALA), "..", "..", "..", "org", "apache", "spark",
    "ml", "tpu", "TpuModels.scala",
)
_WRAPPERS = os.path.join(os.path.dirname(_SCALA), "Wrappers.scala")


def _builder_fields(fn_name):
    """`attrs \\ "field"` reads inside one ModelBuilder function."""
    src = open(_TPU_MODELS).read()
    m = re.search(
        rf"def {fn_name}\(uid: String, attrs: JValue\).*?(?=\n  def |\n\}})",
        src, re.S,
    )
    assert m, f"ModelBuilder.{fn_name} not found"
    return set(re.findall(r'attrs\s*\\\s*"(\w+)"', m.group(0)))


def _fit(tmp_path, rng, operator, params, supervised, classify=False):
    from spark_rapids_ml_tpu.connect_plugin import handle_request

    X = rng.normal(size=(150, 4)).astype(np.float32)
    df = pd.DataFrame({"features": list(X)})
    if supervised:
        raw = X @ np.arange(1, 5)
        df["label"] = (
            (raw > np.median(raw)).astype(np.float64) if classify
            else raw.astype(np.float64)
        )
    path = str(tmp_path / "d.parquet")
    df.to_parquet(path)
    resp = handle_request({
        # byte-identical request shape to PythonWorkerRunner.scala
        # (including inline_arrays, which the JVM always sends)
        "op": "fit", "operator": operator, "params": params,
        "data": path, "model_path": str(tmp_path / "m"),
        "inline_arrays": True,
    })
    assert resp["status"] == "ok", resp
    return resp["attributes"]


def _is_matrix(v):
    return (
        isinstance(v, list) and v
        and all(isinstance(r, list) and len(r) == len(v[0]) for r in v)
    )


def test_modelbuilder_logistic_regression_fields(tmp_path, rng):
    attrs = _fit(tmp_path, rng, "LogisticRegression", {"regParam": 0.01},
                 True, classify=True)
    fields = _builder_fields("logisticRegression")
    assert fields == {"coef_", "intercept_", "classes_"}
    assert _is_matrix(attrs["coef_"])  # arr2
    assert isinstance(attrs["intercept_"], list)  # arr1
    assert isinstance(attrs["classes_"], list) and len(attrs["classes_"]) == 2


def test_modelbuilder_linear_regression_fields(tmp_path, rng):
    attrs = _fit(tmp_path, rng, "LinearRegression", {}, True)
    fields = _builder_fields("linearRegression")
    assert fields == {"coef_", "intercept_"}
    coef = attrs["coef_"]
    # the Scala side reads arr1 — a flat (d,) list, not a matrix
    assert isinstance(coef, list) and len(coef) == 4
    assert all(isinstance(c, (int, float)) for c in coef)
    assert isinstance(attrs["intercept_"], (int, float))  # doubleOf


def test_modelbuilder_kmeans_fields(tmp_path, rng):
    attrs = _fit(tmp_path, rng, "KMeans", {"k": 3, "seed": 1}, False)
    fields = _builder_fields("kmeans")
    assert fields == {"cluster_centers_"}
    centers = attrs["cluster_centers_"]
    assert _is_matrix(centers) and len(centers) == 3 and len(centers[0]) == 4


def test_modelbuilder_pca_fields(tmp_path, rng):
    attrs = _fit(
        tmp_path, rng, "PCA",
        {"k": 2, "inputCol": "features", "outputCol": "o"}, False,
    )
    fields = _builder_fields("pca")
    assert fields == {"components_", "explained_variance_ratio_"}
    comp = attrs["components_"]
    assert _is_matrix(comp) and len(comp) == 2 and len(comp[0]) == 4
    evr = attrs["explained_variance_ratio_"]
    assert isinstance(evr, list) and len(evr) == 2


def test_wrapper_rf_classifier_num_classes_field(tmp_path, rng):
    # TpuRandomForestClassifier reads `attrs \ "num_classes"` directly
    # (Wrappers.scala) — the worker must emit it as an integer
    src = open(_WRAPPERS).read()
    assert '"num_classes"' in src
    attrs = _fit(
        tmp_path, rng, "RandomForestClassifier",
        {"numTrees": 4, "maxDepth": 3, "seed": 0}, True, classify=True,
    )
    assert attrs["num_classes"] == 2
    assert isinstance(attrs["num_classes"], int)


def test_every_operator_in_wrappers_round_trips(tmp_path, rng):
    # one fit+transform per wrapper operator, driven exactly as the
    # Scala TpuEstimator.trainOnPython would
    from spark_rapids_ml_tpu.connect_plugin import handle_request

    src = open(_WRAPPERS).read()
    ops = re.findall(r'operatorName: String = "(\w+)"', src)
    assert sorted(ops) == [
        "KMeans", "LinearRegression", "LogisticRegression", "PCA",
        "RandomForestClassifier", "RandomForestRegressor",
    ]
    params = {
        "KMeans": {"k": 2, "seed": 0},
        "LinearRegression": {},
        "LogisticRegression": {"regParam": 0.01},
        "PCA": {"k": 2, "inputCol": "features", "outputCol": "o"},
        "RandomForestClassifier": {"numTrees": 3, "maxDepth": 3, "seed": 0},
        "RandomForestRegressor": {"numTrees": 3, "maxDepth": 3, "seed": 0},
    }
    model_suffix = {
        "RandomForestClassifier": "RandomForestClassificationModel",
        "RandomForestRegressor": "RandomForestRegressionModel",
    }
    for op in ops:
        sup = op not in ("KMeans", "PCA")
        X = rng.normal(size=(100, 4)).astype(np.float32)
        df = pd.DataFrame({"features": list(X)})
        if sup:
            raw = X @ np.arange(1, 5)
            df["label"] = (
                (raw > np.median(raw)).astype(np.float64)
                if op == "LogisticRegression" or "Classifier" in op
                else raw.astype(np.float64)
            )
        path = str(tmp_path / f"{op}.parquet")
        df.to_parquet(path)
        mp = str(tmp_path / f"{op}_m")
        r = handle_request({"op": "fit", "operator": op,
                            "params": params[op], "data": path,
                            "model_path": mp, "inline_arrays": True})
        assert r["status"] == "ok", (op, r)
        model_op = model_suffix.get(op, op + "Model")
        out = str(tmp_path / f"{op}_o.parquet")
        r = handle_request({"op": "transform", "operator": model_op,
                            "params": {}, "data": path, "model_path": mp,
                            "output_path": out})
        assert r["status"] == "ok", (op, r)
        assert r["num_rows"] == 100


def test_arrays_ship_only_when_inline_requested(tmp_path, rng):
    # without inline_arrays (non-JVM callers) arrays stay path-resident:
    # shapes ship, payloads do not; with it, payloads ship regardless of
    # size (the cap-lift branch PythonWorkerRunner always exercises)
    from spark_rapids_ml_tpu.connect_plugin import handle_request

    X = rng.normal(size=(80, 3)).astype(np.float32)
    path = str(tmp_path / "d.parquet")
    pd.DataFrame({"features": list(X)}).to_parquet(path)
    base = {"op": "fit", "operator": "KMeans", "params": {"k": 2, "seed": 0},
            "data": path, "model_path": str(tmp_path / "m")}
    plain = handle_request(dict(base))
    assert plain["status"] == "ok"
    assert "cluster_centers__shape" in plain["attributes"]
    assert "cluster_centers_" not in plain["attributes"]
    inline = handle_request(dict(base, model_path=str(tmp_path / "m2"),
                                 inline_arrays=True))
    assert inline["status"] == "ok"
    assert inline["attributes"]["cluster_centers__shape"] == [2, 3]
    assert len(inline["attributes"]["cluster_centers_"]) == 2
