#
# Connect-plugin worker — the analog of the reference's Spark Connect
# backend (`connect_plugin.py:68-273`, spawned per request by the JVM
# `PythonEstimatorRunner`/`PythonModelRunner`, jvm/.../Plugin.scala:26-57).
# The reference worker receives (operator_name, params, dataset) over a
# py4j gateway, fits/transforms, and returns JSON model attributes or a
# transformed DataFrame handle.
#
# Here the JVM gateway is replaced by a transport any host process (a
# Spark 4.0 Connect server plugin, a service, a test) can speak: one JSON
# request per line on stdin, one JSON response per line on stdout.
# Datasets travel as parquet paths — the natural exchange format for a
# JVM caller (df.write.parquet) and exactly what the streaming ingest
# path consumes.
#
#   {"op": "fit", "operator": "LogisticRegression", "params": {...},
#    "data": "<parquet path>", "model_path": "<dir>"}
#      -> {"status": "ok", "attributes": {...scalar attrs...},
#          "model_path": ...}
#   {"op": "transform", "operator": "LogisticRegressionModel",
#    "params": {...}, "data": "<parquet path>", "model_path": "<dir>",
#    "output_path": "<parquet path>"}
#      -> {"status": "ok", "output_path": ..., "num_rows": N}
#
# The operator registry mirrors the 6 plugin-supported algorithms
# (reference connect_plugin.py:127-243).
#
from __future__ import annotations

import json
import sys
import traceback
from typing import IO, Any, Dict


def _registry() -> Dict[str, Any]:
    from .classification import (
        LogisticRegression,
        LogisticRegressionModel,
        RandomForestClassificationModel,
        RandomForestClassifier,
    )
    from .clustering import KMeans, KMeansModel
    from .feature import PCA, PCAModel
    from .regression import (
        LinearRegression,
        LinearRegressionModel,
        RandomForestRegressionModel,
        RandomForestRegressor,
    )

    return {
        "LogisticRegression": (LogisticRegression, LogisticRegressionModel),
        "RandomForestClassifier": (
            RandomForestClassifier, RandomForestClassificationModel,
        ),
        "RandomForestRegressor": (
            RandomForestRegressor, RandomForestRegressionModel,
        ),
        "LinearRegression": (LinearRegression, LinearRegressionModel),
        "KMeans": (KMeans, KMeansModel),
        "PCA": (PCA, PCAModel),
    }


def _sanitize_nonfinite(v):
    """Recursively stringify non-finite floats (strict-JSON wire format
    for the JVM side, which maps the strings back in ModelBuilder)."""
    import math

    if isinstance(v, float) and not math.isfinite(v):
        return "NaN" if math.isnan(v) else (
            "Infinity" if v > 0 else "-Infinity"
        )
    if isinstance(v, list):
        return [_sanitize_nonfinite(x) for x in v]
    return v


def _scalar_attributes(
    model, max_inline_elems: float = 0
) -> Dict[str, Any]:
    """JSON-safe model attributes.  Numeric arrays up to
    `max_inline_elems` elements are INLINE (nested lists) — the Scala
    ModelBuilder reconstructs native Spark models from them
    (TpuModels.scala `attrs \\ "coef_"` etc.), matching the reference's
    py4j inline-attribute transport.  Larger arrays (RF node tables,
    UMAP embeddings) stay path-resident in the model directory and only
    their `_shape` ships; those models transform via the Python-backed
    round trip instead."""
    import numpy as np

    out: Dict[str, Any] = {}
    for k, v in model._get_model_attributes().items():
        if isinstance(v, (np.integer, np.floating, np.bool_)):
            v = v.item()
        v = _sanitize_nonfinite(v)
        if isinstance(v, (str, int, float, bool)) or v is None:
            out[k] = v
        elif isinstance(v, list) and all(
            isinstance(x, (str, int, float, bool)) for x in v
        ):
            out[k] = v  # e.g. classes_
        elif isinstance(v, np.ndarray):
            out[k + "_shape"] = list(v.shape)
            if v.size <= max_inline_elems and (
                np.issubdtype(v.dtype, np.number)
                or v.dtype == np.bool_
            ):
                out[k] = _sanitize_nonfinite(v.tolist())
    return out


_model_cache: Dict[Any, Any] = {}


def handle_request(req: Dict[str, Any]) -> Dict[str, Any]:
    registry = _registry()
    op = req.get("op")
    operator = str(req.get("operator", ""))
    params = dict(req.get("params") or {})
    data = req.get("data")

    base = operator[:-5] if operator.endswith("Model") else operator
    # model class names do not all strip to their estimator's name
    # (RandomForestClassificationModel -> RandomForestClassifier)
    base = {
        "RandomForestClassification": "RandomForestClassifier",
        "RandomForestRegression": "RandomForestRegressor",
    }.get(base, base)
    if base not in registry:
        return {
            "status": "error",
            "error": f"unsupported operator '{operator}'; supported: "
            + ", ".join(sorted(registry)),
        }
    est_cls, model_cls = registry[base]

    if op == "fit":
        est = est_cls(**params)
        model = est.fit(data)
        model_path = req.get("model_path")
        if model_path:
            model.save(model_path)
        # a JVM caller building a real Spark model (jvm/ ModelBuilder)
        # sends inline_arrays=true: the full array payload ships inline
        # (reference py4j semantics); other callers get scalars + shapes
        # and read arrays from model_path
        attributes = _scalar_attributes(
            model,
            max_inline_elems=(
                float("inf") if req.get("inline_arrays") else 0
            ),
        )
        return {
            "status": "ok",
            "operator": base + "Model",
            "attributes": attributes,
            "model_path": model_path,
        }

    if op == "transform":
        model_path = req.get("model_path")
        if not model_path:
            return {"status": "error", "error": "transform requires model_path"}
        # long-lived workers serve many transforms per model: cache the
        # loaded model (and with it the lazily staged device index)
        key = (operator, str(model_path))
        model = _model_cache.get(key)
        if model is None:
            model = model_cls.load(model_path)
            _model_cache.clear()  # one resident model keeps HBM bounded
            _model_cache[key] = model
        if params:
            model._set_params(**params)
        from .data import _to_pandas

        pdf = _to_pandas(data)
        out_df = model.transform(pdf)
        output_path = req.get("output_path")
        num_rows = int(len(out_df))
        if output_path:
            out_df.to_parquet(output_path)
        return {"status": "ok", "output_path": output_path, "num_rows": num_rows}

    return {"status": "error", "error": f"unknown op '{op}' (fit|transform)"}


def main(infile: IO = sys.stdin, outfile: IO = sys.stdout) -> None:
    """Serve line-JSON requests until EOF (one worker can handle many
    requests; the reference spawns one worker per request, which also
    works — a single line then EOF)."""
    import os

    for line in infile:
        line = line.strip()
        if not line:
            continue
        try:
            resp = handle_request(json.loads(line))
        except Exception as e:
            resp = {
                "status": "error",
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:],
            }
        outfile.write(json.dumps(resp) + "\n")
        outfile.flush()


if __name__ == "__main__":
    main()
