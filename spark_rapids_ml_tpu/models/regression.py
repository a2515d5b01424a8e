#
# Regression: LinearRegression + RandomForestRegressor — the analog
# of reference regression.py (1148 LoC).  The three cuML distributed solvers
# (LinearRegressionMG eig / RidgeMG / CDMG coordinate descent, dispatched at
# regression.py:544-627) are replaced by ops/linear.py: one fused
# sufficient-statistics pass + replicated closed-form / FISTA solve.
#
from __future__ import annotations

from typing import Any, Dict, List, Optional, Union

import numpy as np

from ..core import FitInput, _TpuEstimator, _TpuEstimatorSupervised, _TpuModel
from ..params import (
    HasElasticNetParam,
    HasFeaturesCol,
    HasFeaturesCols,
    HasFitIntercept,
    HasLabelCol,
    HasMaxIter,
    HasPredictionCol,
    HasRegParam,
    HasStandardization,
    HasTol,
    HasWeightCol,
    Param,
    TypeConverters,
    _TpuParams,
)
from ..utils import _ArrayBatch


class LinearRegressionClass:
    """Param mapping (reference LinearRegressionClass regression.py:181-232)."""

    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        return {
            "aggregationDepth": "",
            "elasticNetParam": "l1_ratio",
            "epsilon": "",
            "fitIntercept": "fit_intercept",
            "loss": "loss",
            "maxBlockSizeInMB": "",
            "maxIter": "max_iter",
            "regParam": "alpha",
            "solver": "solver",
            "standardization": "standardization",
            "tol": "tol",
            # improvement over the reference (weightCol -> None): the fused
            # stats kernel supports sample weights natively
            "weightCol": "",
        }

    @classmethod
    def _param_value_mapping(cls):
        return {
            "loss": lambda x: {
                "squaredError": "squared_loss",
                "huber": None,
                "squared_loss": "squared_loss",
            }.get(x, None),
            "solver": lambda x: {
                "auto": "auto",
                "normal": "eig",
                "l-bfgs": None,
                "eig": "eig",
            }.get(x, None),
        }

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "algorithm": "auto",
            "fit_intercept": True,
            "verbose": False,
            "alpha": 0.0001,
            "solver": "auto",
            "loss": "squared_loss",
            "l1_ratio": 0.15,
            "max_iter": 1000,
            "tol": 0.001,
            "standardization": True,
            "shuffle": True,
        }


class _LinearRegressionTpuParams(
    _TpuParams,
    HasFeaturesCol,
    HasFeaturesCols,
    HasLabelCol,
    HasPredictionCol,
    HasRegParam,
    HasElasticNetParam,
    HasFitIntercept,
    HasStandardization,
    HasMaxIter,
    HasTol,
    HasWeightCol,
):
    """Shared params (reference _LinearRegressionCumlParams)."""

    solver = Param("_", "solver", "The solver algorithm: auto, normal or eig.",
                   TypeConverters.toString)
    loss = Param("_", "loss", "The loss function: squaredError.",
                 TypeConverters.toString)
    aggregationDepth = Param("_", "aggregationDepth", "treeAggregate depth (ignored).",
                             TypeConverters.toInt)
    maxBlockSizeInMB = Param("_", "maxBlockSizeInMB", "block size (ignored).",
                             TypeConverters.toFloat)

    def __init__(self) -> None:
        super().__init__()
        self._setDefault(
            regParam=0.0,
            elasticNetParam=0.0,
            fitIntercept=True,
            standardization=True,
            maxIter=100,
            tol=1e-6,
            solver="auto",
            loss="squaredError",
            aggregationDepth=2,
        )

    def setFeaturesCol(self, value: Union[str, List[str]]):
        if isinstance(value, str):
            self._set_params(featuresCol=value)
        else:
            self._set_params(featuresCols=value)
        return self

    def setFeaturesCols(self, value: List[str]):
        return self._set_params(featuresCols=value)

    def setLabelCol(self, value: str):
        self._set(labelCol=value)
        return self

    def setPredictionCol(self, value: str):
        self._set(predictionCol=value)
        return self

    def setRegParam(self, value: float):
        return self._set_params(regParam=value)

    def setElasticNetParam(self, value: float):
        return self._set_params(elasticNetParam=value)

    def setFitIntercept(self, value: bool):
        return self._set_params(fitIntercept=value)

    def setStandardization(self, value: bool):
        return self._set_params(standardization=value)

    def setMaxIter(self, value: int):
        return self._set_params(maxIter=value)

    def setTol(self, value: float):
        return self._set_params(tol=value)

    def setWeightCol(self, value: str):
        return self._set_params(weightCol=value)


class LinearRegression(
    LinearRegressionClass, _TpuEstimatorSupervised, _LinearRegressionTpuParams
):
    """Distributed linear regression on TPU (API parity: reference
    LinearRegression regression.py:282-694).

    Solver dispatch mirrors the reference (regression.py:544-627): regParam=0
    -> OLS normal equations; elasticNetParam=0 -> ridge closed form; else
    FISTA (same optimum as cuML's CD for the convex elastic-net objective).
    All variants consume one fused sufficient-statistics pass.

    Examples
    --------
    >>> import pandas as pd
    >>> from spark_rapids_ml_tpu.regression import LinearRegression
    >>> df = pd.DataFrame({"features": [[1.0, 2.0], [2.0, 3.0], [3.0, 4.0]],
    ...                    "label": [3.0, 5.0, 7.0]})
    >>> model = LinearRegression().setFeaturesCol("features").fit(df)
    >>> round(float(model.transform(df)["prediction"][0]), 2)
    3.0
    """

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._set_params(**kwargs)

    def _fista_checkpoint(self, gram: np.ndarray, sxy: np.ndarray, sw: float):
        """(checkpoint_path, tag) for the FISTA elastic-net loop when the
        `checkpoint_dir` conf is set (the estimator-wide resume contract,
        resilience/checkpoint.py).  The tag binds the problem CONTENT —
        Gram/cross-moment checksums, not just shapes — so a same-shaped
        fit on different data can never resume this one's state."""
        from ..resilience.checkpoint import (
            checkpoint_file_for,
            resolve_checkpoint_dir,
        )

        ckpt_dir = resolve_checkpoint_dir()
        if not ckpt_dir:
            return None, ""
        p = self._tpu_params
        tag = (
            f"linreg-fista|d={int(gram.shape[0])}|sw={sw}"
            f"|gs={float(np.float64(gram).sum()):.12g}"
            f"|xs={float(np.float64(sxy).sum()):.12g}"
            f"|a={p['alpha']}|l1r={p['l1_ratio']}|int={p['fit_intercept']}"
            f"|std={p.get('standardization', True)}|mi={p['max_iter']}"
        )
        return checkpoint_file_for(ckpt_dir, tag), tag

    def _fit_array(self, fit_input: FitInput) -> Dict[str, Any]:
        import jax

        from ..ops.linear import linreg_sufficient_stats, solve_linear_host
        from ..tracing import trace

        p = fit_input.params
        # the device's pass, the copy to the host and the host's solve each
        # under a span of its own: the fetch follows the wait at once, so
        # waiting here serialises nothing that overlapped
        with trace("linreg_gram"):
            stats = jax.block_until_ready(
                linreg_sufficient_stats(fit_input.X, fit_input.w, fit_input.y)
            )
        with trace("linreg_fetch"):
            gram_h, sxy_h, s1_h = (np.asarray(a) for a in stats[:3])
            sw, sy, syy = (float(a) for a in stats[3:])
        ckpt_path, ckpt_tag = self._fista_checkpoint(gram_h, sxy_h, sw)
        with trace("linreg_host_solve"):
            coef, intercept, diag = solve_linear_host(
                gram_h,
                sxy_h,
                s1_h,
                sw,
                sy,
                syy,
                reg_param=float(p["alpha"]),
                elasticnet_param=float(p["l1_ratio"]),
                fit_intercept=bool(p["fit_intercept"]),
                standardization=bool(p.get("standardization", True)),
                tol=float(p["tol"]),
                max_iter=int(p["max_iter"]),
                checkpoint_path=ckpt_path,
                checkpoint_tag=ckpt_tag,
            )
        # summary metrics via a cancellation-free residual pass over the
        # still-staged data (the one-pass SSE expansion loses ~eps·Σwy²)
        import jax.numpy as jnp

        from ..ops.linear import _summary_from_sse, linreg_residual_sse

        with trace("linreg_residual"):
            sse = float(
                jax.device_get(
                    linreg_residual_sse(
                        fit_input.X,
                        fit_input.w,
                        fit_input.y,
                        jnp.asarray(coef, fit_input.X.dtype),
                        fit_input.X.dtype.type(intercept),
                    )
                )
            )
        diag.update(
            _summary_from_sse(sse, sw, sy, syy, bool(p["fit_intercept"]))
        )
        # the fetched Gram is dropped here, under a span, and not at the
        # return, where it ran under none: unmapping 36 MB at 3,000
        # columns is milliseconds of the host's
        with trace("linreg_release", detail="work"):
            del stats, gram_h, sxy_h, s1_h
        dtype = np.dtype(fit_input.dtype)
        return {
            "coef_": coef.astype(dtype),
            "intercept_": float(intercept),
            "n_iter_": int(diag["n_iter"]),
            "rmse_": float(diag["rmse"]),
            "mse_": float(diag["mse"]),
            "r2_": float(diag["r2"]),
            "n_cols": fit_input.pdesc.n,
            "dtype": str(dtype.name),
        }

    def _supports_streaming_stats(self) -> bool:
        return True

    def _supports_fused_stats(self) -> bool:
        # the Gram/moment/cross sums are chunk-order invariant, so
        # accumulating while staging is exact (fused.py)
        return True

    def _fit_fused(self, batch: _ArrayBatch) -> Dict[str, Any]:
        """Fused stage-and-solve over an in-memory host batch: the
        weighted Gram/moment/cross statistics accumulate on the mesh as
        each chunk lands (fused.py), then the same host solve as the
        streamed-statistics path.  Summary rmse/mse/r2 come from the
        one-pass SSE expansion (as on every streamed path — no staged
        array exists for a residual pass)."""
        from ..fused import fused_chunk_rows, fused_linreg_stats, iter_host_chunks

        X = batch.X
        dtype = self._out_dtype(X)
        d = int(X.shape[1])
        ldt = self._fit_label_dtype() or np.dtype(dtype)

        def producer(n_dev: int):
            rows = fused_chunk_rows(
                int(X.shape[0]), d, np.dtype(dtype).itemsize, n_dev
            )
            return iter_host_chunks(
                X, batch.y, batch.weight, rows, dtype, label_dtype=ldt
            )

        st = fused_linreg_stats(producer, d, dtype)
        return self._attrs_from_stats(st, dtype)

    def _fit_fused_parquet(self, path: str) -> Dict[str, Any]:
        """Fused stage-and-solve straight from parquet (decode on the
        producer thread, accumulate on the mesh)."""
        from ..fused import (
            fused_chunk_rows,
            fused_linreg_stats,
            iter_parquet_chunks,
        )
        from ..streaming import parquet_row_count, probe_num_features

        fcol, fcols, label_col, weight_col, dtype = self._streaming_io_params()
        if label_col is None:
            raise ValueError("labelCol must be set for LinearRegression")
        d = probe_num_features(path, fcol, fcols)
        n = parquet_row_count(path)
        ldt = self._fit_label_dtype() or np.dtype(dtype)

        def producer(n_dev: int):
            rows = fused_chunk_rows(n, d, np.dtype(dtype).itemsize, n_dev)
            prep = {"s": 0.0, "iv": []}  # readers self-time their decode
            return (
                iter_parquet_chunks(
                    path, fcol, fcols, label_col, weight_col, rows, dtype,
                    label_dtype=ldt, prep=prep,
                ),
                prep,
            )

        st = fused_linreg_stats(producer, d, dtype)
        return self._attrs_from_stats(st, dtype)

    def _supports_fold_weights(self) -> bool:
        # closed-form/FISTA solve over w-weighted sufficient statistics
        # (ops/linear.py SUPPORTS_ZERO_WEIGHT_ROWS): a CV fold mask is
        # exactly a zero weight, and the solution is row-count free
        from ..ops import linear as _linear_ops

        return bool(_linear_ops.SUPPORTS_ZERO_WEIGHT_ROWS)

    def _fit_streaming(self, path: str) -> Dict[str, Any]:
        """Beyond-HBM fit from multi-pass streamed sufficient statistics
        (streaming.py `linreg_streaming_stats`); the host solve is the same
        `solve_linear_host` the in-memory path uses."""
        from ..streaming import linreg_streaming_stats

        fcol, fcols, label_col, weight_col, dtype = self._streaming_io_params()
        if label_col is None:
            raise ValueError("labelCol must be set for LinearRegression")
        st = linreg_streaming_stats(
            path, fcol, fcols, label_col, weight_col, dtype=dtype
        )
        return self._attrs_from_stats(st, dtype)

    def _fit_streaming_csr(self, batch) -> Dict[str, Any]:
        """Sparse fit from blocked-densify sufficient statistics
        (streaming.py `linreg_stats_from_csr`): exact, with one dense row
        block of host memory — the analog of the reference's CSR path
        (classification.py:960-966 applied to the normal equations)."""
        from ..streaming import linreg_stats_from_csr

        dtype = self._out_dtype(batch.X)
        st = linreg_stats_from_csr(
            batch.X.tocsr(), np.asarray(batch.y), batch.weight, dtype=dtype
        )
        return self._attrs_from_stats(st, dtype)

    def _attrs_from_stats(self, st: Dict[str, Any], dtype) -> Dict[str, Any]:
        from ..ops.linear import solve_linear_host

        p = self._tpu_params
        ckpt_path, ckpt_tag = self._fista_checkpoint(
            np.asarray(st["gram"]), np.asarray(st["sxy"]), float(st["sw"])
        )
        coef, intercept, diag = solve_linear_host(
            np.asarray(st["gram"]),
            np.asarray(st["sxy"]),
            np.asarray(st["s1"]),
            float(st["sw"]),
            float(st["sy"]),
            float(st["syy"]),
            reg_param=float(p["alpha"]),
            elasticnet_param=float(p["l1_ratio"]),
            fit_intercept=bool(p["fit_intercept"]),
            standardization=bool(p.get("standardization", True)),
            tol=float(p["tol"]),
            max_iter=int(p["max_iter"]),
            checkpoint_path=ckpt_path,
            checkpoint_tag=ckpt_tag,
        )
        dtype = np.dtype(dtype)
        return {
            "coef_": coef.astype(dtype),
            "intercept_": float(intercept),
            "n_iter_": int(diag["n_iter"]),
            "rmse_": float(diag["rmse"]),
            "mse_": float(diag["mse"]),
            "r2_": float(diag["r2"]),
            "n_cols": int(np.asarray(st["gram"]).shape[0]),
            "dtype": str(dtype.name),
        }

    def _create_model(self, attrs: Dict[str, Any]) -> "LinearRegressionModel":
        return LinearRegressionModel(**attrs)

    def _cpu_fit(self, batch: _ArrayBatch) -> "LinearRegressionModel":
        from sklearn.linear_model import ElasticNet, LinearRegression as SkLR, Ridge

        reg = self.getOrDefault("regParam")
        l1r = self.getOrDefault("elasticNetParam")
        n = batch.X.shape[0]
        if reg == 0.0:
            sk = SkLR(fit_intercept=self.getOrDefault("fitIntercept"))
        elif l1r == 0.0:
            sk = Ridge(alpha=reg * n, fit_intercept=self.getOrDefault("fitIntercept"))
        else:
            sk = ElasticNet(
                alpha=reg, l1_ratio=l1r,
                fit_intercept=self.getOrDefault("fitIntercept"),
            )
        sk.fit(batch.X, batch.y, sample_weight=batch.weight)
        # summary metrics so the fallback path matches the TPU surface
        w = (
            np.ones(batch.X.shape[0])
            if batch.weight is None
            else np.asarray(batch.weight, np.float64)
        )
        y = np.asarray(batch.y, np.float64)
        resid = y - sk.predict(batch.X)
        sse = float((w * resid * resid).sum())
        from ..ops.linear import _summary_from_sse

        stats = _summary_from_sse(
            sse, float(w.sum()), float((w * y).sum()),
            float((w * y * y).sum()), self.getOrDefault("fitIntercept"),
        )
        return LinearRegressionModel(
            coef_=np.asarray(sk.coef_, batch.X.dtype),
            intercept_=float(sk.intercept_),
            n_iter_=int(np.max(getattr(sk, "n_iter_", 0)) or 0),
            rmse_=stats["rmse"],
            mse_=stats["mse"],
            r2_=stats["r2"],
            n_cols=int(batch.X.shape[1]),
            dtype=str(batch.X.dtype),
        )


class LinearRegressionTrainingSummary:
    """Spark LinearRegressionTrainingSummary analog (exact-from-stats)."""

    def __init__(self, rootMeanSquaredError: float, meanSquaredError: float,
                 r2: float, totalIterations: int) -> None:
        self.rootMeanSquaredError = float(rootMeanSquaredError)
        self.meanSquaredError = float(meanSquaredError)
        self.r2 = float(r2)
        self.totalIterations = int(totalIterations)


class LinearRegressionSummary:
    """Evaluation summary on a given dataset (pyspark
    LinearRegressionSummary surface over the metrics subsystem)."""

    def __init__(self, predictions, metrics, fit_intercept: bool = True) -> None:
        self.predictions = predictions
        self._m = metrics
        self._fit_intercept = bool(fit_intercept)

    @property
    def rootMeanSquaredError(self) -> float:
        return float(self._m.root_mean_squared_error)

    @property
    def meanSquaredError(self) -> float:
        return float(self._m.mean_squared_error)

    @property
    def meanAbsoluteError(self) -> float:
        return float(self._m.mean_absolute_error)

    @property
    def r2(self) -> float:
        # Spark passes throughOrigin=!fitIntercept (RegressionMetrics),
        # matching the training summary's through-origin SStot
        return float(self._m.r2(through_origin=not self._fit_intercept))

    @property
    def explainedVariance(self) -> float:
        return float(self._m.explained_variance)


class LinearRegressionModel(
    LinearRegressionClass, _TpuModel, _LinearRegressionTpuParams
):
    """Linear regression model (reference LinearRegressionModel
    regression.py:696-900)."""

    def __init__(self, **attrs: Any) -> None:
        super().__init__(**attrs)
        self.coef_: np.ndarray = np.asarray(attrs["coef_"])
        self.intercept_: float = float(attrs["intercept_"])
        self.n_iter_: int = int(attrs.get("n_iter_", 0))
        self.rmse_: float = float(attrs.get("rmse_", float("nan")))
        self.mse_: float = float(attrs.get("mse_", float("nan")))
        self.r2_: float = float(attrs.get("r2_", float("nan")))
        self.n_cols: int = int(attrs["n_cols"])
        self.dtype: str = str(attrs.get("dtype", "float32"))

    @property
    def coefficients(self) -> np.ndarray:
        """pyspark.ml parity."""
        return self.coef_

    @property
    def intercept(self) -> float:
        return self.intercept_

    @property
    def hasSummary(self) -> bool:
        return np.isfinite(self.rmse_)

    @property
    def summary(self) -> "LinearRegressionTrainingSummary":
        """Training summary (pyspark parity): weighted training rmse/mse/r2
        computed EXACTLY from the fit's sufficient statistics — no second
        data pass (Spark's summary re-reads the training data)."""
        if not self.hasSummary:
            raise RuntimeError("No training summary available on this model")
        return LinearRegressionTrainingSummary(
            rootMeanSquaredError=self.rmse_,
            meanSquaredError=self.mse_,
            r2=self.r2_,
            totalIterations=self.n_iter_,
        )

    def evaluate(self, dataset) -> "LinearRegressionSummary":
        """Metrics of this model on `dataset` (pyspark
        LinearRegressionModel.evaluate; the reference delegates to the
        pyspark CPU model, regression.py:770 — here the TPU transform +
        the metrics subsystem compute them natively)."""
        from ..core import _evaluate_frame
        from ..metrics import RegressionMetrics

        out_df, y, preds, weights = _evaluate_frame(self, dataset)
        # the SPARK param is what _copyValues propagates onto the model
        # (the backend _tpu_params dict stays at defaults here)
        fit_intercept = bool(self.getOrDefault("fitIntercept"))
        return LinearRegressionSummary(
            predictions=out_df,
            metrics=RegressionMetrics.from_predictions(y, preds, weights),
            fit_intercept=fit_intercept,
        )

    def predict(self, value) -> float:
        """Prediction for ONE sample (pyspark LinearRegressionModel.predict;
        the reference falls back to the pyspark CPU model,
        regression.py:764)."""
        v = np.asarray(value, np.float64).reshape(-1)
        coef = np.asarray(self.coef_, np.float64).reshape(-1)
        if v.shape[0] != coef.shape[0]:
            raise ValueError(
                f"feature vector has {v.shape[0]} entries; model expects "
                f"{coef.shape[0]}"
            )
        return float(coef @ v + float(self.intercept_))

    def _transform_device(self, Xs) -> Dict[str, Any]:
        import jax.numpy as jnp

        from ..ops.linear import linreg_predict

        return {
            self.getOrDefault("predictionCol"): linreg_predict(
                Xs,
                jnp.asarray(self.coef_.astype(Xs.dtype)),
                Xs.dtype.type(self.intercept_),
            )
        }

    def cpu(self):
        from sklearn.linear_model import LinearRegression as SkLR

        sk = SkLR()
        sk.coef_ = self.coef_.astype(np.float64)
        sk.intercept_ = float(self.intercept_)
        sk.n_features_in_ = self.n_cols
        return sk


# ---------------------------------------------------------------------------
# RandomForestRegressor (reference regression.py RandomForestRegressor +
# tree.py shared layer)
# ---------------------------------------------------------------------------


from ..models.tree import (  # noqa: E402
    _RandomForestEstimator,
    _RandomForestModel,
)


class RandomForestRegressor(_RandomForestEstimator):
    """Distributed random forest regressor on TPU (API parity: reference
    RandomForestRegressor in regression.py:860-1000 + tree.py:314-528).
    Variance-split histogram trees; ensemble parallelism over the mesh
    (each device fits numTrees/num_workers trees on its local rows,
    reference tree.py:330-341, docstring regression.py:895-899).

    `featureSubsetStrategy="auto"` is a third of the columns a node
    (Spark's rule for regression).  A level's (node, feature, bin)
    histogram of (w, w y, w y^2) is real-valued: float32 sums of labels
    less one constant per worker (its weighted mean label), added a panel
    of features at a time where a node reads many, and a split's gain is
    ranked as (S_l - n_l S/n)^2 / (n_l n_r), so that labels with a mean of
    thousands of standard deviations split as float64 would split them.
    The fitted model's leaves hold (w, sum y, sum y^2) of the labels as
    given; a forest is bit-identical from fit to fit (`ops/forest.py`).

    Examples
    --------
    >>> import numpy as np, pandas as pd
    >>> from spark_rapids_ml_tpu.regression import RandomForestRegressor
    >>> df = pd.DataFrame({"features": [[0.0], [0.1], [0.9], [1.0]],
    ...                    "label": [0.0, 0.0, 10.0, 10.0]})
    >>> rf = RandomForestRegressor(numTrees=5, seed=3, num_workers=1)
    >>> model = rf.setFeaturesCol("features").setLabelCol("label").fit(df)
    >>> [round(v, 1) for v in model.transform(df)["prediction"]]
    [0.0, 0.0, 10.0, 10.0]
    """

    def _is_classification(self) -> bool:
        return False

    def _create_model(self, attrs: Dict[str, Any]) -> "RandomForestRegressionModel":
        return RandomForestRegressionModel(**attrs)

    def _cpu_fit(self, batch: _ArrayBatch) -> "RandomForestRegressionModel":
        raise NotImplementedError(
            "RandomForestRegressor has no CPU fallback; unset unsupported params"
        )


class RandomForestRegressionModel(_RandomForestModel):
    """Random forest regression model (reference
    RandomForestRegressionModel in regression.py)."""

    def _transform_device(self, Xs) -> Dict[str, Any]:
        import jax.numpy as jnp

        from ..ops.forest import forest_apply

        leaves = forest_apply(
            Xs,
            jnp.asarray(self.feature),
            jnp.asarray(self.threshold.astype(Xs.dtype)),
            jnp.asarray(self.left_child),
            max_depth=self.max_depth,
        )  # (T, n)
        stats = jnp.take_along_axis(
            jnp.asarray(self.leaf_stats.astype(Xs.dtype)),
            leaves[:, :, None], axis=1,
        )  # (T, n, 3): (weight, sum y, sum y^2)
        w = jnp.maximum(stats[:, :, 0], 1e-12)
        preds = (stats[:, :, 1] / w).mean(axis=0)
        return {self.getOrDefault("predictionCol"): preds.astype(Xs.dtype)}

    def cpu(self):
        from .classification import _NumpyForestPredictor

        return _NumpyForestPredictor(self, classification=False)

    def predict(self, value) -> float:
        """Single-sample forest mean (the reference falls back to the
        pyspark CPU model; the node-table forest is host-resident)."""
        v = np.asarray(value, np.float64).reshape(1, -1)
        if v.shape[1] != self.n_cols:
            raise ValueError(
                f"feature vector has {v.shape[1]} entries; model expects "
                f"{self.n_cols}"
            )
        return float(self.cpu().predict(v)[0])
