#
# Classification: LogisticRegression + RandomForestClassifier — the
# analog of reference classification.py (1615 LoC).  The cuML
# `LogisticRegressionMG` L-BFGS/OWL-QN distributed solver
# (classification.py:1046-1081) is replaced by ops/logistic.py +
# ops/lbfgs.py: a fully-jitted L-BFGS whose gradient psums over the mesh.
#
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Union

import numpy as np

from ..core import FitInput, _TpuEstimatorSupervised, _TpuModel
from ..params import (
    HasElasticNetParam,
    HasEnableSparseDataOptim,
    HasFeaturesCol,
    HasFeaturesCols,
    HasFitIntercept,
    HasLabelCol,
    HasMaxIter,
    HasPredictionCol,
    HasProbabilityCol,
    HasRawPredictionCol,
    HasRegParam,
    HasStandardization,
    HasTol,
    HasWeightCol,
    Param,
    TypeConverters,
    _TpuParams,
)
from ..utils import _ArrayBatch


def _label_range_kernel(y, w):
    import jax.numpy as jnp

    valid = w > 0
    big = jnp.iinfo(jnp.int32).max
    return (
        jnp.where(valid, y, big).min(),
        jnp.where(valid, y, -1).max(),
    )


def _label_check_kernel(y, w):
    """(is_integral, min_label) among valid rows, for float label arrays."""
    import jax.numpy as jnp

    valid = w > 0
    yf = y.astype(jnp.float32)
    integral = jnp.all(jnp.where(valid, yf == jnp.round(yf), True))
    mn = jnp.where(valid, yf, jnp.inf).min()
    return integral, mn


def _label_range(y, w):
    """(min, max) label among valid (w>0) rows, computed on device."""
    import jax

    global _label_range_jit
    if _label_range_jit is None:
        _label_range_jit = jax.jit(_label_range_kernel)
    # one host round-trip for both scalars (device_get batches the fetch;
    # separate int() casts would each block)
    return jax.device_get(_label_range_jit(y, w))


_label_range_jit = None
_label_check_jit = None


class LogisticRegressionClass:
    """Param mapping (reference LogisticRegressionClass
    classification.py:679-747, incl. the regParam -> C inversion
    classification.py:701-705)."""

    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        return {
            "maxIter": "max_iter",
            "regParam": "C",
            "elasticNetParam": "l1_ratio",
            "tol": "tol",
            "fitIntercept": "fit_intercept",
            # improvements over the reference (-> None there): the TPU
            # predict path honors threshold; the kernel takes sample weights
            "threshold": "",
            "thresholds": None,
            "standardization": "standardization",
            "weightCol": "",
            "aggregationDepth": "",
            "family": "family",
            "lowerBoundsOnCoefficients": None,
            "upperBoundsOnCoefficients": None,
            "lowerBoundsOnIntercepts": None,
            "upperBoundsOnIntercepts": None,
            "maxBlockSizeInMB": "",
        }

    @classmethod
    def _param_value_mapping(cls):
        # Spark regParam -> sklearn/cuml-style inverse C (reference
        # classification.py:701-705): C = 1/regParam, 0 means unregularized.
        # NOTE: value maps here are keyed by the SPARK param name.
        return {"regParam": lambda x: 1.0 / x if x > 0.0 else (0.0 if x == 0.0 else None)}

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "fit_intercept": True,
            "standardization": False,
            "verbose": False,
            "C": 1.0,
            "penalty": "l2",
            "l1_ratio": None,
            "max_iter": 1000,
            "tol": 0.0001,
            "family": "auto",
            "lbfgs_memory": 10,
            "linesearch_max_iter": 20,
        }


class _LogisticRegressionTpuParams(
    _TpuParams,
    HasFeaturesCol,
    HasFeaturesCols,
    HasLabelCol,
    HasPredictionCol,
    HasProbabilityCol,
    HasRawPredictionCol,
    HasEnableSparseDataOptim,
    HasRegParam,
    HasElasticNetParam,
    HasFitIntercept,
    HasStandardization,
    HasMaxIter,
    HasTol,
    HasWeightCol,
):
    """Shared params (reference _LogisticRegressionCumlParams
    classification.py:750-820)."""

    family = Param("_", "family", 'Label distribution: "auto", "binomial", '
                   '"multinomial".', TypeConverters.toString)
    threshold = Param("_", "threshold", "binary prediction threshold in [0,1].",
                      TypeConverters.toFloat)
    # declared for pyspark API parity; mapped to None (unsupported on TPU)
    thresholds = Param("_", "thresholds", "per-class thresholds (unsupported).",
                       TypeConverters.toListFloat)
    lowerBoundsOnCoefficients = Param("_", "lowerBoundsOnCoefficients",
                                      "box constraint (unsupported).",
                                      TypeConverters.identity)
    upperBoundsOnCoefficients = Param("_", "upperBoundsOnCoefficients",
                                      "box constraint (unsupported).",
                                      TypeConverters.identity)
    lowerBoundsOnIntercepts = Param("_", "lowerBoundsOnIntercepts",
                                    "box constraint (unsupported).",
                                    TypeConverters.identity)
    upperBoundsOnIntercepts = Param("_", "upperBoundsOnIntercepts",
                                    "box constraint (unsupported).",
                                    TypeConverters.identity)

    def __init__(self) -> None:
        super().__init__()
        self._setDefault(
            regParam=0.0,
            elasticNetParam=0.0,
            tol=1e-6,
            maxIter=100,
            fitIntercept=True,
            standardization=True,
            family="auto",
            threshold=0.5,
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

    def setProbabilityCol(self, value: str):
        self._set(probabilityCol=value)
        return self

    def setRawPredictionCol(self, value: str):
        self._set(rawPredictionCol=value)
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

    def setThreshold(self, value: float):
        return self._set_params(threshold=value)

    def setFamily(self, value: str):
        return self._set_params(family=value)


class LogisticRegression(
    LogisticRegressionClass, _TpuEstimatorSupervised, _LogisticRegressionTpuParams
):
    """Distributed logistic regression on TPU (API parity: reference
    LogisticRegression classification.py:822-1304).

    Binomial labels use Spark's single-coefficient-vector parameterization;
    multinomial uses softmax with the full coefficient matrix.  Both run the
    jitted L-BFGS (OWL-QN when elasticNetParam > 0) of ops/lbfgs.py with
    `lbfgs_memory=10`, `linesearch_max_iter=20` (cuML's settings, reference
    classification.py:1046-1052).  Standardization is applied on-device and
    coefficients are un-scaled after the solve (reference
    classification.py:1018-1028).

    Examples
    --------
    >>> import pandas as pd
    >>> from spark_rapids_ml_tpu.classification import LogisticRegression
    >>> df = pd.DataFrame({"features": [[1.0, 2.0], [1.0, 3.0], [2.0, 1.0], [3.0, 1.0]],
    ...                    "label": [1.0, 1.0, 0.0, 0.0]})
    >>> model = LogisticRegression(regParam=0.01).setFeaturesCol("features").fit(df)
    >>> model.transform(df)["prediction"].tolist()
    [1, 1, 0, 0]
    """

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._set_params(**kwargs)

    def _fit_label_dtype(self):
        return np.dtype(np.int32)

    def _use_sparse_kernel(self, batch: _ArrayBatch) -> bool:
        # None (auto) -> sparse inputs stay sparse; True forces the sparse
        # kernel even for dense inputs; False forces densify (reference
        # _use_sparse_in_cuml, core.py:183-216)
        opt = self.getOrDefault("enable_sparse_data_optim")
        if opt is True:
            return True
        if opt is False:
            return False
        from ..data import _is_sparse

        return _is_sparse(batch.X)

    def _validate_input(self, batch: _ArrayBatch) -> None:
        classes = np.unique(batch.y)
        if not np.all(classes == classes.astype(np.int64)):
            raise RuntimeError(f"Labels MUST be Integers, but got {classes}")
        if classes.min() < 0:
            raise RuntimeError(f"Labels MUST be non-negative, but got {classes}")

    def _validate_device_input(self, ds) -> None:
        """Same label contract as `_validate_input`, evaluated on device for
        DeviceDataset fits (before the int32 cast would mask violations)."""
        import jax

        global _label_check_jit
        if _label_check_jit is None:
            _label_check_jit = jax.jit(_label_check_kernel)
        integral, mn = jax.device_get(_label_check_jit(ds.y, ds.weight))
        if not bool(integral):
            raise RuntimeError("Labels MUST be Integers")
        if float(mn) < 0:
            raise RuntimeError(f"Labels MUST be non-negative, but got min {mn}")

    def _supports_streaming_stats(self) -> bool:
        # beyond-HBM epoch-streaming L-BFGS (streaming.py
        # `logreg_streaming_fit`): every solver evaluation re-streams the
        # parquet chunks through a donated loss+gradient accumulator
        return True

    def _supports_fold_weights(self) -> bool:
        # convex w-weighted objective, deterministic zero init
        # (ops/logistic.py SUPPORTS_ZERO_WEIGHT_ROWS): a CV fold mask is
        # exactly a zero weight and the optimum is row-count free
        from ..ops import logistic as _logistic_ops

        return bool(_logistic_ops.SUPPORTS_ZERO_WEIGHT_ROWS)

    def _fit_streaming(self, path: str) -> Dict[str, Any]:
        """Beyond-HBM fit: host-driven L-BFGS/OWL-QN whose oracle streams
        the dataset per evaluation — the reachability answer to the 1B-row
        BASELINE workload (dataset bounded by disk, not HBM x chips; the
        analog of the reference's reserved-memory ingest scaling,
        utils.py:403-522 + classification.py:1046-1081)."""
        from ..streaming import logreg_streaming_fit

        fcol, fcols, label_col, weight_col, dtype = self._streaming_io_params()
        if label_col is None:
            raise ValueError("labelCol must be set for LogisticRegression")
        p = self._tpu_params
        C = float(p["C"])
        reg_param = 1.0 / C if C > 0 else 0.0
        l1_ratio = p.get("l1_ratio")
        en = float(l1_ratio) if l1_ratio is not None else float(
            self.getOrDefault("elasticNetParam")
        )
        fit_intercept = bool(p["fit_intercept"])
        from ..resilience.checkpoint import resolve_checkpoint_dir

        ckpt_dir = resolve_checkpoint_dir(streaming=True)
        res = logreg_streaming_fit(
            path, fcol, fcols, label_col, weight_col,
            family=str(self.getOrDefault("family")),
            l2=reg_param * (1.0 - en),
            l1=reg_param * en,
            fit_intercept=fit_intercept,
            standardization=bool(p.get("standardization", True)),
            tol=float(p["tol"]),
            max_iter=int(p["max_iter"]),
            history=int(p.get("lbfgs_memory", 10)),
            ls_max=int(p.get("linesearch_max_iter", 20)),
            dtype=dtype,
            # filename derives from the fit's content tag inside the
            # solver: stable across process restarts (a uid-based name
            # made a preempted-and-restarted fit miss its checkpoint)
            checkpoint_dir=ckpt_dir or None,
        )
        dtype = np.dtype(dtype)
        if "degenerate_label" in res:
            cv = float(res["degenerate_label"])
            if cv not in (0.0, 1.0):
                raise RuntimeError(
                    "class value must be either 1. or 0. when dataset has one label"
                )
            return {
                "coef_": np.zeros((1, res["d"]), dtype),
                "intercept_": np.array(
                    [np.inf if cv == 1.0 else -np.inf], dtype
                ),
                "classes_": [cv],
                "n_cols": res["d"],
                "dtype": str(dtype.name),
                "num_iters": 0,
                "objective": 0.0,
            }
        coef = np.asarray(res["coef"], np.float64)
        intercept = np.asarray(res["intercept"], np.float64)
        if res["std"] is not None:
            std = np.asarray(res["std"], np.float64)
            coef = np.where(std > 0, coef / std, coef)
            if fit_intercept and res["mean"] is not None:
                intercept = intercept - coef @ np.asarray(res["mean"], np.float64)
        if fit_intercept and len(intercept) > 1:
            intercept = intercept - intercept.mean()
        hist = [float(v) for v in res["history"]]
        return {
            "coef_": coef.astype(dtype),
            "intercept_": intercept.astype(dtype),
            "classes_": [float(c) for c in range(res["n_classes"])],
            "n_cols": int(res["d"]),
            "dtype": str(dtype.name),
            "num_iters": int(res["n_iter"]),
            "objective": float(hist[-1]) if hist else 0.0,
            "objective_history": hist,
            "converged": bool(res.get("converged", False)),
            # true dataset passes incl. line-search backtracks
            "streaming_epochs": int(res.get("epochs", 0)),
        }

    def _fit_array(self, fit_input: FitInput) -> Dict[str, Any]:
        import jax.numpy as jnp

        from ..ops.logistic import logreg_fit, logreg_fit_binary
        from ..ops.stats import standardize, weighted_moments
        from ..tracing import event, fact, trace

        p = fit_input.params
        dtype = np.dtype(fit_input.dtype)
        # label range via two on-device scalar reductions — pulling the full
        # y/w arrays to host would cross HBM->host for the whole dataset;
        # integrality was validated host-side pre-staging (_validate_input)
        with trace("label_range"):
            y_min, y_max = _label_range(fit_input.y, fit_input.w)
        y_min, y_max = int(y_min), int(y_max)

        # degenerate single-label dataset (Spark semantics: +/-inf intercept,
        # reference classification.py:1106-1121)
        if y_min == y_max:
            cv = float(y_min)
            if cv not in (0.0, 1.0):
                raise RuntimeError(
                    "class value must be either 1. or 0. when dataset has one label"
                )
            return {
                "coef_": np.zeros((1, fit_input.pdesc.n), dtype),
                "intercept_": np.array([np.inf if cv == 1.0 else -np.inf], dtype),
                "classes_": [cv],
                "n_cols": fit_input.pdesc.n,
                "dtype": str(dtype.name),
                "num_iters": 0,
                "objective": 0.0,
            }

        # Spark numClasses = max(label)+1 (can include empty classes;
        # cuML instead uses unique - see reference TODO classification.py:1106)
        n_classes = y_max + 1
        family = str(self.getOrDefault("family"))
        binomial = n_classes == 2 and family in ("auto", "binomial")

        C = float(p["C"])
        reg_param = 1.0 / C if C > 0 else 0.0
        l1_ratio = p.get("l1_ratio")
        en = float(l1_ratio) if l1_ratio is not None else float(
            self.getOrDefault("elasticNetParam")
        )
        l2 = reg_param * (1.0 - en)
        l1 = reg_param * en
        fit_intercept = bool(p["fit_intercept"])
        standardization = bool(p.get("standardization", True))
        tol = float(p["tol"])
        max_iter = int(p["max_iter"])

        import jax

        w = fit_input.w
        sparse = "ell_cols" in fit_input.extra
        # estimator-wide checkpoint/resume: `checkpoint_dir` set -> the
        # host-dispatched (checkpointable) solver runs regardless of the
        # FLOP gate — the fused while_loop is one opaque device program
        # with no iteration boundary to persist at
        from ..resilience.checkpoint import (
            checkpoint_file_for,
            resolve_checkpoint_dir,
        )

        ckpt_dir = resolve_checkpoint_dir()
        ckpt_path = None
        ckpt_tag = ""
        if ckpt_dir:
            from ..core import _fit_fingerprint

            # m (lbfgs_memory) is shape-critical: the checkpointed S/Y
            # history buffers are (m, n), so a resume under a different m
            # must tag-mismatch and start fresh, not broadcast-fail.
            # n binds n_valid, never the padded shape: padding depends on
            # the device count, and an elastic resume on a shrunken mesh
            # must derive the same tag (resilience/elastic.py)
            ckpt_tag = (
                f"logreg-mem|n={int(fit_input.n_valid)}"
                f"|d={fit_input.pdesc.n}|C={n_classes}|l2={l2}|l1={l1}"
                f"|int={fit_intercept}|std={standardization}|mi={max_iter}"
                f"|m={int(p.get('lbfgs_memory', 10))}"
                f"|ls={int(p.get('linesearch_max_iter', 20))}"
                f"|{_fit_fingerprint(fit_input)}"
            )
            ckpt_path = checkpoint_file_for(ckpt_dir, ckpt_tag)
        kwargs = dict(
            l2=l2,
            l1=l1,
            fit_intercept=fit_intercept,
            tol=tol,
            max_iter=max_iter,
            history=int(p.get("lbfgs_memory", 10)),
            ls_max=int(p.get("linesearch_max_iter", 20)),
        )
        mean = std = None
        n_evals = None  # evaluations the solver ran, where it counts them
        if sparse:
            # ELL sparse path (the analog of the reference's CSR
            # LogisticRegressionMG, classification.py:1054-1055).
            # Standardization is std-scaling only — no centering, which
            # preserves sparsity and (with an intercept) the same optimum.
            from ..ops.logistic import logreg_fit_binary_ell, logreg_fit_ell
            from ..ops.sparse import ell_scale_columns, ell_weighted_moments

            vals, cols = fit_input.X, fit_input.extra["ell_cols"]
            d = fit_input.pdesc.n
            evaluation = "autodiff"
            event(
                f"lbfgs_eval_kernel[{evaluation}]",
                detail="ELL sparse rows: the margin is a gather-contract",
            )
            if standardization:
                _, std = ell_weighted_moments(vals, cols, w, d=d)
                vals = ell_scale_columns(vals, cols, 1.0 / std)
            # same per-program budget gate as the dense branch: a
            # reference-scale sparse fit must not compile the whole solve
            # into one program either (`dispatch_flops_limit`)
            from ..config import get_config

            C_eff = 1 if binomial else n_classes
            per_eval = 4.0 * vals.shape[0] * vals.shape[1] * C_eff
            budget = float(get_config("dispatch_flops_limit"))
            if per_eval * max_iter * 2.0 > budget or ckpt_path:
                from ..ops.logistic import logreg_fit_host_dispatch
                from ..ops.sparse import ell_matmat, ell_matvec

                self.logger.info(
                    "LogisticRegression: host-dispatched L-BFGS (sparse; "
                    f"{per_eval * max_iter * 2.0:.2e} fused FLOPs vs "
                    f"budget {budget:.0e}, checkpointing "
                    f"{'on' if ckpt_path else 'off'})"
                )
                coef, b, loss, n_iter, hist, n_evals = logreg_fit_host_dispatch(
                    vals, w, fit_input.y, n_classes=n_classes,
                    binomial=binomial, d=d,
                    data=(vals, cols),
                    margin_fn=lambda dat, beta: ell_matvec(*dat, beta),
                    logits_fn=lambda dat, Wm: ell_matmat(*dat, Wm),
                    checkpoint_path=ckpt_path,
                    checkpoint_tag=ckpt_tag,
                    **kwargs,
                )
            elif binomial:
                coef, b, loss, n_iter, hist = logreg_fit_binary_ell(
                    vals, cols, w, fit_input.y, d=d, **kwargs
                )
            else:
                coef, b, loss, n_iter, hist, n_evals = logreg_fit_ell(
                    vals, cols, w, fit_input.y, n_classes=n_classes, d=d,
                    **kwargs
                )
        else:
            X = fit_input.X
            if standardization:
                mean, std, _ = weighted_moments(X, w)
                if fit_intercept:
                    X = standardize(X, w, mean, std)
                else:
                    # no intercept to absorb a centering shift: scale only
                    # (Spark's aggregators never center; this keeps the
                    # optimum identical to the sparse path as well)
                    X = standardize(
                        X, w, jnp.zeros_like(mean), std
                    )
                    mean = None
            from ..config import get_config

            if get_config("bf16_features") and X.dtype == jnp.float32:
                # bandwidth lever: the L-BFGS margin/gradient matvecs are
                # HBM-bound; bf16 feature STORAGE halves the bytes per
                # iteration while the solver state and accumulation stay
                # f32 (the MXU consumes bf16 natively).  Opt-in: costs ~3
                # decimal digits of feature precision.
                X = X.astype(jnp.bfloat16)
            # which evaluation either route runs is read from the rows
            # alone: one read of them an evaluation (a Pallas kernel) for
            # dense float32 binomial rows on TPUs, for dense float32
            # multinomial rows on TPUs another kernel over row tiles or the
            # rows in blocks, autodiff otherwise
            from ..ops.logistic import (
                one_pass_program_bytes,
                softmax_plan,
                softmax_program_bytes,
            )
            from ..ops.pallas_logistic import one_pass_plan
            from ..parallel.device_cache import bytes_beside, fused_program_fits

            one_pass, why_kernel = one_pass_plan(X, binomial)
            softmax = None
            if not binomial:
                softmax, why_kernel = softmax_plan(X, n_classes)
            evaluation = (
                "one_pass" if one_pass else softmax.evaluation if softmax else "autodiff"
            )
            # fused single-program L-BFGS while the device holds what that
            # program holds beside the resident rows
            # (`device_cache.fused_program_fits`, the memory test KMeans'
            # router reads too), which the evaluation decides: the kernel
            # reads the rows where they lie, so the program holds a few
            # rows-length vectors; autodiff's `while_loop` holds a second
            # copy of the rows.  Else, and for a checkpointed fit (its
            # state persists per iteration), host-driven L-BFGS, one
            # evaluation per program.
            checkpointing = f"checkpointing {'on' if ckpt_path else 'off'}"
            if one_pass is not None or softmax is not None:
                # no FLOP budget here: the program's length is no memory
                # question, and the budget's reason was the dispatch
                # timeout of a development link that is gone (the
                # published depth on the chip: PERF.md §6, PR 38)
                shard_rows, d = X.addressable_shards[0].data.shape
                if one_pass is not None:
                    held = one_pass_program_bytes(shard_rows, d, kwargs["history"])
                else:
                    held = softmax_program_bytes(
                        shard_rows, d, n_classes, kwargs["history"], softmax
                    )
                fits = fused_program_fits(X, held, rows_in_place=True)
                host_dispatch = bool(ckpt_path) or not fits
                why = (
                    f"the {evaluation} evaluation reads the rows in place: "
                    f"the fused program "
                    f"holds at most {held:.3g} B beside them, "
                    f"{bytes_beside(X):.3g} B are free, so it "
                    f"{'fits' if fits else 'does NOT fit'} the device; "
                    f"{checkpointing}"
                )
            else:
                # autodiff keeps the per-program budget too
                # (`dispatch_flops_limit`; the reference 1M x 3000
                # maxIter=200 config crosses it), inherited from a
                # development link that is gone (ROADMAP Design 2)
                C_eff = 1 if binomial else n_classes
                per_eval = 4.0 * X.shape[0] * X.shape[1] * C_eff
                fused_flops = per_eval * max_iter * 2.0  # ~2 evals/iter
                budget = float(get_config("dispatch_flops_limit"))
                fits = fused_program_fits(X)
                host_dispatch = fused_flops > budget or bool(ckpt_path) or not fits
                why = (
                    f"{fused_flops:.2e} fused FLOPs vs budget {budget:.0e}, "
                    f"{checkpointing}, fused "
                    f"program's second copy of the features "
                    f"{'fits' if fits else 'does NOT fit'} the device"
                )
            # which solver ran is a fact of the fit: it goes in the fit
            # report's span tree, not only in the log
            event(
                f"lbfgs_route[{'host_dispatch' if host_dispatch else 'fused'}]",
                detail=why,
            )
            event(f"lbfgs_eval_kernel[{evaluation}]", detail=why_kernel)
            if host_dispatch:
                from ..ops.logistic import logreg_fit_host_dispatch

                self.logger.info(
                    f"LogisticRegression: host-dispatched L-BFGS ({why})"
                )
                coef, b, loss, n_iter, hist, n_evals = logreg_fit_host_dispatch(
                    X, w, fit_input.y, n_classes=n_classes,
                    binomial=binomial, checkpoint_path=ckpt_path,
                    checkpoint_tag=ckpt_tag, one_pass=one_pass,
                    softmax=softmax, **kwargs
                )
            else:
                # asynchronous: the call returns once the one program is
                # dispatched (or, the first time, compiled); the wait for
                # it lands in `solve_fetch`
                with trace("lbfgs_fused_dispatch"):
                    if binomial:
                        coef, b, loss, n_iter, hist = logreg_fit_binary(
                            X, w, fit_input.y, one_pass=one_pass, **kwargs
                        )
                    else:
                        coef, b, loss, n_iter, hist, n_evals = logreg_fit(
                            X, w, fit_input.y, n_classes=n_classes,
                            softmax=softmax, **kwargs
                        )
        # ONE batched device->host fetch for every output (each separate
        # np.asarray/float() would pay a full host sync)
        fetch = {"coef": coef, "b": b, "loss": loss, "n_iter": n_iter,
                 "hist": hist, "n_evals": n_evals}
        if standardization:
            fetch["std"] = std
            if mean is not None:
                fetch["mean"] = mean
        with trace("solve_fetch"):
            host = jax.device_get(fetch)
        loss, n_iter = host["loss"], host["n_iter"]
        # what the solver did, once a fit (`fit_report()["lbfgs"]`); the
        # fused binomial program does not count its evaluations (None)
        fact(
            "lbfgs",
            evaluations=None if host["n_evals"] is None else int(host["n_evals"]),
            iterations=int(n_iter), classes=n_classes, evaluation=evaluation,
        )
        if binomial:
            coef = np.asarray(host["coef"], np.float64).reshape(1, -1)
            intercept = np.array([float(host["b"])])
        else:
            coef = np.asarray(host["coef"], np.float64)
            intercept = np.asarray(host["b"], np.float64)

        if standardization:
            std = np.asarray(host["std"], np.float64)
            coef = np.where(std > 0, coef / std, coef)
            if fit_intercept and "mean" in host:
                # dense path centers features; undo the shift (the sparse
                # path never centers, so its intercept is already correct)
                mean = np.asarray(host["mean"], np.float64)
                intercept = intercept - coef @ mean
        # Spark centers multinomial intercepts (softmax shift-invariance;
        # reference classification.py:1135-1147)
        if fit_intercept and len(intercept) > 1:
            intercept = intercept - intercept.mean()

        # Spark's LogisticRegressionTrainingSummary.objectiveHistory:
        # FULL (penalty-inclusive) objective per iteration, entry 0 =
        # initial.  Entries 0..n_iter are all written; strip only a
        # defensive trailing-NaN tail so objectiveHistory[j] always means
        # iteration j (a mid-run non-finite objective is reported, not
        # hidden).
        hist = np.asarray(host["hist"], np.float64)[: int(n_iter) + 1]
        while len(hist) and np.isnan(hist[-1]):
            hist = hist[:-1]
        if len(hist):
            # `objective` matches the history definition (incl. the L1
            # term under OWL-QN) so summary.objectiveHistory[-1] ==
            # model.objective always holds
            loss = hist[-1]
        return {
            "coef_": coef.astype(dtype),
            "intercept_": intercept.astype(dtype),
            "classes_": [float(c) for c in range(n_classes)],
            "n_cols": fit_input.pdesc.n,
            "dtype": str(dtype.name),
            "num_iters": int(n_iter),
            "objective": float(loss),
            "objective_history": [float(v) for v in hist],
        }

    def _create_model(self, attrs: Dict[str, Any]) -> "LogisticRegressionModel":
        return LogisticRegressionModel(**attrs)

    def _cpu_fit(self, batch: _ArrayBatch) -> "LogisticRegressionModel":
        from sklearn.linear_model import LogisticRegression as SkLR

        reg = self.getOrDefault("regParam")
        en = self.getOrDefault("elasticNetParam")
        n = batch.X.shape[0]
        if reg == 0.0:
            sk = SkLR(penalty=None, fit_intercept=self.getOrDefault("fitIntercept"),
                      max_iter=1000)
        elif en == 0.0:
            sk = SkLR(C=1.0 / (reg * n), penalty="l2", max_iter=1000,
                      fit_intercept=self.getOrDefault("fitIntercept"))
        else:
            sk = SkLR(C=1.0 / (reg * n), penalty="elasticnet", l1_ratio=en,
                      solver="saga", max_iter=5000,
                      fit_intercept=self.getOrDefault("fitIntercept"))
        sk.fit(batch.X, batch.y.astype(np.int32), sample_weight=batch.weight)
        return LogisticRegressionModel(
            coef_=np.asarray(sk.coef_, batch.X.dtype),
            intercept_=np.asarray(sk.intercept_, batch.X.dtype),
            classes_=[float(c) for c in sk.classes_],
            n_cols=int(batch.X.shape[1]),
            dtype=str(batch.X.dtype),
            num_iters=int(np.max(sk.n_iter_)),
            objective=0.0,
        )


class LogisticRegressionTrainingSummary:
    """Spark LogisticRegressionTrainingSummary analog (the surface
    tests_large reads: `model.summary.objectiveHistory`,
    reference tests_large/test_large_logistic_regression.py:39-60)."""

    def __init__(self, objectiveHistory: List[float], totalIterations: int):
        self.objectiveHistory = list(objectiveHistory)
        self.totalIterations = int(totalIterations)


class LogisticRegressionModel(
    LogisticRegressionClass, _TpuModel, _LogisticRegressionTpuParams
):
    """Logistic regression model (reference LogisticRegressionModel
    classification.py:1306-1615)."""

    def __init__(self, **attrs: Any) -> None:
        super().__init__(**attrs)
        self.coef_: np.ndarray = np.atleast_2d(np.asarray(attrs["coef_"]))
        self.intercept_: np.ndarray = np.atleast_1d(np.asarray(attrs["intercept_"]))
        self.classes_: List[float] = [float(c) for c in attrs["classes_"]]
        self.n_cols: int = int(attrs["n_cols"])
        self.dtype: str = str(attrs.get("dtype", "float32"))
        self.num_iters: int = int(attrs.get("num_iters", 0))
        self.objective: float = float(attrs.get("objective", 0.0))
        self.objective_history: List[float] = [
            float(v) for v in attrs.get("objective_history", [])
        ]

    @property
    def numClasses(self) -> int:
        return len(self.classes_)

    @property
    def hasSummary(self) -> bool:
        # always available after fit (pyspark parity); paths without a
        # solver trace (degenerate single-label, CPU fallback) report the
        # single final objective
        return True

    @property
    def summary(self) -> "LogisticRegressionTrainingSummary":
        """Training summary (pyspark parity: objectiveHistory records the
        full objective per L-BFGS iteration — Spark's
        LogisticRegressionTrainingSummary surface)."""
        return LogisticRegressionTrainingSummary(
            objectiveHistory=self.objective_history or [self.objective],
            totalIterations=self.num_iters,
        )

    @property
    def coefficients(self) -> np.ndarray:
        """Binary models: the single coefficient vector (pyspark parity)."""
        if self.coef_.shape[0] == 1:
            return self.coef_[0]
        raise RuntimeError("Multinomial model: use coefficientMatrix")

    @property
    def coefficientMatrix(self) -> np.ndarray:
        return self.coef_

    @property
    def intercept(self) -> float:
        if len(self.intercept_) == 1:
            return float(self.intercept_[0])
        raise RuntimeError("Multinomial model: use interceptVector")

    @property
    def interceptVector(self) -> np.ndarray:
        return self.intercept_

    def _is_binomial(self) -> bool:
        return self.coef_.shape[0] == 1

    def _output_columns(self) -> List[str]:
        return [
            self.getOrDefault("predictionCol"),
            self.getOrDefault("probabilityCol"),
            self.getOrDefault("rawPredictionCol"),
        ]

    def _transform_array(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        # +/-inf intercepts (single-label degenerate model) can't go
        # through XLA math cleanly; handle on host
        if self._is_binomial() and not np.isfinite(self.intercept_[0]):
            n = X.shape[0]
            p1 = 1.0 if self.intercept_[0] > 0 else 0.0
            dt = X.dtype if hasattr(X, "dtype") else np.float32
            preds = np.full(n, p1, np.int32)
            probs = np.tile([1.0 - p1, p1], (n, 1)).astype(dt)
            raw = np.tile(
                [-self.intercept_[0], self.intercept_[0]], (n, 1)
            ).astype(dt)
            return {
                self.getOrDefault("predictionCol"): preds,
                self.getOrDefault("probabilityCol"): probs,
                self.getOrDefault("rawPredictionCol"): raw,
            }
        return super()._transform_array(X)

    def _transform_device(self, Xs) -> Dict[str, Any]:
        import jax.numpy as jnp

        from ..ops.logistic import binary_predict, logreg_predict

        if self._is_binomial():
            preds, probs, raw = binary_predict(
                Xs,
                jnp.asarray(self.coef_[0].astype(Xs.dtype)),
                Xs.dtype.type(self.intercept_[0]),
            )
            threshold = float(self.getOrDefault("threshold"))
            if threshold != 0.5:
                preds = (probs[:, 1] > threshold).astype(jnp.int32)
        else:
            preds, probs, raw = logreg_predict(
                Xs,
                jnp.asarray(self.coef_.astype(Xs.dtype)),
                jnp.asarray(self.intercept_.astype(Xs.dtype)),
            )
        return {
            self.getOrDefault("predictionCol"): preds.astype(jnp.int32),
            self.getOrDefault("probabilityCol"): probs,
            self.getOrDefault("rawPredictionCol"): raw,
        }

    # -- single-sample API (pyspark Model surface).  The reference falls
    # back to the pyspark CPU model here (classification.py:1593-1615);
    # the coefficient math is host-resident, so compute directly. --------

    def _margins(self, value) -> np.ndarray:
        v = np.asarray(value, np.float64).reshape(-1)
        if v.shape[0] != self.n_cols:
            raise ValueError(
                f"feature vector has {v.shape[0]} entries; model expects "
                f"{self.n_cols}"
            )
        return self.coef_.astype(np.float64) @ v + self.intercept_.astype(
            np.float64
        )

    def predictRaw(self, value) -> np.ndarray:
        """Raw margin vector for one sample (Spark: [-m, m] for binomial)."""
        m = self._margins(value)
        if self._is_binomial():
            return np.array([-m[0], m[0]])
        return m

    def predictProbability(self, value) -> np.ndarray:
        m = self._margins(value)
        if self._is_binomial():
            p1 = 1.0 / (1.0 + np.exp(-m[0]))
            return np.array([1.0 - p1, p1])
        e = np.exp(m - m.max())
        return e / e.sum()

    def predict(self, value) -> float:
        probs = self.predictProbability(value)
        if self._is_binomial():
            threshold = float(self.getOrDefault("threshold"))
            return float(probs[1] > threshold)
        return float(np.argmax(probs))

    def evaluate(self, dataset) -> "LogisticRegressionSummary":
        """Metrics of this model on `dataset` (pyspark
        LogisticRegressionModel.evaluate; the reference delegates to the
        pyspark CPU model — here the TPU transform + the metrics
        subsystem compute them natively).  Goes through the standard
        `_transform`, so featuresCol/featuresCols resolution, chunked
        distributed inference, and the full predictions frame (original
        columns + prediction/probability/rawPrediction) all apply."""
        return _evaluate_classification(self, dataset, LogisticRegressionSummary)

    def cpu(self):
        from sklearn.linear_model import LogisticRegression as SkLR

        sk = SkLR()
        if self._is_binomial():
            sk.coef_ = self.coef_.astype(np.float64)
            sk.intercept_ = self.intercept_.astype(np.float64)
            sk.classes_ = np.array([0.0, 1.0])
        else:
            sk.coef_ = self.coef_.astype(np.float64)
            sk.intercept_ = self.intercept_.astype(np.float64)
            sk.classes_ = np.array(self.classes_)
        sk.n_features_in_ = self.n_cols
        return sk


class _ClassificationSummary:
    """Shared evaluation summary (the pyspark classification summary
    surface over the metrics subsystem)."""

    def __init__(self, predictions, metrics) -> None:
        self.predictions = predictions
        self._m = metrics

    @property
    def accuracy(self) -> float:
        return float(self._m.accuracy)

    @property
    def weightedPrecision(self) -> float:
        return float(self._m.weighted_precision)

    @property
    def weightedRecall(self) -> float:
        return float(self._m.weighted_recall)

    def weightedFMeasure(self, beta: float = 1.0) -> float:
        # a METHOD, matching pyspark's summary surface
        return float(self._m.weighted_f_measure(beta))


class LogisticRegressionSummary(_ClassificationSummary):
    pass


class RandomForestClassificationSummary(_ClassificationSummary):
    pass


def _evaluate_classification(model, dataset, summary_cls):
    """Shared evaluate() tail for the classification models: the standard
    transform front half + multiclass metrics -> summary."""
    from ..core import _evaluate_frame
    from ..metrics import MulticlassMetrics

    out_df, y, preds, weights = _evaluate_frame(model, dataset)
    return summary_cls(
        predictions=out_df,
        metrics=MulticlassMetrics.from_predictions(y, preds, weights=weights),
    )


# ---------------------------------------------------------------------------
# RandomForestClassifier (reference classification.py RandomForestClassifier
# + tree.py shared layer)
# ---------------------------------------------------------------------------


from ..models.tree import (  # noqa: E402
    _RandomForestEstimator,
    _RandomForestModel,
)


class RandomForestClassifier(
    _RandomForestEstimator, HasProbabilityCol, HasRawPredictionCol
):
    """Distributed random forest classifier on TPU (API parity: reference
    RandomForestClassifier in classification.py + tree.py:314-528).

    Ensemble parallelism matches the reference (tree.py:330-341): each mesh
    device grows numTrees/num_workers trees on its local row shard with the
    ops/forest.py histogram builder; no collectives are needed during
    growth (the reference similarly uses no NCCL for RF, tree.py:523-524).

    Growth is exact level by level to `maxDepth`: a level's frontier holds
    min(2^level, the worker's rows) nodes and the node table is the heap.
    (`max_active_nodes`, a backend parameter, caps the frontier for those
    who pass it; above the cap the largest nodes keep growing.)  Bin edges
    are quantiles of a seeded stratified sample of max(maxBins^2, 10,000)
    of each worker's rows; bin ids are 8-bit, so `maxBins` <= 256.  The
    draws (edge sample, Poisson bootstrap weights, each node's features)
    are stated in ops/forest.py's header; the same seed and rows give the
    same forest, bit for bit, fit after fit.

    Examples
    --------
    >>> import numpy as np, pandas as pd
    >>> from spark_rapids_ml_tpu.classification import RandomForestClassifier
    >>> df = pd.DataFrame({"features": [[0.0], [0.1], [0.9], [1.0]],
    ...                    "label": [0.0, 0.0, 1.0, 1.0]})
    >>> rf = RandomForestClassifier(numTrees=5, seed=7, num_workers=1)
    >>> model = rf.setFeaturesCol("features").setLabelCol("label").fit(df)
    >>> model.transform(df)["prediction"].tolist()
    [0, 0, 1, 1]
    """

    def setProbabilityCol(self, value: str):
        self._set(probabilityCol=value)
        return self

    def setRawPredictionCol(self, value: str):
        self._set(rawPredictionCol=value)
        return self

    def _is_classification(self) -> bool:
        return True

    def _validate_input(self, batch: _ArrayBatch) -> None:
        y = np.asarray(batch.y)
        classes = np.unique(y)
        if np.any(classes < 0) or not np.allclose(classes, np.round(classes)):
            # reference error remap tree.py:415-421
            raise ValueError(
                "Labels must be non-negative integers 0..numClasses-1, got "
                f"{classes[:10]}"
            )

    def _validate_device_input(self, ds) -> None:
        # device-side label check for DeviceDataset fits (same contract as
        # the host path; mirrors LogisticRegression's device validation)
        import jax

        global _label_check_jit
        if _label_check_jit is None:
            _label_check_jit = jax.jit(_label_check_kernel)
        integral, mn = jax.device_get(_label_check_jit(ds.y, ds.weight))
        if not bool(integral) or float(mn) < 0:
            raise ValueError(
                "Labels must be non-negative integers 0..numClasses-1"
            )

    def _num_stat_classes(self, fit_input: FitInput) -> int:
        import jax

        from ..tracing import trace

        # labels are validated >= 0; padded rows are 0, so a plain max works
        # (one scalar device->host fetch)
        with trace("label_range"):
            C = int(jax.device_get(fit_input.y.max())) + 1
        self._n_classes_ = C
        return C

    def _fit_array(self, fit_input: FitInput) -> Dict[str, Any]:
        attrs = super()._fit_array(fit_input)
        attrs["num_classes"] = self._n_classes_
        return attrs

    def _create_model(self, attrs: Dict[str, Any]) -> "RandomForestClassificationModel":
        return RandomForestClassificationModel(**attrs)

    def _cpu_fit(self, batch: _ArrayBatch) -> "RandomForestClassificationModel":
        raise NotImplementedError(
            "RandomForestClassifier has no CPU fallback; unset unsupported params"
        )


class RandomForestClassificationModel(
    _RandomForestModel, HasProbabilityCol, HasRawPredictionCol
):
    """Random forest classification model (reference
    RandomForestClassificationModel in classification.py)."""

    def __init__(self, **attrs: Any) -> None:
        super().__init__(**attrs)
        self.num_classes: int = int(attrs.get("num_classes",
                                              self.leaf_stats.shape[-1]))

    @property
    def numClasses(self) -> int:
        return self.num_classes

    def _output_columns(self) -> List[str]:
        return [
            self.getOrDefault("predictionCol"),
            self.getOrDefault("probabilityCol"),
            self.getOrDefault("rawPredictionCol"),
        ]

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
        # per-tree leaf class-count distributions, normalized per tree then
        # summed (Spark rawPrediction semantics)
        stats = jnp.asarray(self.leaf_stats.astype(Xs.dtype))  # (T, L, C)
        counts = jnp.take_along_axis(stats, leaves[:, :, None], axis=1)
        sums = jnp.maximum(counts.sum(axis=2, keepdims=True), 1e-12)
        raw = (counts / sums).sum(axis=0)  # (n, C)
        probs = raw / self.numTrees
        preds = jnp.argmax(raw, axis=1).astype(jnp.int32)
        return {
            self.getOrDefault("predictionCol"): preds,
            self.getOrDefault("probabilityCol"): probs,
            self.getOrDefault("rawPredictionCol"): raw,
        }

    def cpu(self):
        """Pure-numpy predictor mirroring the fitted forest (the reference
        converts treelite -> Spark model, utils.py:585-809; here the model
        arrays themselves are the portable format)."""
        return _NumpyForestPredictor(self, classification=True)

    # single-sample API (the reference falls back to the pyspark CPU
    # model, classification.py:606-616; the node-table forest is
    # host-resident, so the numpy predictor answers directly)

    def predictProbability(self, value) -> np.ndarray:
        v = np.asarray(value, np.float64).reshape(1, -1)
        if v.shape[1] != self.n_cols:
            raise ValueError(
                f"feature vector has {v.shape[1]} entries; model expects "
                f"{self.n_cols}"
            )
        return self.cpu().predict_proba(v)[0]

    def predictRaw(self, value) -> np.ndarray:
        # rawPrediction = per-tree normalized class votes summed
        return self.predictProbability(value) * self.numTrees

    def predict(self, value) -> float:
        return float(np.argmax(self.predictProbability(value)))

    def evaluate(self, dataset) -> "RandomForestClassificationSummary":
        """Metrics of this model on `dataset` (pyspark
        RandomForestClassificationModel.evaluate; absent from the
        reference entirely)."""
        return _evaluate_classification(
            self, dataset, RandomForestClassificationSummary
        )


class _NumpyForestPredictor:
    """Host-side forest predictor over the portable model arrays."""

    def __init__(self, model: _RandomForestModel, classification: bool) -> None:
        self.feature = model.feature
        self.threshold = model.threshold
        self.leaf_stats = model.leaf_stats
        self.left_child = model.left_child
        self.max_depth = model.max_depth
        self.classification = classification

    def _leaves(self, X: np.ndarray) -> np.ndarray:
        T, n = self.feature.shape[0], X.shape[0]
        node = np.zeros((T, n), np.int64)
        for _ in range(self.max_depth):
            f = np.take_along_axis(self.feature, node, axis=1)
            thr = np.take_along_axis(self.threshold, node, axis=1)
            lc = np.take_along_axis(self.left_child, node, axis=1)
            x = X[np.arange(n)[None, :], np.maximum(f, 0)]
            child = lc + (x > thr)
            node = np.where(f < 0, node, child)
        return node

    def predict(self, X: np.ndarray) -> np.ndarray:
        leaves = self._leaves(np.asarray(X))
        stats = np.take_along_axis(
            self.leaf_stats, leaves[:, :, None], axis=1
        )
        if self.classification:
            sums = np.maximum(stats.sum(axis=2, keepdims=True), 1e-12)
            return np.argmax((stats / sums).sum(axis=0), axis=1)
        w = np.maximum(stats[:, :, 0], 1e-12)
        return (stats[:, :, 1] / w).mean(axis=0)

    def predict_proba(self, X: np.ndarray) -> np.ndarray:
        assert self.classification
        leaves = self._leaves(np.asarray(X))
        stats = np.take_along_axis(
            self.leaf_stats, leaves[:, :, None], axis=1
        )
        sums = np.maximum(stats.sum(axis=2, keepdims=True), 1e-12)
        probs = (stats / sums).sum(axis=0)
        return probs / probs.sum(axis=1, keepdims=True)
