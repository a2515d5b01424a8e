#
# Feature transformers: PCA — the analog of reference feature.py (468 LoC).
# The cuML PCAMG distributed fit (feature.py:240-261) is replaced by
# ops/pca.py: the covariance from the sharded rows in place + the top-k
# eigenpairs of it on the host.
#
from __future__ import annotations

from typing import Any, Dict, List, Optional, Union

import numpy as np

from ..core import FitInput, _TpuEstimator, _TpuModel
from ..params import (
    HasFeaturesCol,
    HasFeaturesCols,
    HasInputCol,
    HasOutputCol,
    Param,
    TypeConverters,
    _TpuParams,
)
from ..utils import _ArrayBatch


class PCAClass:
    """Param mapping (reference PCAClass feature.py:60-75)."""

    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        return {"k": "n_components"}

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "n_components": None,
            "svd_solver": "auto",
            "verbose": False,
            "whiten": False,
        }


class _PCATpuParams(_TpuParams, HasInputCol, HasOutputCol, HasFeaturesCol, HasFeaturesCols):
    """Shared params for PCA / PCAModel (reference _PCACumlParams
    feature.py:77-130)."""

    k = Param("_", "k", "the number of principal components.", TypeConverters.toInt)
    inputCols = Param(
        "_", "inputCols", "input column names for multi-column features.",
        TypeConverters.toListString,
    )

    def setInputCol(self, value: Union[str, List[str]]) -> "_PCATpuParams":
        if isinstance(value, str):
            self._set_params(inputCol=value)
        else:
            self._set_params(inputCols=value)
        return self

    def setInputCols(self, value: List[str]) -> "_PCATpuParams":
        return self._set_params(inputCols=value)

    def setOutputCol(self, value: str) -> "_PCATpuParams":
        return self._set_params(outputCol=value)

    def getInputCol(self) -> Union[str, List[str]]:
        if self.isSet(self.inputCols):
            return self.getOrDefault(self.inputCols)
        if self.isDefined(self.inputCol):
            return self.getOrDefault(self.inputCol)
        raise RuntimeError("inputCol is not set")

    def setK(self, value: int) -> "_PCATpuParams":
        return self._set_params(k=value)

    def getK(self) -> int:
        return self.getOrDefault("k")


class PCA(PCAClass, _TpuEstimator, _PCATpuParams):
    """Distributed PCA on TPU (API parity: reference PCA feature.py:117-297).

    Learns the top-k principal components of row-sharded data with a single
    psum'd Gram matrix per fit.  Spark semantics: `transform` projects the
    raw (uncentered) input onto the components.

    Examples
    --------
    >>> import pandas as pd
    >>> from spark_rapids_ml_tpu.feature import PCA
    >>> df = pd.DataFrame({"features": [[-1.0, -1.0], [0.0, 0.0], [1.0, 1.0]]})
    >>> model = PCA(k=1).setInputCol("features").setOutputCol("pca_features").fit(df)
    >>> model.transform(df)["pca_features"].tolist()  # doctest: +SKIP
    [[-1.414...], [0.0], [1.414...]]
    """

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(k=None)
        self._set_params(**kwargs)

    def _fit_array(self, fit_input: FitInput) -> Dict[str, Any]:
        import jax

        from ..ops.pca import (
            pca_eigensolve_resident,
            pca_fit_randomized,
            pca_scatter,
            resolve_pca_solver,
        )
        from ..tracing import trace

        d = fit_input.pdesc.n
        k = fit_input.params.get("n_components") or d
        if k > d:
            raise ValueError(f"k={k} exceeds the number of features {d}")
        k = int(k)
        X, w = fit_input.X, fit_input.w
        # solver dispatch (conf pca_solver=auto|full|randomized): resident
        # rows whose Gram and (d,d) eigensolve are each a fraction of a
        # second get the exact answer, as the reference's cuML MG path
        # gives it; longer or wider than that, the
        # randomized range-finder scales the Gram work O(n d l) instead
        # of O(n d^2) when k << d (ops/pca.py resolve_pca_solver)
        solver, l, power_iters, _reason = resolve_pca_solver(
            d, k, resident_rows=int(X.shape[0]) // max(len(X.devices()), 1)
        )
        if solver == "randomized":
            out = pca_fit_randomized(X, w, k, int(l), int(power_iters))
        else:
            # the device's two passes, the copy to the host and the
            # eigensolve (a device program of milliseconds, then the host)
            # each under a span of its own, as a ridge fit's
            with trace("pca_covariance"):
                stats = jax.block_until_ready(pca_scatter(X, w))
            with trace("pca_fetch"):
                scatter, s1, sw, shift = (np.asarray(a) for a in stats)
            with trace("pca_eigensolve"):
                out = pca_eigensolve_resident(
                    stats[0], scatter, s1, float(sw), shift, k)
            # the fetched second moments are dropped here, under a span,
            # and not at the return, where unmapping their 36 MB (at 3,000
            # columns) ran under none
            with trace("pca_release", detail="work"):
                del stats, scatter, s1, shift
        dtype = np.dtype(fit_input.dtype)
        mean, components, ev, evr, sv = (np.asarray(a).astype(dtype) for a in out)
        return {
            "mean_": mean,
            "components_": components,
            "explained_variance_": ev,
            "explained_variance_ratio_": evr,
            "singular_values_": sv,
            "n_cols": d,
            "dtype": str(dtype.name),
        }

    def _supports_streaming_stats(self) -> bool:
        return True

    def _supports_fused_stats(self) -> bool:
        # one-pass second moments: the chunk order of arrival is
        # irrelevant, so accumulating while staging is exact
        return True

    def _resolved_k(self, d: int) -> int:
        k = int(self._tpu_params.get("n_components") or d)
        if k > d:
            raise ValueError(f"k={k} exceeds the number of features {d}")
        return k

    def _fit_fused(self, batch: _ArrayBatch) -> Dict[str, Any]:
        """Fused stage-and-solve over an in-memory host batch: the
        moment (or randomized-projected) accumulators fold each chunk in
        as it lands on the mesh (fused.py)."""
        from ..fused import fused_chunk_rows, fused_pca_stats, iter_host_chunks

        X = batch.X
        dtype = self._out_dtype(X)
        d = int(X.shape[1])

        def producer(n_dev: int):
            rows = fused_chunk_rows(
                int(X.shape[0]), d, np.dtype(dtype).itemsize, n_dev
            )
            return iter_host_chunks(X, None, batch.weight, rows, dtype)

        st = fused_pca_stats(producer, d, self._resolved_k(d), dtype)
        return self._attrs_from_fused(st, dtype)

    def _fit_fused_parquet(self, path: str) -> Dict[str, Any]:
        """Fused stage-and-solve straight from parquet: the chunk decode
        (the dominant host cost of the refconfig fits) runs on the
        producer thread, overlapped with the on-mesh accumulation."""
        from ..fused import (
            fused_chunk_rows,
            fused_pca_stats,
            iter_parquet_chunks,
        )
        from ..streaming import parquet_row_count, probe_num_features

        fcol, fcols, _, weight_col, dtype = self._streaming_io_params()
        d = probe_num_features(path, fcol, fcols)
        n = parquet_row_count(path)

        def producer(n_dev: int):
            rows = fused_chunk_rows(n, d, np.dtype(dtype).itemsize, n_dev)
            prep = {"s": 0.0, "iv": []}  # readers self-time their decode
            return (
                iter_parquet_chunks(
                    path, fcol, fcols, None, weight_col, rows, dtype,
                    prep=prep,
                ),
                prep,
            )

        st = fused_pca_stats(producer, d, self._resolved_k(d), dtype)
        return self._attrs_from_fused(st, dtype)

    def _attrs_from_fused(self, st: Dict[str, Any], dtype) -> Dict[str, Any]:
        if st.get("kind") == "projected":
            return self._attrs_from_projected(st, dtype)
        return self._attrs_from_moments(st, dtype)

    def _attrs_from_projected(self, st: Dict[str, Any], dtype) -> Dict[str, Any]:
        """Finalize the stage-overlapped RANDOMIZED fit: the small
        Q-projected eigenproblem from the accumulated tall-skinny
        moments (ops/pca.py `pca_attrs_from_projected`)."""
        from ..ops.pca import pca_attrs_from_projected

        mean, components, ev, evr, sv = pca_attrs_from_projected(
            st["Q"], st["SQ"], st["s1"], st["ssq"], float(st["sw"]),
            int(st["k"]),
        )
        dtype = np.dtype(dtype)
        return {
            "mean_": mean.astype(dtype),
            "components_": components.astype(dtype),
            "explained_variance_": ev.astype(dtype),
            "explained_variance_ratio_": evr.astype(dtype),
            "singular_values_": sv.astype(dtype),
            "n_cols": int(components.shape[1]),
            "dtype": str(dtype.name),
        }

    def _supports_fold_weights(self) -> bool:
        # weighted mean/covariance + deterministic eigh (ops/pca.py
        # SUPPORTS_ZERO_WEIGHT_ROWS): fold masks are plain zero weights
        from ..ops import pca as _pca_ops

        return bool(_pca_ops.SUPPORTS_ZERO_WEIGHT_ROWS)

    def _fit_streaming(self, path: str) -> Dict[str, Any]:
        """Beyond-HBM fit from multi-pass streamed second moments
        (streaming.py `pca_streaming_stats`): the dataset never resides in
        host RAM or HBM, only the (d,d) accumulator does.  The host
        finalization is the resident fit's (`ops/pca.py pca_eigensolve_host`)."""
        from ..streaming import pca_streaming_stats

        fcol, fcols, _, weight_col, dtype = self._streaming_io_params()
        st = pca_streaming_stats(path, fcol, fcols, weight_col, dtype=dtype)
        return self._attrs_from_moments(st, dtype)

    def _fit_streaming_csr(self, batch) -> Dict[str, Any]:
        """Sparse fit from blocked-densify second moments
        (streaming.py `pca_stats_from_csr`): exact, with one dense row
        block of host memory — the TPU analog of the reference's CSR PCA
        staging (core.py:220-265)."""
        from ..streaming import pca_stats_from_csr

        dtype = self._out_dtype(batch.X)
        st = pca_stats_from_csr(
            batch.X.tocsr(), batch.weight, dtype=dtype
        )
        return self._attrs_from_moments(st, dtype)

    def _attrs_from_moments(self, st: Dict[str, Any], dtype) -> Dict[str, Any]:
        """Finalize a streamed or fused fit from its second moments about
        zero: the resident fit's host eigensolve with no shift."""
        from ..ops.pca import pca_eigensolve_host

        S, s1 = np.asarray(st["S"]), np.asarray(st["s1"])
        d = S.shape[0]
        mean, components, ev, evr, sv = pca_eigensolve_host(
            S, s1, float(st["sw"]), np.zeros(d), self._resolved_k(d)
        )
        dtype = np.dtype(dtype)
        return {
            "mean_": mean.astype(dtype),
            "components_": components.astype(dtype),
            "explained_variance_": ev.astype(dtype),
            "explained_variance_ratio_": evr.astype(dtype),
            "singular_values_": sv.astype(dtype),
            "n_cols": d,
            "dtype": str(dtype.name),
        }

    def _create_model(self, attrs: Dict[str, Any]) -> "PCAModel":
        return PCAModel(**attrs)

    def _cpu_fit(self, batch: _ArrayBatch) -> "PCAModel":
        from sklearn.decomposition import PCA as SkPCA

        k = self.getOrDefault("k") or batch.X.shape[1]
        sk = SkPCA(n_components=k, svd_solver="full").fit(batch.X)
        model = PCAModel(
            mean_=sk.mean_.astype(batch.X.dtype),
            components_=sk.components_.astype(batch.X.dtype),
            explained_variance_=sk.explained_variance_.astype(batch.X.dtype),
            explained_variance_ratio_=sk.explained_variance_ratio_.astype(batch.X.dtype),
            singular_values_=sk.singular_values_.astype(batch.X.dtype),
            n_cols=int(batch.X.shape[1]),
            dtype=str(batch.X.dtype),
        )
        return model


class PCAModel(PCAClass, _TpuModel, _PCATpuParams):
    """PCA projection model (reference PCAModel feature.py:299-468).

    Note: like Spark, `transform` does NOT remove the mean — cuML does, and
    the reference adds `mean @ components^T` back (feature.py:447-459); here
    the projection is simply `X @ components^T`.
    """

    def __init__(self, **attrs: Any) -> None:
        super().__init__(**attrs)
        self.mean_: np.ndarray = np.asarray(attrs["mean_"])
        self.components_: np.ndarray = np.asarray(attrs["components_"])
        self.explained_variance_: np.ndarray = np.asarray(attrs["explained_variance_"])
        self.explained_variance_ratio_: np.ndarray = np.asarray(
            attrs["explained_variance_ratio_"]
        )
        self.singular_values_: np.ndarray = np.asarray(attrs["singular_values_"])
        self.n_cols: int = int(attrs["n_cols"])
        self.dtype: str = str(attrs.get("dtype", "float32"))
        self._set_params(k=int(self.components_.shape[0]))

    @property
    def pc(self) -> np.ndarray:
        """Principal components as a (n_features, k) matrix, matching
        pyspark.ml PCAModel.pc (column-major components)."""
        return self.components_.T

    @property
    def explainedVariance(self) -> np.ndarray:
        """Ratio of variance explained per component (pyspark parity)."""
        return self.explained_variance_ratio_

    def _output_columns(self) -> List[str]:
        return [self.getOrDefault("outputCol")]

    def _transform_device(self, Xs) -> Dict[str, Any]:
        import jax.numpy as jnp

        from ..ops.pca import pca_transform

        return {
            self.getOrDefault("outputCol"): pca_transform(
                Xs, jnp.asarray(self.components_.astype(Xs.dtype))
            )
        }

    def cpu(self):
        from sklearn.decomposition import PCA as SkPCA

        sk = SkPCA(n_components=self.components_.shape[0])
        sk.components_ = self.components_
        sk.mean_ = self.mean_
        sk.explained_variance_ = self.explained_variance_
        sk.explained_variance_ratio_ = self.explained_variance_ratio_
        sk.singular_values_ = self.singular_values_
        sk.n_components_ = self.components_.shape[0]
        sk.n_features_in_ = self.n_cols
        return sk
