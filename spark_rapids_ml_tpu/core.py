#
# Core runtime — the analog of reference core.py (1967 LoC):
# `_CumlCaller` (core.py:439) / `_CumlEstimator` (core.py:1067) /
# `_CumlModel` (core.py:1356) re-designed for a single-controller JAX SPMD
# runtime.  The reference's orchestration shape
#   preprocess -> repartition(num_workers) -> mapInPandas barrier fit over
#   NCCL -> collect model rows -> driver model
# becomes
#   extract host arrays -> shard rows onto a Mesh -> jit'd kernel with XLA
#   collectives -> host model attributes
# with no process boundary: the controller stages data and XLA moves it.
#
from __future__ import annotations

import json
import os
import time
from abc import abstractmethod
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .data import DatasetLike, DeviceDataset, _ensure_dense, extract_arrays
from .params import Param, Params, _TpuParams
from .parallel import TpuContext
from .telemetry.locks import named_lock
from .utils import PartitionDescriptor, _ArrayBatch, get_logger


@dataclass
class FitInput:
    """Everything a kernel needs for one distributed fit — the analog of the
    `params` dict handed to `_cuml_fit_func` (reference `param_alias`
    core.py:154-175: handle/part_sizes/num_cols/rank/loop)."""

    mesh: Any  # jax.sharding.Mesh
    X: Any  # jax.Array, rows sharded over DATA_AXIS, zero-padded
    w: Any  # jax.Array (N_pad,) validity * sample weight
    y: Optional[Any]  # jax.Array or None
    pdesc: PartitionDescriptor
    dtype: np.dtype
    n_valid: int
    params: Dict[str, Any]  # resolved backend params (_tpu_params)
    extra: Dict[str, Any] = field(default_factory=dict)


# error classification now lives in the resilience layer (one classifier
# set for every dispatch site); re-exported here for back-compat
from .resilience import is_oom as _is_oom  # noqa: F401


def _fit_fingerprint(fit_input: FitInput) -> str:
    """Cheap content fingerprint binding an in-memory checkpoint tag to
    the DATA, not just its shape: scalar device reductions over the
    staged arrays (plus the label sum when present).  Without this, a
    crashed fit's checkpoint would be silently resumed by a same-shaped,
    same-hyperparameter fit on DIFFERENT data — skipping most of its
    iterations (the in-file tag check in resilience/checkpoint.py can
    only refuse what the tag encodes).  Streaming fits bind the dataset
    path instead.

    The reductions are EXACT and mesh-layout-independent: each array is
    bitcast to same-width integers and summed with modular (wraparound)
    arithmetic, which is associative + commutative — so the fingerprint
    is invariant under re-sharding and padding-row changes (padding is
    +0.0, bit pattern 0).  This is load-bearing for elastic recovery
    (resilience/elastic.py): a fit resumed on a SHRUNKEN mesh must
    derive the same tag from its re-staged arrays or its checkpoint is
    orphaned, and f32 float sums differ in the last ulp per shard count
    (per-shard partial-sum order changes with the device set)."""
    import jax
    import jax.numpy as jnp

    def _isum(arr) -> int:
        itype = {1: jnp.int8, 2: jnp.int16, 4: jnp.int32, 8: jnp.int64}[
            np.dtype(arr.dtype).itemsize
        ]
        if jnp.issubdtype(arr.dtype, jnp.floating):
            arr = jax.lax.bitcast_convert_type(arr, itype)
        return int(jax.device_get(jnp.sum(arr.astype(itype), dtype=itype)))

    parts = [f"sx={_isum(fit_input.X)}", f"swt={_isum(fit_input.w)}"]
    if fit_input.y is not None:
        parts.append(f"sy={_isum(fit_input.y)}")
    return "|".join(parts)


def _resolve_feature_params(inst: Params) -> Tuple[Optional[str], Sequence[str]]:
    """Which column(s) hold features: featuresCol/featuresCols for
    predictors, inputCol/inputCols for feature transformers like PCA
    (reference _PCACumlParams setInputCol feature.py:77-115)."""
    features_cols: Sequence[str] = ()
    if inst.hasParam("featuresCols") and inst.isSet("featuresCols"):
        features_cols = inst.getOrDefault("featuresCols")
    elif inst.hasParam("inputCols") and inst.isSet("inputCols"):
        features_cols = inst.getOrDefault("inputCols")
    features_col: Optional[str] = None
    if inst.hasParam("featuresCol") and inst.isDefined("featuresCol"):
        features_col = inst.getOrDefault("featuresCol")
    if inst.hasParam("inputCol") and inst.isSet("inputCol"):
        features_col = inst.getOrDefault("inputCol")
    return features_col, features_cols


class Estimator(Params):
    """pyspark.ml.Estimator-compatible base."""

    def fit(self, dataset: DatasetLike, params: Optional[Dict[Param, Any]] = None):
        est = self.copy(params) if params else self
        # every fit runs under a minted run_id and a root `fit[<Est>]`
        # span (telemetry/report.py): retries, device-loss recoveries and
        # checkpoint resumes recorded anywhere below stamp this run, and
        # the assembled per-fit report lands on the model
        # (`model.fit_report()`; JSON artifact when `telemetry_dir` is
        # set)
        from .monitor.baseline import baseline_mode, baseline_scope
        from .telemetry.report import FitTelemetry

        tel = FitTelemetry(type(est).__name__)
        with tel.span():
            # drift-baseline capture (monitor/): the chunked fit paths
            # (fused stage-and-solve, streamed statistics) fold their
            # decoded host chunks into a baseline fingerprint when a
            # collector is armed — zero extra data passes; conf "on"
            # additionally folds in-memory batches (one host pass)
            with baseline_scope(baseline_mode() != "off") as coll:
                model = est._fit(dataset)
            fp = coll.fingerprint() if coll is not None else None
            if fp is not None:
                model._drift_baseline = fp
        tel.attach(model, log=getattr(est, "logger", None))
        return model

    @abstractmethod
    def _fit(self, dataset: DatasetLike):
        ...


class Transformer(Params):
    """pyspark.ml.Transformer-compatible base."""

    def transform(self, dataset: DatasetLike, params: Optional[Dict[Param, Any]] = None):
        from .tracing import current_run_id, run_context

        tr = self.copy(params) if params else self
        # a TOP-LEVEL transform mints its own run_id; a transform running
        # inside an active run (Pipeline._fit driving its stages, CV
        # eval) inherits it, so its spans and retry markers stay attached
        # to the fit that issued them
        if current_run_id():
            return tr._transform(dataset)
        with run_context(prefix="transform"):
            return tr._transform(dataset)

    @abstractmethod
    def _transform(self, dataset: DatasetLike):
        ...


class Model(Transformer):
    def fit_report(self) -> Optional[Dict[str, Any]]:
        """The telemetry report of the fit that produced this model
        (telemetry/report.py): stage timing tree, bytes staged, cache
        hits, retries/recoveries, solver iteration/loss curve.  None for
        models not produced by `Estimator.fit` in this process (loaded
        from disk, hand-built).  The same dict is written to
        `telemetry_dir` as a JSON artifact when that conf is set."""
        return getattr(self, "_fit_report", None)


# ---------------------------------------------------------------------------
# Persistence (reference _CumlEstimatorWriter/Reader core.py:268-307 and
# _CumlModelWriter/Reader core.py:310-355).  Directory layout:
#   <path>/metadata.json   class, uid, params, _tpu_params, scalar attributes
#   <path>/arrays.npz      ndarray model attributes
# ---------------------------------------------------------------------------


def _json_default(o: Any) -> Any:
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(f"not JSON serializable: {type(o)}")


class _Writer:
    def __init__(self, instance: "_TpuParams") -> None:
        self.instance = instance
        self._overwrite = False

    def overwrite(self) -> "_Writer":
        self._overwrite = True
        return self

    def save(self, path: str) -> None:
        if os.path.exists(path) and not self._overwrite:
            raise IOError(f"Path {path} already exists; use .write().overwrite().save()")
        os.makedirs(path, exist_ok=True)
        inst = self.instance
        metadata: Dict[str, Any] = {
            "class": type(inst).__module__ + "." + type(inst).__qualname__,
            "uid": inst.uid,
            "timestamp": int(time.time() * 1000),
            "paramMap": {p.name: v for p, v in inst._paramMap.items()},
            "defaultParamMap": {p.name: v for p, v in inst._defaultParamMap.items()},
            "tpu_params": inst._tpu_params,
            "num_workers": inst._num_workers,
            "float32_inputs": inst._float32_inputs,
        }
        arrays: Dict[str, np.ndarray] = {}
        if isinstance(inst, _TpuModel):
            from .data import _is_sparse

            attrs: Dict[str, Any] = {}
            sparse_attrs: List[str] = []
            for k, v in inst._get_model_attributes().items():
                if _is_sparse(v):
                    # CSR attributes (sparse kNN item sets, sparse UMAP raw
                    # data) persist as their three component arrays + shape;
                    # np.savez has no sparse container
                    csr = v.tocsr()
                    arrays[k + "__csr_data"] = np.asarray(csr.data)
                    arrays[k + "__csr_indices"] = np.asarray(csr.indices)
                    arrays[k + "__csr_indptr"] = np.asarray(csr.indptr)
                    arrays[k + "__csr_shape"] = np.asarray(csr.shape, np.int64)
                    sparse_attrs.append(k)
                elif isinstance(v, np.ndarray):
                    arrays[k] = v
                else:
                    attrs[k] = v
            metadata["attributes"] = attrs
            metadata["array_attributes"] = sorted(arrays)
            if sparse_attrs:
                metadata["sparse_attributes"] = sorted(sparse_attrs)
        with open(os.path.join(path, "metadata.json"), "w") as f:
            json.dump(metadata, f, default=_json_default)
        npz_path = os.path.join(path, "arrays.npz")
        if os.path.exists(npz_path):
            os.remove(npz_path)  # stale arrays from a previous overwrite-save
        if arrays:
            np.savez(npz_path, **arrays)
        # drift baseline (monitor/fingerprint.py): the fit-time
        # distribution fingerprint persists NEXT TO the model arrays so
        # a loaded model can register with the serving drift monitor
        fp_path = os.path.join(path, "drift_baseline.bin")
        if os.path.exists(fp_path):
            os.remove(fp_path)  # stale baseline from an overwrite-save
        fp = getattr(inst, "_drift_baseline", None)
        if fp is not None:
            with open(fp_path, "wb") as f:
                f.write(fp.to_bytes())


def _load_metadata(path: str) -> Dict[str, Any]:
    with open(os.path.join(path, "metadata.json")) as f:
        return json.load(f)


def _load_arrays(path: str) -> Dict[str, np.ndarray]:
    npz_path = os.path.join(path, "arrays.npz")
    if not os.path.exists(npz_path):
        return {}
    with np.load(npz_path, allow_pickle=False) as z:
        return {k: z[k] for k in z.files}


class _ReadWriteMixin:
    """save/load entry points shared by estimators and models."""

    def write(self) -> _Writer:
        return _Writer(self)  # type: ignore[arg-type]

    def save(self, path: str) -> None:
        self.write().save(path)

    @classmethod
    def _restore_params(cls, inst: "_TpuParams", meta: Dict[str, Any]) -> None:
        for name, v in meta.get("defaultParamMap", {}).items():
            if inst.hasParam(name):
                inst._defaultParamMap[inst.getParam(name)] = v
        for name, v in meta.get("paramMap", {}).items():
            if inst.hasParam(name):
                inst._paramMap[inst.getParam(name)] = v
        inst._tpu_params = dict(meta.get("tpu_params", {}))
        inst._num_workers = meta.get("num_workers")
        inst._float32_inputs = meta.get("float32_inputs", True)

    @classmethod
    def load(cls, path: str):
        meta = _load_metadata(path)
        if issubclass(cls, _TpuModel):
            arrays = _load_arrays(path)
            wanted = meta.get("array_attributes")
            if wanted is not None:
                arrays = {k: v for k, v in arrays.items() if k in wanted}
            for name in meta.get("sparse_attributes", []):
                import scipy.sparse as sp

                arrays[name] = sp.csr_matrix(
                    (
                        arrays.pop(name + "__csr_data"),
                        arrays.pop(name + "__csr_indices"),
                        arrays.pop(name + "__csr_indptr"),
                    ),
                    shape=tuple(arrays.pop(name + "__csr_shape")),
                )
            attrs = dict(meta.get("attributes", {}))
            attrs.update(arrays)
            inst = cls._from_attributes(attrs)
            fp_path = os.path.join(path, "drift_baseline.bin")
            if os.path.exists(fp_path):
                from .monitor.fingerprint import Fingerprint

                with open(fp_path, "rb") as f:
                    inst._drift_baseline = Fingerprint.from_bytes(f.read())
        else:
            inst = cls()
        cls._restore_params(inst, meta)
        return inst

    @classmethod
    def read(cls):
        class _Reader:
            @staticmethod
            def load(path: str):
                return cls.load(path)

        return _Reader()


# ---------------------------------------------------------------------------
# _TpuCaller: shared fit-calling logic (reference _CumlCaller core.py:439)
# ---------------------------------------------------------------------------


class _TpuCaller(_TpuParams, _ReadWriteMixin):
    def _out_dtype(self, X: np.ndarray) -> np.dtype:
        # float64 stays float64 only when float32_inputs is disabled
        # (reference _float32_inputs handling, core.py:514-537).
        if X.dtype == np.float64 and not self._float32_inputs:
            return np.dtype(np.float64)
        return np.dtype(np.float32)

    def _require_p2p(self) -> bool:
        """Analog of `_require_nccl_ucx` (reference core.py:570-577): whether
        the kernel needs p2p-style all-to-all (exact kNN, DBSCAN)."""
        return False

    def _validate_device_input(self, ds: DeviceDataset) -> None:
        """Device-side analog of `_validate_input` for device-resident
        datasets (runs BEFORE any label dtype cast)."""

    def _fit_label_dtype(self) -> Optional[np.dtype]:
        return np.dtype(np.float32)

    def _use_sparse_kernel(self, batch: _ArrayBatch) -> bool:
        """Whether a sparse host batch should stage as ELL for a sparse
        kernel instead of densifying (the analog of `_use_sparse_in_cuml`,
        reference core.py:183-216).  Estimators with sparse kernels
        override; default densifies."""
        return False

    def _fit_streaming_csr(self, batch: _ArrayBatch) -> Optional[Dict[str, Any]]:
        """Fit from blocked-densify sufficient statistics over a host CSR
        batch (bounded host + device memory).  Estimators with streamed
        statistics (PCA, LinearRegression) override; default None means
        the generic whole-densify staging runs instead."""
        return None

    def _over_device_budget(self, need_bytes: float) -> bool:
        """Whether a staged dataset estimate exceeds the device-memory
        budget (or force_streaming_stats is set) — ONE formula for the
        parquet and sparse streamed-stats decisions AND the device-cache
        residency accounting (parallel/device_cache.py shares it via
        `device_data_budget_bytes`).  Bytes the cache holds RESIDENT
        count against the estimate — but residency is re-creatable, so
        entries are LRU-evicted first rather than pushing this fit onto
        the much slower streamed-statistics path while droppable data
        holds the room."""
        from .config import get_config
        from .parallel.device_cache import (
            cache_resident_bytes,
            device_data_budget_bytes,
            evict_to_fit,
        )
        from .telemetry.memory import record_budget_decision

        if bool(get_config("force_streaming_stats")):
            # the answer is True regardless — do not evict a warm cache
            # for a decision the force flag already made
            record_budget_decision("fit_dataset", need_bytes, True)
            return True
        budget = device_data_budget_bytes()
        if need_bytes + cache_resident_bytes() > budget:
            evict_to_fit(need_bytes, budget)
        over = need_bytes + cache_resident_bytes() > budget
        # the prediction side of budget_drift_ratio (telemetry/memory.py):
        # the measured peak watermark lands in the same fit report, so
        # the n_dev+2 gather factors and reservation math get checked
        # against the chips instead of stayed faith-based
        record_budget_decision("fit_dataset", need_bytes, over)
        return over

    def _supports_fold_weights(self) -> bool:
        """Whether this estimator's kernels honor the zero-weight-row
        contract (ops SUPPORTS_ZERO_WEIGHT_ROWS) AND its fit trajectory
        is row-count insensitive, so a CV fold may be selected by weight
        MASK over the resident full dataset instead of a gather view
        (parallel/device_cache.py).  Weight-capable deterministic solvers
        (LinearRegression, LogisticRegression, PCA) override to True;
        the default (gather/compaction fallback) is always correct."""
        return False

    def _sparse_over_budget(self, batch: _ArrayBatch) -> bool:
        """Whether a sparse batch's DENSE form exceeds the device budget
        — the sparse analog of the parquet streamed-stats decision."""
        from .data import _is_sparse

        if not _is_sparse(batch.X):
            return False
        n, d = batch.X.shape
        return self._over_device_budget(
            n * d * np.dtype(self._out_dtype(batch.X)).itemsize
        )

    def _maybe_fit_sparse_stats(
        self, batch: _ArrayBatch
    ) -> Optional[Dict[str, Any]]:
        """Route a sparse over-budget batch to the blocked-CSR statistics
        fit (reference keeps such data CSR end-to-end,
        classification.py:960-966)."""
        if not self._sparse_over_budget(batch):
            return None
        attrs = self._fit_streaming_csr(batch)
        if attrs is not None:
            self.logger.info(
                "Sparse dataset beyond the device budget: fit from "
                "blocked-CSR streamed statistics."
            )
        return attrs

    def _stage_fit_input(
        self,
        batch: _ArrayBatch,
        paramMaps: Optional[Sequence[Dict[str, Any]]] = None,
    ) -> FitInput:
        """Stage host arrays onto the mesh — the analog of the executor-side
        staging loop + CumlContext entry (reference core.py:886-994).

        In multi-process (pod) mode, `batch` holds only this process's LOCAL
        rows; the `RowStager` assembles the global sharded arrays without
        any process materializing the full dataset (the analog of each
        Spark barrier task staging its own partition)."""
        from .data import _is_sparse
        from .parallel.mesh import RowStager

        with TpuContext(self.num_workers, require_p2p=self._require_p2p()) as ctx:
            mesh = ctx.mesh
        n_dev = mesh.devices.size
        extra: Dict[str, Any] = {}
        if self._use_sparse_kernel(batch):
            import scipy.sparse as sp

            from .ops.sparse import ell_from_csr

            csr = (
                batch.X if _is_sparse(batch.X) else sp.csr_matrix(batch.X)
            )  # enable_sparse_data_optim=True forces sparse staging
            vals_host, cols_host = ell_from_csr(csr)
            import jax

            if jax.process_count() > 1:
                # the ELL width K is the LOCAL max nnz/row; processes must
                # agree on the global array shape, so widen to the global max
                from jax.experimental import multihost_utils

                k_all = np.asarray(
                    multihost_utils.process_allgather(
                        np.asarray(vals_host.shape[1], np.int64)
                    )
                ).reshape(-1)
                k_max = int(k_all.max())
                if vals_host.shape[1] < k_max:
                    # widen with the (0.0, col 0) no-op entries ell_from_csr
                    # uses for its own padding
                    pad = k_max - vals_host.shape[1]
                    vals_host = np.pad(vals_host, ((0, 0), (0, pad)))
                    cols_host = np.pad(cols_host, ((0, 0), (0, pad)))
            dtype = self._out_dtype(vals_host)
            st = RowStager(vals_host.shape[0], mesh)
            Xs = st.stage(vals_host, dtype)
            extra = {"ell_cols": st.stage(cols_host, np.int32)}
        else:
            X_host = _ensure_dense(batch.X)
            dtype = self._out_dtype(X_host)
            st = RowStager(X_host.shape[0], mesh)
            Xs = st.stage(X_host, dtype)
        n_padded = Xs.shape[0]
        if st._interleave:
            # dataset row r lies at staged position (r % n_dev) * shard +
            # r // n_dev: kernels that rank rows in dataset order (the
            # KMeans `random` init) undo it
            extra["interleaved_over"] = n_dev
        w = st.mask(dtype, weights=batch.weight)
        y = None
        if batch.y is not None:
            ldt = self._fit_label_dtype() or dtype
            y = st.stage(np.asarray(batch.y).reshape(-1).astype(ldt), ldt)
        per_shard = [n_padded // n_dev] * n_dev
        pdesc = PartitionDescriptor.build(per_shard, int(batch.X.shape[1]))
        return FitInput(
            mesh=mesh,
            X=Xs,
            w=w,
            y=y,
            pdesc=pdesc,
            dtype=dtype,
            n_valid=st.n_valid,
            params=dict(self._tpu_params),
            extra=extra,
        )

    def _stage_from_device(self, ds: DeviceDataset) -> FitInput:
        """Zero-copy staging from an already-device-resident DeviceDataset
        (the cached-DataFrame fast path): only label dtype casts run, on
        device."""
        supervised = getattr(self, "_is_supervised", lambda: False)()
        if supervised and ds.y is None:
            raise ValueError("Supervised fit requires a DeviceDataset with labels")
        self._validate_device_input(ds)
        dtype = np.dtype(ds.X.dtype)
        y = ds.y
        ldt = self._fit_label_dtype() if supervised else None
        if y is not None and ldt is not None and np.dtype(y.dtype) != ldt:
            y = y.astype(ldt)
        n_dev = ds.mesh.devices.size
        per_shard = [ds.X.shape[0] // n_dev] * n_dev
        pdesc = PartitionDescriptor.build(per_shard, int(ds.X.shape[1]))
        return FitInput(
            mesh=ds.mesh,
            X=ds.X,
            w=ds.weight,
            y=y,
            pdesc=pdesc,
            dtype=dtype,
            n_valid=ds.n_valid,
            params=dict(self._tpu_params),
        )


# ---------------------------------------------------------------------------
# _TpuEstimator (reference _CumlEstimator core.py:1067)
# ---------------------------------------------------------------------------


class _TpuEstimator(Estimator, _TpuCaller):
    def __init__(self) -> None:
        super().__init__()
        self._init_tpu_params()
        self.logger = get_logger(type(self))

    # -- subclass contract ---------------------------------------------------

    @abstractmethod
    def _fit_array(self, fit_input: FitInput) -> Dict[str, Any]:
        """Run the distributed kernel, return host model attributes — the
        analog of the closure returned by `_get_cuml_fit_func`
        (e.g. reference classification.py:968-1221)."""

    @abstractmethod
    def _create_model(self, attrs: Dict[str, Any]) -> "_TpuModel":
        """Build the Model from fit attributes (reference
        `_create_pyspark_model` core.py:1267-1279)."""

    def _is_supervised(self) -> bool:
        return False

    def _validate_input(self, batch: _ArrayBatch) -> None:
        """Validate the raw host batch before dtype casting/staging (the
        analog of `_validate_parameters` + label checks, reference
        core.py:585-608)."""

    def _enable_fit_multiple_in_single_pass(self) -> bool:
        # Reference core.py:1172-1175.
        return True

    def _supports_cpu_fallback(self) -> bool:
        return self._cpu_fit is not _TpuEstimator._cpu_fit

    def _cpu_fit(self, batch: _ArrayBatch) -> "_TpuModel":
        """sklearn fallback fit (the reference falls back to pyspark.ml,
        core.py:1283-1297; without Spark the CPU engine is sklearn)."""
        raise NotImplementedError(
            f"{type(self).__name__} has no CPU fallback implementation"
        )

    # -- fit orchestration ---------------------------------------------------

    def _run_fit_kernel(
        self,
        fit_input: FitInput,
        restage: Optional[Callable[[], FitInput]] = None,
    ) -> Dict[str, Any]:
        """Dispatch the distributed fit kernel through the resilience
        layer (resilience/): the `fit_kernel` fault-injection site, the
        `guarded` watchdog (`dispatch_deadline_s` — a hang raises a typed
        DispatchTimeout instead of blocking the controller), and the
        configured RetryPolicy: transient RPC/DEADLINE errors back off and
        re-dispatch, OOM drops the failed dispatch's temporaries and
        re-dispatches, a preemption re-inits `jax.distributed` first —
        and iterative solvers with `checkpoint_dir` set then resume from
        their per-iteration checkpoint rather than iteration 0.

        `restage` is the elastic-recovery hook: when a DEVICE LOSS is
        recovered by shrinking the mesh (resilience/elastic.py), the
        staged inputs must move to the surviving devices before the
        re-dispatch — the callable rebuilds the FitInput against the
        degraded mesh (a fresh `_stage_fit_input` of the same host
        batch).  Without it (or when the recovery falls back to the
        full-retry path) the re-dispatch reuses the original staging."""
        from .resilience import guarded, maybe_inject, retry_call

        cell = {"fi": fit_input}
        # the cell owns the staging from here: dropping the parameter
        # binding (and callers not keeping their own locals) lets a
        # successful restage actually free the pre-loss arrays
        fit_input = None  # type: ignore[assignment]

        def _kernel() -> Dict[str, Any]:
            maybe_inject("fit_kernel")
            from .telemetry import utilization

            t0 = time.perf_counter()
            try:
                return self._fit_array(cell["fi"])
            finally:
                # the blocking kernel window is device activity on the
                # run's utilization timeline (telemetry/utilization.py):
                # the two-phase fit paths get a device-busy series even
                # though their solve is one opaque dispatch
                utilization.note_interval(
                    "device", t0, time.perf_counter(), cause="fit_kernel"
                )

        def _on_device_loss() -> None:
            from .resilience.elastic import recover_from_device_loss

            if recover_from_device_loss(self.logger) and restage is not None:
                # the old staging is held for fallback only: a restage
                # can itself fail (on real hardware a host round-trip
                # through arrays sharded over the dead chip raises) —
                # then the retry keeps the original staging and behaves
                # like the pre-elastic full retry instead of crashing
                # the fit with an opaque hook error
                old, cell["fi"] = cell["fi"], None
                from .tracing import trace

                try:
                    with trace("elastic_restage", self.logger):
                        cell["fi"] = restage()
                except Exception as e:
                    cell["fi"] = old
                    self.logger.warning(
                        f"Elastic restage failed ({type(e).__name__}: "
                        f"{e}); retrying with the original staging"
                    )

        return retry_call(
            lambda: guarded(_kernel, label="fit_kernel", log=self.logger),
            label="fit_kernel",
            log=self.logger,
            on_device_loss=_on_device_loss,
        )

    def _extract(self, dataset: DatasetLike) -> _ArrayBatch:
        features_col, features_cols = _resolve_feature_params(self)
        label_col = (
            self.getOrDefault("labelCol")
            if self._is_supervised() and self.hasParam("labelCol")
            else None
        )
        weight_col = (
            self.getOrDefault("weightCol")
            if self.hasParam("weightCol") and self.isSet("weightCol")
            else None
        )
        return extract_arrays(
            dataset,
            features_col=features_col,
            features_cols=features_cols,
            label_col=label_col,
            weight_col=weight_col,
            dtype=None,  # preserve input precision; _out_dtype decides
            supervised=self._is_supervised(),
        )

    # -- fused stage-and-solve (fused.py) ------------------------------------

    def _supports_fused_stats(self) -> bool:
        """Whether this estimator can fit from chunk-accumulated
        sufficient statistics folded in WHILE the data stages (the fused
        stage-and-solve engine, fused.py) — PCA/LinearRegression
        override.  Distinct from `_supports_streaming_stats` only in
        intent: the same statistics, but accumulated mesh-sharded with
        the host producer thread overlapped, for datasets that would
        otherwise stage fully and then solve."""
        return False

    def _fit_fused(self, batch: _ArrayBatch) -> Dict[str, Any]:
        """Fused fit of an in-memory host batch (estimators declaring
        `_supports_fused_stats` implement)."""
        raise NotImplementedError

    def _fit_fused_parquet(self, path: str) -> Dict[str, Any]:
        """Fused fit streaming chunks straight from parquet (the decode
        is the overlapped host prep)."""
        raise NotImplementedError

    def _maybe_fit_fused(
        self, source, est_bytes: Optional[float] = None
    ) -> Optional[Dict[str, Any]]:
        """Route an eligible fit through the fused stage-and-solve path
        (conf `fused_stage_solve`): sufficient statistics accumulate on
        the mesh as each chunk lands instead of staging everything and
        then solving.  Multi-process pods fuse too: each rank decodes
        only its row-group share (fused.process_row_group_shares), folds
        on its local devices, and the partials meet in one cross-process
        reduction at pass completion — the path degrades only when the
        reduce seam has no transport (parallel/context.py
        `cross_process_reduce_ready`).  Returns model attrs, or None to
        keep the two-phase path — sparse batches, conf off/below the
        auto threshold, and estimators without the capability all
        degrade.  `source` is a host `_ArrayBatch` or a parquet path.

        The dispatch runs under the retry policy with the accumulators
        treated as RE-CREATABLE state: any mid-pass failure (the
        `fused_accumulate` fault site — OOM, device loss) restarts the
        whole pass with fresh accumulators on the (possibly shrunken)
        mesh, never resuming half-accumulated sums, so a retried chunk
        can never double-count."""
        if not self._supports_fused_stats():
            return None
        from .fused import fused_enabled

        is_path = isinstance(source, str)
        if not is_path:
            from .data import _is_sparse

            if _is_sparse(source.X) or self._use_sparse_kernel(source):
                return None
            if est_bytes is None:
                est_bytes = (
                    int(source.X.shape[0])
                    * int(source.X.shape[1])
                    * np.dtype(self._out_dtype(source.X)).itemsize
                )
        if est_bytes is None or not fused_enabled(est_bytes):
            return None
        from .fused import fused_mode
        from .resilience import retry_call
        from .tracing import trace

        self.logger.info(
            "Fused stage-and-solve: accumulating sufficient statistics "
            "on the mesh while the data stages (fused_stage_solve="
            f"{fused_mode()}, ~{est_bytes / 2**20:.0f} MiB)."
        )
        with trace("fused_fit", self.logger):
            return retry_call(
                (lambda: self._fit_fused_parquet(source))
                if is_path
                else (lambda: self._fit_fused(source)),
                label="fused_fit",
                log=self.logger,
            )

    # -- streaming ingest (reference reserved-memory loader utils.py:403-522) --

    def _supports_streaming_stats(self) -> bool:
        """Whether `_fit_streaming` can fit from multi-pass streamed
        sufficient statistics (beyond-HBM datasets).  PCA/LinReg override."""
        return False

    def _fit_streaming(self, path: str) -> Dict[str, Any]:
        raise NotImplementedError

    def _streaming_io_params(self):
        features_col, features_cols = _resolve_feature_params(self)
        label_col = (
            self.getOrDefault("labelCol")
            if self._is_supervised() and self.hasParam("labelCol")
            else None
        )
        weight_col = (
            self.getOrDefault("weightCol")
            if self.hasParam("weightCol") and self.isSet("weightCol")
            else None
        )
        dtype = np.float32 if self._float32_inputs else np.float64
        return features_col, features_cols, label_col, weight_col, dtype

    def _stage_or_stream(self, path: str) -> Optional[Dict[str, Any]]:
        """Fit a parquet dataset without the controller ever holding the
        full array: multi-pass streaming stats when the data exceeds the
        device-memory budget (capable estimators only), else chunked
        stream-staging into HBM + the normal device-resident fit.  Returns
        model attrs, or None to fall back to in-memory extraction."""
        from .config import get_config
        from .streaming import (
            chunk_rows_for,
            parquet_row_count,
            probe_num_features,
            stage_parquet,
        )

        if (
            self.hasParam("enable_sparse_data_optim")
            and self.getOrDefault("enable_sparse_data_optim") is True
        ):
            return None  # CSR staging needs the host matrix
        fcol, fcols, label_col, weight_col, dtype = self._streaming_io_params()
        if self._supports_streaming_stats():
            n = parquet_row_count(path)
            d = probe_num_features(path, fcol, fcols)
            need = n * d * np.dtype(dtype).itemsize
            if self._over_device_budget(need):
                self.logger.info(
                    f"Dataset (~{need/2**30:.1f} GiB) beyond the device "
                    "budget or force_streaming_stats set; fitting from "
                    "multi-pass streamed statistics."
                )
                return self._run_streaming_fit(path)
            # within budget: the fused stage-and-solve path accumulates
            # the statistics while the parquet chunks decode — the
            # 220s-stage + 193s-solve additivity this collapses is the
            # refconfig gap (fused.py; conf fused_stage_solve)
            attrs = self._maybe_fit_fused(path, est_bytes=need)
            if attrs is not None:
                return attrs
        ds_dev = fit_input = None
        try:
            from .resilience import maybe_inject

            def _stage_all() -> FitInput:
                maybe_inject("stage_parquet")
                ds = stage_parquet(
                    path,
                    features_col=fcol,
                    features_cols=fcols,
                    label_col=label_col,
                    weight_col=weight_col,
                    num_workers=self.num_workers,
                    dtype=dtype,
                    label_dtype=self._fit_label_dtype() if label_col else None,
                    chunk_rows=None,
                )
                return self._stage_from_device(ds)

            # no local binding: the kernel runner's cell is the only
            # owner of the staging, so an elastic restage can free it.
            # Restage re-ingests the parquet chunks onto the degraded
            # mesh (the streaming reader re-resolves the mesh).
            return self._run_fit_kernel(_stage_all(), restage=_stage_all)
        except Exception as e:
            # drop the staged buffers BEFORE any retry — keeping them alive
            # would hold the very HBM whose exhaustion we are recovering from
            ds_dev = fit_input = None  # noqa: F841
            # OOM backoff (the analog of the reference's reserved-memory
            # retry loop, utils.py:403-522): fall back to the multi-pass
            # streamed-statistics fit when the estimator supports it
            if not _is_oom(e):
                raise
            if not self._supports_streaming_stats():
                raise RuntimeError(
                    "Dataset exceeds device memory while stream-staging and "
                    f"{type(self).__name__} cannot fit from streamed "
                    "statistics; raise num_workers (more chips) or reduce "
                    "the dataset"
                ) from e
            oom_text = f"{type(e).__name__}: {e}"[:200]
        # the retry runs OUTSIDE the except block: while handling, the
        # interpreter's exception state (sys.exc_info) pins the solver's
        # inner frames via the traceback, whose locals reference the
        # staged device arrays — a retry inside the block would run with
        # the exhausted HBM still held (the refconfig kmeans retry itself
        # once died RESOURCE_EXHAUSTED this way).  Leaving the block pops
        # the exception and frees them.
        import gc

        # resident cache entries are re-creatable; they must not starve
        # an OOM recovery (the registry's claim is dropped — in-flight
        # consumers of an entry keep their views alive)
        from .parallel.device_cache import clear_device_cache

        clear_device_cache()
        gc.collect()
        # the refit is many times slower than the resident fit it
        # replaces and still returns a model: the marker (counted in the
        # fit report's resilience section as `oom_streaming_refits`) is
        # what lets a caller that needed the resident route refuse it
        from .tracing import event

        event("oom_streaming_refit", detail=oom_text, log=self.logger)
        self.logger.warning(
            "Device staging exhausted HBM; retrying as a "
            "multi-pass streaming-statistics fit."
        )
        return self._run_streaming_fit(path)

    def _run_streaming_fit(self, path: str) -> Dict[str, Any]:
        """Dispatch a multi-pass streaming fit through the retry policy.
        Streaming fits re-resolve the mesh and re-stage every chunk each
        epoch, so a device-loss recovery needs no explicit restage hook:
        the re-dispatched fit lands on the degraded mesh by construction
        and (with `checkpoint_dir` set) resumes from its last completed
        iteration."""
        from .resilience import retry_call

        return retry_call(
            lambda: self._fit_streaming(path),
            label="fit_streaming",
            log=self.logger,
        )

    def _fit(self, dataset: DatasetLike) -> "_TpuModel":
        if self._use_cpu_fallback():
            self.logger.warning(
                "Unsupported params set; falling back to CPU (sklearn) fit "
                "(analog of spark.rapids.ml.cpu.fallback, reference core.py:1283-1297)."
            )
            if isinstance(dataset, DeviceDataset):
                batch = dataset.to_host_batch()
            else:
                batch = self._extract(dataset)
            self._validate_input(batch)
            model = self._cpu_fit(batch)
            self._copyValues(model)
            return model
        t0 = time.time()
        from .tracing import device_profile, trace

        # large Spark DataFrames route around the controller: executors
        # write parquet to the exchange dir and the streaming-ingest path
        # below takes over (spark_interop.spark_dataframe_to_staging)
        from .spark_interop import is_spark_dataframe

        exchange_cleanup = None
        if is_spark_dataframe(dataset):
            from .spark_interop import spark_dataframe_to_staging

            dataset, exchange_cleanup = spark_dataframe_to_staging(dataset)
        attrs = None
        try:
            with device_profile():
                if isinstance(dataset, DeviceDataset):
                    with trace("stage_from_device", self.logger):
                        # single-element hand-off: popping below leaves
                        # the kernel runner's cell as the only owner, so
                        # an elastic restage can free the old staging
                        staged = [self._stage_from_device(dataset)]
                    with trace("fit_kernel", self.logger):
                        # elastic restage: the resident DeviceDataset is
                        # sharded over the PRE-loss mesh, so a recovery
                        # must round-trip through the host to land the
                        # rows on the survivors (that fetch can fail on
                        # real hardware — the runner then falls back to
                        # the original staging)
                        attrs = self._run_fit_kernel(
                            staged.pop(),
                            restage=lambda: self._stage_fit_input(
                                dataset.to_host_batch()
                            ),
                        )
                else:
                    from .config import get_config
                    from .streaming import is_parquet_path

                    if is_parquet_path(dataset) and get_config("streaming_ingest"):
                        with trace("stream_ingest_fit", self.logger):
                            attrs = self._stage_or_stream(dataset)
                    if attrs is None:
                        with trace("extract", self.logger):
                            batch = self._extract(dataset)
                            self._validate_input(batch)
                        from .data import _is_sparse as _sparse_chk
                        from .monitor.baseline import (
                            baseline_mode,
                            fold_batch,
                        )

                        if (
                            baseline_mode() == "on"
                            and not _sparse_chk(batch.X)
                            and np.ndim(batch.X) == 2
                        ):
                            # conf "on": in-memory fits capture their
                            # baseline from one host pass over the
                            # extracted batch (no staging, no device
                            # work; the chunked paths still prefer
                            # their zero-cost chunk fold — fold_batch
                            # no-ops once a pass has captured)
                            fold_batch(batch.X, batch.weight)
                        attrs = self._maybe_fit_sparse_stats(batch)
                    if attrs is None:
                        # fused stage-and-solve for in-memory host
                        # batches: statistics accumulate chunk-by-chunk
                        # as the rows land on the mesh (fused.py) —
                        # None keeps the two-phase stage-then-solve path
                        attrs = self._maybe_fit_fused(batch)
                    if attrs is None:
                        with trace("stage", self.logger):
                            # hand-off list: see the DeviceDataset branch
                            staged = [self._stage_fit_input(batch)]
                        with trace("fit_kernel", self.logger):
                            attrs = self._run_fit_kernel(
                                staged.pop(),
                                restage=lambda: self._stage_fit_input(batch),
                            )
        finally:
            if exchange_cleanup:
                import shutil

                shutil.rmtree(exchange_cleanup, ignore_errors=True)
        model = self._create_model(attrs)
        self._copyValues(model)
        model._num_workers = self._num_workers
        model._float32_inputs = self._float32_inputs
        self.logger.info(f"Finished fit in {time.time() - t0:.3f}s")
        return model

    def fitMultiple(
        self, dataset: DatasetLike, paramMaps: Sequence[Dict[Param, Any]]
    ) -> Iterator[Tuple[int, "_TpuModel"]]:
        """Fit one model per param map in a SINGLE pass over the data: the
        dataset is staged onto the mesh once and every param map re-runs the
        (cached-compile) kernel on the resident device arrays — the analog of
        the reference's single-pass fitMultiple (core.py:1177-1228,
        `_FitMultipleIterator` core.py:1022-1064)."""
        estimator = self.copy()

        single_pass = estimator._enable_fit_multiple_in_single_pass()
        batch = None
        if (
            single_pass
            and not isinstance(dataset, DeviceDataset)
            and type(estimator)._fit_streaming_csr
            is not _TpuCaller._fit_streaming_csr
        ):
            # extract ONCE: the same batch either proves the dataset is a
            # sparse over-budget one (per-model fits route each map
            # through the blocked-CSR statistics path; whole-densify
            # staging is impossible) or is reused for staging below
            batch = estimator._extract(dataset)
            if estimator._sparse_over_budget(batch):
                single_pass = False

        if single_pass:
            if isinstance(dataset, DeviceDataset):
                staged = {"fi": estimator._stage_from_device(dataset)}

                def _restage() -> FitInput:
                    return estimator._stage_fit_input(dataset.to_host_batch())

            else:
                if batch is None:
                    batch = estimator._extract(dataset)
                estimator._validate_input(batch)
                staged = {"fi": estimator._stage_fit_input(batch)}

                def _restage() -> FitInput:
                    return estimator._stage_fit_input(batch)

            def fit_single(index: int) -> Tuple[int, "_TpuModel"]:
                from .tracing import run_context

                est_i = estimator.copy(paramMaps[index])

                def _with_params(fi: FitInput) -> FitInput:
                    return FitInput(
                        **{**fi.__dict__, "params": dict(est_i._tpu_params)}
                    )

                def _elastic_restage() -> FitInput:
                    # elastic device-loss recovery mid-grid: re-stage
                    # onto the degraded mesh and PUBLISH the new staging
                    # so the remaining param maps fit from it instead of
                    # the arrays sharded over the lost device (a benign
                    # race: a concurrent fit holding the old staging
                    # just fails once more and restages again)
                    staged["fi"] = _restage()
                    return _with_params(staged["fi"])

                # one run_id per grid member, so a retry/recovery inside
                # fitMultiple attributes to the param map it interrupted
                with run_context(prefix="fit"):
                    attrs = est_i._run_fit_kernel(
                        _with_params(staged["fi"]), restage=_elastic_restage
                    )
                    model = est_i._create_model(attrs)
                est_i._copyValues(model, paramMaps[index])
                return index, model

        else:

            def fit_single(index: int) -> Tuple[int, "_TpuModel"]:
                return index, estimator.fit(dataset, paramMaps[index])

        return _FitMultipleIterator(fit_single, len(paramMaps))

    def _cached_fit_entry(self, dataset: DatasetLike):
        """Resident-cache entry for `dataset` (parallel/device_cache.py):
        extract + validate the host batch, fingerprint it, and return the
        cached staged arrays — staging ONCE on a miss.  Returns None (the
        caller keeps the legacy host-slicing path) when the cache is off,
        the run is multi-process, a CPU fallback/sparse kernel is
        selected, or the entry exceeds the residency budget."""
        from .parallel.device_cache import cache_enabled, get_or_stage

        if not cache_enabled():
            return None
        import jax

        if jax.process_count() > 1:
            # fold views index the GLOBAL staged layout; the per-process
            # block layout is not derivable host-side — legacy path
            return None
        if self._use_cpu_fallback():
            return None
        if not self._enable_fit_multiple_in_single_pass():
            return None
        from .data import _is_sparse

        batch = self._extract(dataset)
        if _is_sparse(batch.X) or self._use_sparse_kernel(batch):
            return None  # dense resident views only (ELL staging differs)
        self._validate_input(batch)
        X = _ensure_dense(batch.X)
        dtype = self._out_dtype(X)
        ldt = self._fit_label_dtype() if self._is_supervised() else None
        from .parallel.mesh import get_mesh

        # EVERY cached CV run gathers at least its eval rows per fold
        # (and gather-path estimators their train views too), and the
        # cross-shard take lowers to an XLA all-gather that transiently
        # replicates the full resident array on every device (~n_dev x
        # cluster-wide) plus the compacted view itself; reserve that
        # headroom up front — mask path included — or the per-fold
        # gather OOMs after the budget check said yes
        factor = float(get_mesh(self.num_workers).devices.size + 2)
        return get_or_stage(
            np.asarray(X, dtype=X.dtype),
            batch.y,
            batch.weight,
            dtype=dtype,
            label_dtype=ldt,
            num_workers=self.num_workers,
            logger=self.logger,
            working_factor=factor,
        )


class _FitMultipleIterator:
    """Thread-safe (index, model) iterator (reference core.py:1022-1064)."""

    def __init__(self, fitSingleModel: Callable[[int], Tuple[int, Any]], numModels: int):
        self.fitSingleModel = fitSingleModel
        self.numModels = numModels
        self.counter = 0
        self.lock = named_lock("fit_multiple")

    def __iter__(self) -> "_FitMultipleIterator":
        return self

    def __next__(self) -> Tuple[int, Any]:
        with self.lock:
            index = self.counter
            if index >= self.numModels:
                raise StopIteration("No models remaining.")
            self.counter += 1
        return self.fitSingleModel(index)


class _TpuEstimatorSupervised(_TpuEstimator):
    """Supervised variant (reference _CumlEstimatorSupervised core.py:1314)."""

    def _is_supervised(self) -> bool:
        return True


# ---------------------------------------------------------------------------
# _TpuModel (reference _CumlModel core.py:1356, _CumlModelWithColumns
# core.py:1756, _CumlModelWithPredictionCol core.py:1957)
# ---------------------------------------------------------------------------


class _TpuModel(Model, _TpuCaller):
    def __init__(self, **model_attributes: Any) -> None:
        super().__init__()
        self._init_tpu_params()
        self._model_attributes = model_attributes
        self.logger = get_logger(type(self))

    def _get_model_attributes(self) -> Dict[str, Any]:
        return self._model_attributes

    @classmethod
    def _from_attributes(cls, attrs: Dict[str, Any]) -> "_TpuModel":
        return cls(**attrs)

    # -- transform contract --------------------------------------------------

    def _transform_device(self, Xs: Any) -> Optional[Dict[str, Any]]:
        """Device-side transform: map a row-sharded (n_pad, d) device
        feature block to `{col: device array}` outputs (row-leading shapes).
        Row-wise models implement this; the base `_transform_array` then
        runs it data-parallel over the mesh in host-bounded chunks — the
        analog of the reference's partition-parallel `pandas_udf` transform
        (core.py:1846-1881).  Models that manage their own staging (DBSCAN,
        UMAP, kNN) leave it unimplemented."""
        return None

    def _transform_array(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        """Map a host feature block to output columns ({col_name: values}).
        Default: the distributed batched driver over `_transform_device`.
        The analog of the per-batch predict closure from
        `_get_cuml_transform_func` (reference core.py:1846-1881)."""
        outs = self._transform_mesh(X)
        if outs is None:
            raise NotImplementedError(
                f"{type(self).__name__} implements neither _transform_array "
                "nor _transform_device"
            )
        return outs

    def _fetch_transform_outputs(self, st, dev) -> Dict[str, np.ndarray]:
        """Fetch a `_transform_device` output dict back to host: device
        arrays trim their padding and restore the input row order via
        the staging layout (`RowStager.fetch`); host-computed outputs
        (degenerate-model paths) head-trim.  The one fetch contract
        shared by the chunked `_transform_mesh` driver below and the
        serving dispatcher (serving/server.py), which stages coalesced
        micro-batches itself and reuses the model's compiled
        `_transform_device` program over them."""
        import jax

        # one compute sync for ALL columns before the per-column fetch:
        # fetching column-by-column would serialize each column's
        # compute wait behind the previous column's transfer — on the
        # serving collect path that wait bills to the collect worker's
        # window instead of overlapping with later columns' compute
        dev_arrays = [v for v in dev.values() if isinstance(v, jax.Array)]
        if dev_arrays:
            jax.block_until_ready(dev_arrays)
        return {
            col: (
                st.fetch(v)
                if isinstance(v, jax.Array)
                else st.trim_host(np.asarray(v))
            )
            for col, v in dev.items()
        }

    def _transform_mesh(self, X: np.ndarray) -> Optional[Dict[str, np.ndarray]]:
        """Distributed, batched inference (reference strategy 6, SURVEY
        §2.12: non-barrier data-parallel transform).  Rows are chunked by
        the `host_batch_bytes` budget, each chunk staged row-sharded over
        the mesh, and the model's `_transform_device` runs SPMD — transform
        throughput scales with mesh size and one chip never holds more
        than a chunk.  Multi-process: every process stages its block of the
        (replicated) input and fetch reassembles global rows."""
        if type(self)._transform_device is _TpuModel._transform_device:
            return None
        import jax

        from .data import _is_sparse
        from .parallel.mesh import RowStager, get_mesh
        from .streaming import chunk_rows_for

        sparse_in = _is_sparse(X)
        if sparse_in:
            # keep CSR; each chunk densifies separately below, so peak
            # host memory is one dense chunk (not the whole matrix)
            X = X.tocsr()
            x_dtype = self._out_dtype(X)
        else:
            X = _ensure_dense(X)
            x_dtype = X.dtype
        n = int(X.shape[0])
        d = int(X.shape[1]) if X.ndim == 2 else 1
        mesh = get_mesh(
            self._num_workers if jax.process_count() == 1 else None
        )
        from .config import get_config
        from .parallel.mesh import bucket_rows_floor

        # floor the chunk to the bucket grid: full chunks then carry ZERO
        # bucket padding and still share one compilation; only the tail
        # chunk buckets up (moot when bucketing is off)
        chunk = max(
            int(chunk_rows_for(d, np.dtype(x_dtype).itemsize)),
            mesh.devices.size,
        )
        if get_config("shape_bucketing"):
            chunk = max(bucket_rows_floor(chunk), mesh.devices.size)
        if n == 0:
            # transform one dummy row, trim everything (static-shape kernels
            # can't run on 0 rows)
            dummy = self._transform_mesh(np.zeros((1, d), x_dtype))
            return {c: v[:0] for c, v in dummy.items()}
        from .tracing import trace

        n_dev = mesh.devices.size

        def _floor_chunk(c: int) -> int:
            """Keep a (re)halved chunk on the bucket grid so full chunks
            stay zero-bucket-padding (the invariant the initial floor
            above establishes)."""
            c = max(c, n_dev)
            if get_config("shape_bucketing"):
                c = max(bucket_rows_floor(c), n_dev)
            return c

        outs: Dict[str, List[np.ndarray]] = {}
        lo = 0
        def _dispatch(lo: int):
            """Stage one chunk and launch its device program (ASYNC — jax
            dispatch returns with the transfer/compute in flight)."""
            from .resilience import maybe_inject

            maybe_inject("transform_dispatch")
            hi = min(lo + chunk, n)
            with trace(f"dispatch_chunk[{lo}:{hi}]", self.logger):
                if sparse_in:
                    from .native import densify_csr

                    Xc = densify_csr(X[lo:hi], hi - lo, x_dtype)
                else:
                    Xc = np.ascontiguousarray(X[lo:hi])
                st = RowStager.for_replicated(Xc.shape[0], mesh)
                dev = self._transform_device(st.stage(Xc, x_dtype))
            return lo, hi, st, dev

        def _collect(pending) -> None:
            """Fetch one in-flight chunk (the sync point) and publish it
            whole: a failure on a later column must not leave earlier
            columns appended (the retry would duplicate their rows)."""
            lo_p, hi_p, st, dev = pending
            with trace(f"transform_chunk[{lo_p}:{hi_p}]", self.logger):
                fetched = self._fetch_transform_outputs(st, dev)
            for col, v in fetched.items():
                outs.setdefault(col, []).append(v)

        # one-deep pipeline: chunk i+1's host->device transfer rides the
        # wire while chunk i computes and fetches: the two directions
        # overlap instead of serializing stage -> compute -> fetch per
        # chunk.
        # Two chunks are in flight, so each gets HALF the single-chunk
        # budget (same peak device footprint as the serial loop), re-floored
        # to the bucket grid
        chunk = _floor_chunk(chunk // 2)
        # recovery is policy-driven (resilience/retry.py): OOM halves the
        # chunk (the policy's shrink-batch action, bounded by the n_dev
        # floor) while transient/preemption errors back off and re-dispatch
        # the SAME chunk size, bounded by max_attempts since the last
        # successfully published chunk
        from .resilience import RetryPolicy

        policy = RetryPolicy.from_config()
        transient_attempts = 0
        pending = None
        while lo < n or pending is not None:
            current = None  # a dispatch failure must not reuse last round's
            try:
                current = _dispatch(lo) if lo < n else None
                if lo < n:
                    lo = current[1]
                if pending is not None:
                    _collect(pending)
                    transient_attempts = 0  # progress resets the budget
                pending = current
            except Exception as e:
                # async errors surface at the fetch, so both in-flight
                # chunks are discarded and re-run from the first
                # unpublished row (completed chunks are kept — the analog
                # of the reference's reserved-memory OOM loop,
                # utils.py:403-522)
                action = policy.classify(e)
                if action == "fatal" or (action == "oom" and chunk <= n_dev):
                    raise
                if action != "oom":
                    transient_attempts += 1
                    if transient_attempts >= policy.max_attempts:
                        raise
                resume_at = pending[0] if pending is not None else (
                    current[0] if current is not None else lo
                )
                to_drain, pending, current = (pending, current), None, None
            else:
                continue
            # the recovery runs OUTSIDE the except block (same
            # poisoned-buffer rule as _stage_or_stream: the exception
            # state pins the failed dispatch's frames, and its locals
            # reference the very device buffers being recovered).
            # Drain the discarded in-flight programs BEFORE the retry:
            # dropping the refs only queues deletion, and an immediate
            # re-dispatch would contend with their unfreed buffers.
            # OOM ONLY: after a preemption the backing runtime is gone and
            # after a watchdog timeout the program is by definition still
            # hung — block_until_ready on either can block forever, which
            # is the very hang class this layer removes
            if action == "oom":
                for inflight in to_drain:
                    if inflight is None:
                        continue
                    for v in inflight[3].values():
                        if isinstance(v, jax.Array):
                            try:
                                v.block_until_ready()
                            except Exception:
                                pass  # the original error already surfaced
            lo = resume_at
            from .resilience.retry import RETRIES
            from .tracing import event

            # same counter family as retry_call: the inline chunk loop
            # must not diverge from the policy wrapper in the metrics
            RETRIES.inc(label="transform_dispatch", action=action)
            event(
                "retry[transform_dispatch]",
                detail=f"action={action} resume_row={lo}",
                log=self.logger,
            )
            if action == "oom":
                # drop re-creatable cache residency before shrinking the
                # chunk — the resident entries may BE the pressure
                from .parallel.device_cache import clear_device_cache

                clear_device_cache()
                chunk = _floor_chunk(chunk // 2)
                self.logger.warning(
                    f"Transform chunk exhausted device memory; resuming at "
                    f"row {lo} with chunk={chunk} rows"
                )
            elif action == "preemption":
                from .resilience.retry import _default_preemption_hook

                # the fit path's repair hook: reinit_distributed guarded so
                # a failed re-bootstrap still lets the retry run
                _default_preemption_hook()
                self.logger.warning(
                    f"Transform dispatch preempted; resuming at row {lo}"
                )
            elif action == "device_loss":
                from .resilience.elastic import recover_from_device_loss

                if recover_from_device_loss(self.logger):
                    # shrink to the surviving mesh: every remaining chunk
                    # stages fresh per dispatch, so adopting the rebuilt
                    # mesh is the whole repair (no resident state to move)
                    mesh = get_mesh(
                        self._num_workers if jax.process_count() == 1 else None
                    )
                    n_dev = mesh.devices.size
                    chunk = _floor_chunk(chunk)
                self.logger.warning(
                    f"Transform dispatch lost a device; resuming at row "
                    f"{lo} on {mesh.devices.size} device(s)"
                )
            else:  # transient
                delay = policy.backoff(transient_attempts)
                self.logger.warning(
                    f"Transform dispatch failed transiently; retrying row "
                    f"{lo} in {delay:.2f}s "
                    f"({transient_attempts}/{policy.max_attempts - 1} "
                    "retries since last progress)"
                )
                time.sleep(delay)
        if all(len(v) == 1 for v in outs.values()):
            return {c: v[0] for c, v in outs.items()}
        return {c: np.concatenate(v, axis=0) for c, v in outs.items()}

    def _output_columns(self) -> List[str]:
        if self.hasParam("predictionCol"):
            return [self.getOrDefault("predictionCol")]
        return ["prediction"]

    def _transform(self, dataset: DatasetLike):
        """Append output columns to a pandas DataFrame input, or return the
        primary output array for array input (reference
        `_CumlModelWithColumns._transform` core.py:1797-1941).  Spark
        DataFrames round-trip through Arrow and come back as Spark
        DataFrames (spark_interop.py)."""
        import pandas as pd

        from .spark_interop import is_spark_dataframe

        if is_spark_dataframe(dataset):
            from .spark_interop import pandas_to_spark, spark_dataframe_to_pandas

            out_pdf = self._transform(spark_dataframe_to_pandas(dataset))
            return pandas_to_spark(out_pdf, dataset)

        if isinstance(dataset, pd.DataFrame) and len(dataset) == 0:
            # empty input transforms to empty output (Spark semantics)
            out_df = dataset.copy()
            for col in self._output_columns():
                out_df[col] = []
            return out_df
        features_col, features_cols = _resolve_feature_params(self)
        batch = extract_arrays(
            dataset,
            features_col=features_col,
            features_cols=features_cols,
            dtype=None,
            supervised=False,
        )
        from .data import _is_sparse

        if _is_sparse(batch.X) and (
            type(self)._transform_device is not _TpuModel._transform_device
            or getattr(self, "_accepts_sparse_transform", False)
        ):
            # keep CSR: _transform_mesh densifies chunk-by-chunk, so peak
            # host memory is one dense chunk instead of the whole matrix
            outputs = self._transform_array(batch.X)
        else:
            X = _ensure_dense(batch.X)
            dtype = self._out_dtype(X)
            outputs = self._transform_array(np.asarray(X, dtype=dtype))
        if isinstance(dataset, pd.DataFrame):
            out_df = dataset.copy()
            for col, values in outputs.items():
                vals: Any = values
                if isinstance(values, np.ndarray) and values.ndim == 2:
                    vals = list(values)
                out_df[col] = vals
            return out_df
        if len(outputs) == 1:
            return next(iter(outputs.values()))
        return outputs

    # -- multi-model single-pass evaluation (reference core.py:1572-1753) ----

    @classmethod
    def _combine(cls, models: List["_TpuModel"]) -> "_CombinedModel":
        """Merge N models (one per param map) into one multi-model for
        single-pass eval (reference `_CumlModel._combine` core.py:1750-1753)."""
        return _CombinedModel(models)

    def _transformEvaluate(self, dataset: DatasetLike, evaluator: Any) -> List[float]:
        """Transform + metric in one logical pass (reference
        `_transformEvaluate` core.py:1725-1748).  A `CachedEvalView`
        scores against the RESIDENT device rows — no eval restaging."""
        from .parallel.device_cache import CachedEvalView

        if isinstance(dataset, CachedEvalView):
            return dataset.evaluate([self], evaluator)
        return [evaluator.evaluate(self.transform(dataset))]

    def cpu(self):
        """Equivalent sklearn model (the reference returns the pyspark.ml
        model, e.g. utils.py:585-809 tree translation)."""
        raise NotImplementedError


def _evaluate_frame(model: "_TpuModel", dataset: DatasetLike):
    """Shared front half of the Model.evaluate() surfaces (LogReg, LinReg,
    RandomForestClassifier): coerce to pandas, validate label/weight
    columns, run the standard `_transform`, and return
    `(out_df, labels, predictions, weights)`."""
    import pandas as pd

    from .data import _to_pandas

    pdf = dataset if isinstance(dataset, pd.DataFrame) else _to_pandas(dataset)
    label_col = model.getOrDefault("labelCol")
    if label_col not in pdf.columns:
        raise ValueError(f"evaluate requires the label column '{label_col}'")
    if len(pdf) == 0:
        raise ValueError("Dataset is empty: nothing to evaluate")
    out_df = model._transform(pdf)
    y = np.asarray(out_df[label_col], np.float64)
    preds = np.asarray(
        out_df[model.getOrDefault("predictionCol")], np.float64
    )
    weights = None
    if model.hasParam("weightCol") and model.isSet("weightCol"):
        wc = model.getOrDefault("weightCol")
        if wc not in out_df.columns:
            raise ValueError(
                f"weightCol '{wc}' is set on the model but absent from "
                "the evaluation dataset"
            )
        weights = np.asarray(out_df[wc], np.float64)
    return out_df, y, preds, weights


class _CombinedModel:
    """N models evaluated against one dataset staging (the analog of the
    reference's multi-model `_transform_evaluate_internal` pass with
    model_index partial-metric rows, core.py:1572-1693).  The input frame is
    materialized once; each member model's (compile-cached) transform runs
    over the same host arrays."""

    def __init__(self, models: List[_TpuModel]) -> None:
        if not models:
            raise ValueError("_combine requires at least one model")
        self.models = list(models)

    def _transformEvaluate(self, dataset: DatasetLike, evaluator: Any) -> List[float]:
        from .parallel.device_cache import CachedEvalView

        if isinstance(dataset, CachedEvalView):
            # every member model scores the RESIDENT sharded rows; only
            # the fold's output columns come back to host
            return dataset.evaluate(self.models, evaluator)
        import pandas as pd

        if not isinstance(dataset, pd.DataFrame):
            return [evaluator.evaluate(m.transform(dataset)) for m in self.models]
        # extract the feature matrix ONCE; every member model transforms the
        # same resident arrays (kernel compilations are shared)
        m0 = self.models[0]
        features_col, features_cols = _resolve_feature_params(m0)
        batch = extract_arrays(
            dataset,
            features_col=features_col,
            features_cols=features_cols,
            dtype=None,
            supervised=False,
        )
        from .data import _is_sparse

        keep_sparse = _is_sparse(batch.X) and all(
            type(m)._transform_device is not _TpuModel._transform_device
            for m in self.models
        )
        X = batch.X if keep_sparse else _ensure_dense(batch.X)
        results = []
        for m in self.models:
            outputs = m._transform_array(
                X if keep_sparse else np.asarray(X, dtype=m._out_dtype(X))
            )
            cols: Dict[str, Any] = {}
            for col, values in outputs.items():
                vals: Any = values
                if isinstance(values, np.ndarray) and values.ndim == 2:
                    vals = list(values)
                cols[col] = vals
            # no per-model deep copy of the input frame (round-1 review):
            # reference the original columns and append the outputs
            base = dataset
            overlap = [c for c in cols if c in dataset.columns]
            if overlap:
                base = dataset.drop(columns=overlap)
            # pandas>=3 copy-on-write: concat is lazy, no deep copy happens
            out_df = pd.concat(
                [base, pd.DataFrame(cols, index=dataset.index)], axis=1
            )
            results.append(evaluator.evaluate(out_df))
        return results
