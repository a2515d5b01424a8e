#
# k-NN: exact NearestNeighbors + ApproximateNearestNeighbors — the analog of
# reference knn.py (1729 LoC).  The cuML NearestNeighborsMG.kneighbors call
# (knn.py:688-779, UCX p2p block exchange) becomes the ops/knn.py ppermute
# ring; the cuVS ivf_flat/ivf_pq local-index-per-partition strategy
# (knn.py:1516-1657) becomes ops/ivf.py bucketed-gather search.
#
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple, Union

import numpy as np

from ..core import _TpuEstimator, _TpuModel, _resolve_feature_params, FitInput
from ..data import DatasetLike, _ensure_dense, extract_arrays
from ..params import (
    HasFeaturesCol,
    HasFeaturesCols,
    HasIDCol,
    Param,
    TypeConverters,
    _TpuParams,
)


class _NNClass:
    """Param mapping (reference _NearestNeighborsClass knn.py:76-90)."""

    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        return {"k": "n_neighbors"}

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {"n_neighbors": 5, "verbose": False}


class _KNNParams(_TpuParams, HasFeaturesCol, HasFeaturesCols, HasIDCol):
    k = Param("_", "k", "The number of nearest neighbors to retrieve.",
              TypeConverters.toInt)

    def __init__(self) -> None:
        super().__init__()
        self._setDefault(k=5)

    def setK(self, value: int):
        return self._set_params(k=value)

    def getK(self) -> int:
        return self.getOrDefault("k")

    def setFeaturesCol(self, value: Union[str, List[str]]):
        if isinstance(value, str):
            self._set_params(featuresCol=value)
        else:
            self._set_params(featuresCols=value)
        return self

    def setFeaturesCols(self, value: List[str]):
        return self._set_params(featuresCols=value)

    def setIdCol(self, value: str):
        return self._set_params(idCol=value)


def _extract_with_ids(
    inst, dataset: DatasetLike, keep_sparse: bool = False
) -> Tuple[np.ndarray, np.ndarray, Any, bool]:
    """Extract (X, ids, source_frame).  The analog of `_ensureIdCol`
    (reference params.py:91-129): when the user names an idCol it is read
    from the dataset, otherwise monotonically-increasing row ids are
    generated.  With `keep_sparse` a CSR input stays CSR — the exact-kNN
    paths stage it dense chunk-by-chunk (RowStager.stage_sparse), the
    analog of the reference keeping CSR end-to-end through fit staging
    (core.py:183-265)."""
    import pandas as pd

    from ..data import _is_sparse

    features_col, features_cols = _resolve_feature_params(inst)
    id_col = (
        inst.getOrDefault("idCol")
        if inst.hasParam("idCol") and inst.isSet("idCol")
        else None
    )
    batch = extract_arrays(
        dataset,
        features_col=features_col,
        features_cols=features_cols,
        id_col=id_col,
        dtype=None,
        supervised=False,
    )
    if keep_sparse and _is_sparse(batch.X):
        X = batch.X.tocsr()
    else:
        X = _ensure_dense(batch.X)
    if batch.row_id is not None:
        ids = np.asarray(batch.row_id)
        auto_ids = False
    else:
        ids = np.arange(X.shape[0], dtype=np.int64)
        auto_ids = True
    df = dataset if isinstance(dataset, pd.DataFrame) else None
    return X, ids, df, auto_ids


def _gather_items(X: np.ndarray, ids: np.ndarray, auto_ids: bool):
    """Multi-process item gather for the replicated-model contract.  Auto-
    generated ids are LOCAL positions per process; regenerate them as global
    positions after the gather so they match single-process numbering
    (user-provided idCol values pass through untouched)."""
    from ..data import _is_sparse
    from ..parallel.mesh import allgather_host_csr, allgather_host_rows

    X = allgather_host_csr(X) if _is_sparse(X) else allgather_host_rows(X)
    if auto_ids:
        ids = np.arange(X.shape[0], dtype=np.int64)
    else:
        ids = allgather_host_rows(ids)
    return X, ids


def _item_layout_for(X: np.ndarray, ids: np.ndarray, auto_ids: bool):
    """Decide the item layout for an exact-kNN fit: replicate the full set
    on every host (small data — the simple contract), or keep FEATURES
    process-local past `knn_replicate_max_bytes` and replicate only the
    cheap global id vector (the analog of the reference's distributed
    block exchange, knn.py:688-779, where no worker holds the full item
    matrix).  Returns (X, ids_global, distributed, n_items_global)."""
    import jax

    from ..config import get_config
    from ..parallel.mesh import allgather_host_rows

    if jax.process_count() == 1:
        X, ids = _gather_items(X, ids, auto_ids)
        return X, ids, False, X.shape[0]
    from jax.experimental import multihost_utils

    counts = np.asarray(
        multihost_utils.process_allgather(
            np.asarray(X.shape[0], np.int64)
        )
    ).reshape(-1)
    n_global = int(counts.sum())
    total_bytes = n_global * int(X.shape[1]) * X.dtype.itemsize
    if total_bytes <= int(get_config("knn_replicate_max_bytes")):
        X, ids = _gather_items(X, ids, auto_ids)
        return X, ids, False, n_global
    if auto_ids:
        ids_global = np.arange(n_global, dtype=np.int64)
    else:
        ids_global = allgather_host_rows(ids)
    return X, ids_global, True, n_global


def _assemble_knn_df(q_ids, indices, dist, sort_by_query_id: bool):
    import pandas as pd

    knn_df = pd.DataFrame(
        {
            "query_id": q_ids,
            "indices": list(indices),
            "distances": list(dist.astype(np.float32)),
        }
    )
    if sort_by_query_id:
        knn_df = knn_df.sort_values("query_id", ignore_index=True)
    return knn_df


def _flatten_join(knn_df, distCol: str, drop_invalid: bool):
    """Vectorized (item_id, query_id, dist) flattening of a knn_df."""
    import pandas as pd

    idx = np.stack(knn_df["indices"].to_numpy())
    dist = np.stack(knn_df["distances"].to_numpy())
    k = idx.shape[1]
    out = pd.DataFrame(
        {
            "item_id": idx.reshape(-1),
            "query_id": np.repeat(knn_df["query_id"].to_numpy(), k),
            distCol: dist.reshape(-1).astype(np.float64),
        }
    )
    if drop_invalid:
        out = out[(out["item_id"] >= 0) & np.isfinite(out[distCol])]
        out = out.reset_index(drop=True)
    return out


class _NNModelBase(_TpuModel):
    """Shared kneighbors/join surface for the exact and approximate models."""

    item_features: np.ndarray
    item_ids: np.ndarray
    _item_df: Any
    # exact search stages CSR queries chunk-bounded; the ANN index probes
    # take dense host queries
    _sparse_query_ok = False

    def _search(self, Q: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
        raise NotImplementedError

    def _metric(self) -> str:
        if self.hasParam("metric"):
            return str(self._tpu_params.get("metric",
                                            self.getOrDefault("metric")))
        return "euclidean"

    def _apply_metric(self, d2: np.ndarray) -> np.ndarray:
        """Map squared-euclidean kernel output to the requested metric.
        Cosine search runs on unit vectors, where cosine distance
        1 - cos = ||u-v||^2 / 2 (the cuVS cosine convention)."""
        metric = self._metric()
        if metric == "sqeuclidean":
            return d2
        if metric == "euclidean":
            return np.sqrt(d2)
        if metric == "cosine":
            return d2 / 2.0
        raise ValueError(
            f"metric '{metric}' is not supported; use euclidean, "
            "sqeuclidean, or cosine"
        )

    def kneighbors(
        self, query_df: DatasetLike, sort_knn_df_by_query_id: bool = True
    ) -> Tuple[Any, Any, Any]:
        """Return (item_df, query_df, knn_df) where knn_df holds one row per
        query: `query_id`, `indices` (item ids), `distances` — reference
        knn.py:579-657 (exact) / knn.py:1256-1470 (approximate; unreachable
        slots are id -1 at distance inf)."""
        import pandas as pd

        from ..data import _is_sparse

        Q, q_ids, q_df, _ = _extract_with_ids(
            self, query_df, keep_sparse=self._sparse_query_ok
        )
        k = int(self._tpu_params.get("n_neighbors", self.getOrDefault("k")))
        dist, pos = self._search(Q if _is_sparse(Q) else np.asarray(Q), k)
        indices = np.where(pos >= 0, self.item_ids[np.maximum(pos, 0)], -1)
        knn_df = _assemble_knn_df(q_ids, indices, dist, sort_knn_df_by_query_id)
        item_df = self._item_df
        if item_df is None:
            item_df = pd.DataFrame({"id": self.item_ids})
        return item_df, q_df, knn_df

    def _transform(self, dataset: DatasetLike):
        raise NotImplementedError(
            f"{type(self).__name__} does not support transform(); use "
            "kneighbors() or the join method (reference knn.py:560-577)."
        )

    def cpu(self):
        from sklearn.neighbors import NearestNeighbors as SkNN

        sk = SkNN(n_neighbors=int(self.getOrDefault("k")), algorithm="brute")
        sk.fit(self.item_features)
        return sk


def _finalize_nn_fit(est, model, df):
    model._item_df = df
    est._copyValues(model)
    model._tpu_params = dict(est._tpu_params)
    model._num_workers = est._num_workers
    model._float32_inputs = est._float32_inputs
    return model


class NearestNeighbors(_NNClass, _TpuEstimator, _KNNParams):
    """Exact brute-force k nearest neighbors (API parity: reference
    NearestNeighbors knn.py:208-513).

    `fit` only captures the item set (the reference's fit tags the item
    DataFrame, knn.py:352-372 — no training happens); the distributed work
    runs in `kneighbors`, where item and query rows are sharded over the
    mesh and item blocks rotate through a `ppermute` ring (the ICI-native
    analog of the reference's UCX p2p block exchange, knn.py:688-779).

    Examples
    --------
    >>> import pandas as pd
    >>> from spark_rapids_ml_tpu.knn import NearestNeighbors
    >>> items = pd.DataFrame({"features": [[0.0, 0.0], [1.0, 1.0], [5.0, 5.0]]})
    >>> queries = pd.DataFrame({"features": [[0.2, 0.2], [4.9, 5.1]]})
    >>> model = NearestNeighbors(k=1).setFeaturesCol("features").fit(items)
    >>> _, _, knn_df = model.kneighbors(queries)
    >>> [int(i[0]) for i in knn_df["indices"]]
    [0, 2]
    """

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._set_params(**kwargs)

    def _fit(self, dataset: DatasetLike) -> "NearestNeighborsModel":
        from ..data import _is_sparse

        X, ids, df, auto_ids = _extract_with_ids(self, dataset,
                                                 keep_sparse=True)
        # multi-process: each process fit() sees its local items.  Small
        # item sets replicate on every host (simple model contract); past
        # `knn_replicate_max_bytes` features stay PROCESS-LOCAL and only
        # the id vector replicates — kneighbors stages each process's
        # block into the global sharded layout, so no host or device ever
        # holds the full N x d matrix.  CSR items stay CSR on the host;
        # kneighbors stages them dense chunk-by-chunk.
        X, ids, distributed, n_global = _item_layout_for(
            X if _is_sparse(X) else np.asarray(X), np.asarray(ids), auto_ids
        )
        model = NearestNeighborsModel(
            item_features=X if _is_sparse(X) else np.asarray(X),
            item_ids=ids,
            n_cols=int(X.shape[1]),
            dtype=str(X.dtype),
            distributed_items=distributed,
            n_items_global=n_global,
        )
        return _finalize_nn_fit(self, model, df)

    def _fit_array(self, fit_input: FitInput) -> Dict[str, Any]:  # pragma: no cover
        raise NotImplementedError("fit is overridden; no kernel at fit time")

    def _create_model(self, attrs: Dict[str, Any]):  # pragma: no cover
        return NearestNeighborsModel(**attrs)


class NearestNeighborsModel(_NNClass, _NNModelBase, _KNNParams):
    """Fitted exact k-NN model (reference NearestNeighborsModel knn.py:516-940)."""

    _sparse_query_ok = True

    def __init__(self, **attrs: Any) -> None:
        super().__init__(**attrs)
        from ..data import _is_sparse

        feats = attrs["item_features"]
        # sparse fits keep the item set CSR on the host (persisted as CSR
        # component arrays, core.py _Writer.save); search stages it dense
        # chunk-by-chunk (stage_sparse), bounding host peak memory
        self.item_features = (
            feats.tocsr() if _is_sparse(feats) else np.asarray(feats)
        )
        self.item_ids: np.ndarray = np.asarray(attrs["item_ids"])
        self.n_cols = int(attrs.get("n_cols", self.item_features.shape[1]))
        self.dtype = str(attrs.get("dtype", self.item_features.dtype))
        # distributed-item layout: `item_features` holds only THIS
        # process's rows; `item_ids` is the (cheap) global id vector
        self.distributed_items = bool(attrs.get("distributed_items", False))
        self.n_items_global = int(
            attrs.get("n_items_global", self.item_features.shape[0])
        )
        self._item_df = None
        self._device_items = None  # lazily cached device-resident item shards

    def _staged_items(self, mesh, dtype):
        """Item rows + validity + positional ids staged onto the mesh once
        and reused across kneighbors calls.  Replicated item arrays shard
        via `RowStager.for_replicated` (each process stages its even block
        of the global rows); distributed item arrays stage each process's
        LOCAL block directly — either way positional ids come from the
        same layout in global process-major order and are remapped to user
        ids on the host afterwards (as the reference remaps cuml row ids,
        knn.py:787-801)."""
        from ..data import _is_sparse
        from ..parallel.mesh import RowStager

        key = (id(mesh), str(dtype))
        if self._device_items is not None and self._device_items[0] == key:
            return self._device_items[1]
        # items ALWAYS stage contiguous (interleave=False): the
        # interleaved layout breaks distance ties by device-layout
        # position, so a sparse fit (contiguous-only staging) or a
        # different device count would return different neighbors among
        # tied candidates.  Contiguous staging ties break by original
        # item position — identical for dense/sparse and for any n_dev —
        # while bucketed padding still shares compiles.
        sparse_items = _is_sparse(self.item_features)
        if self.distributed_items:
            st = RowStager(
                self.item_features.shape[0], mesh, interleave=False,
            )
        else:
            st = RowStager.for_replicated(
                self.item_features.shape[0], mesh, interleave=False,
            )
        staged = (
            st.stage_sparse(self.item_features, dtype)
            if sparse_items
            else st.stage(self.item_features, dtype),
            st.mask(dtype),
            st.row_ids(),
        )
        self._device_items = (key, staged)
        return staged

    def save(self, path: str) -> None:
        if self.distributed_items:
            raise NotImplementedError(
                "A distributed-item NearestNeighborsModel holds only this "
                "process's feature rows; persist the source dataset (or "
                "lower knn_replicate_max_bytes to refit replicated) "
                "instead of saving the model."
            )
        super().save(path)

    def cpu(self):
        if self.distributed_items:
            # sklearn on the local block would silently search a fraction
            # of the items with positions that don't match the global ids
            raise NotImplementedError(
                "cpu() needs the full item set; this distributed-item "
                "model holds only this process's rows"
            )
        return super().cpu()

    def _search(self, Q: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Distributed ring brute force; (metric distances, positional
        indices) trimmed of padding."""
        from ..ops.knn import knn_ring_topk, knn_topk_single
        from ..parallel import TpuContext
        from ..parallel.mesh import RowStager

        from ..data import _is_sparse

        n_items = self.n_items_global
        if k > n_items:
            raise ValueError(f"k={k} exceeds the number of items ({n_items})")
        with TpuContext(self.num_workers, require_p2p=True) as ctx:
            mesh = ctx.mesh
        dtype = self._out_dtype(self.item_features)
        items, valid, ids = self._staged_items(mesh, dtype)
        # queries stage contiguous like the items: the query's device
        # decides its ring start offset, so an interleaved dense layout
        # vs the contiguous sparse layout would merge item blocks in
        # different orders and resolve distance TIES differently
        if _is_sparse(Q):
            qst = RowStager.for_replicated(
                Q.shape[0], mesh, interleave=False
            )
            queries = qst.stage_sparse(Q, dtype)
        else:
            qst = RowStager.for_replicated(
                np.asarray(Q).shape[0], mesh, interleave=False
            )
            queries = qst.stage(np.asarray(Q), dtype)
        if mesh.devices.size == 1:
            d2, idx = knn_topk_single(items, valid, ids, queries, k=k)
        else:
            d2, idx = knn_ring_topk(items, valid, ids, queries, k=k, mesh=mesh)
        return self._apply_metric(qst.fetch(d2)), qst.fetch(idx)

    def exactNearestNeighborsJoin(self, query_df: DatasetLike, distCol: str = "distCol"):
        """Flattened (item_id, query_id, distance) join — reference
        knn.py:803-940."""
        _, _, knn_df = self.kneighbors(query_df)
        return _flatten_join(knn_df, distCol, drop_invalid=False)

    def _get_model_attributes(self) -> Dict[str, Any]:
        return {
            "item_features": self.item_features,
            "item_ids": self.item_ids,
            "n_cols": self.n_cols,
            "dtype": self.dtype,
        }


class _ANNClass:
    """Param mapping (reference _ApproximateNearestNeighborsClass
    knn.py:843-865)."""

    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        return {"k": "n_neighbors", "algorithm": "algorithm",
                "algoParams": "algo_params", "metric": "metric"}

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "n_neighbors": 5,
            "algorithm": "ivfflat",
            "algo_params": None,
            "metric": "euclidean",
            "verbose": False,
        }


class _ANNParams(_KNNParams):
    algorithm = Param("_", "algorithm",
                      "ANN algorithm: ivfflat, ivfpq, or cagra.",
                      TypeConverters.toString)
    algoParams = Param("_", "algoParams",
                       "algorithm-specific parameters (nlist/nprobe/M/n_bits/"
                       "refine_ratio).", TypeConverters.identity)
    metric = Param("_", "metric", "distance metric (euclidean/sqeuclidean/cosine).",
                   TypeConverters.toString)

    def __init__(self) -> None:
        super().__init__()
        self._setDefault(algorithm="ivfflat", metric="euclidean")

    def setAlgorithm(self, value: str):
        return self._set_params(algorithm=value)

    def getAlgorithm(self) -> str:
        return self.getOrDefault("algorithm")

    def setAlgoParams(self, value: Dict[str, Any]):
        return self._set_params(algoParams=value)

    def setMetric(self, value: str):
        return self._set_params(metric=value)


_SUPPORTED_ANN_ALGOS = ("ivfflat", "ivfpq", "cagra")


class ApproximateNearestNeighbors(_ANNClass, _TpuEstimator, _ANNParams):
    """Approximate k nearest neighbors (API parity: reference
    ApproximateNearestNeighbors knn.py:941-1222, backed by cuVS
    ivf_flat/ivf_pq/cagra).

    `fit` trains the index: an ops/kmeans.py coarse quantizer plus (for
    `ivfpq`) per-subspace residual codebooks — the analog of the cuVS index
    build (reference knn.py:1516-1530) — or, for `cagra`, an NN-descent
    kNN graph searched by fixed-iteration beam traversal (ops/cagra.py; the
    analog of cuVS CAGRA, reference knn.py:1581-1657).  `kneighbors`
    shards queries over the mesh and probes the replicated index (the
    single-controller inverse of the reference's shard-index/
    broadcast-queries layout, knn.py:1448-1470).

    algoParams (reference knn.py:860-865 passthrough dict):
      - nlist: number of inverted lists (default ~sqrt(n))
      - nprobe: lists probed per query (default 20, clamped to nlist)
      - M / n_bits: ivfpq subspaces / code bits (defaults 8 / 8)
      - refine_ratio: ivfpq exact re-rank multiplier (default 2)
      - graph_degree / nn_descent_niter: cagra graph degree (default 32)
        and NN-descent build rounds (default 8)
      - nn_descent_sample: cagra local-join width per round (default
        graph_degree; pass 2*graph_degree for the exhaustive join)
      - itopk_size / max_iterations: cagra search beam width (default 64)
        and traversal iterations (default 12) — cuVS search param names

    Examples
    --------
    >>> import numpy as np
    >>> from spark_rapids_ml_tpu.knn import ApproximateNearestNeighbors
    >>> X = np.random.default_rng(0).normal(size=(256, 16)).astype("float32")
    >>> ann = ApproximateNearestNeighbors(k=4, algoParams={"nlist": 8, "nprobe": 8})
    >>> _, _, knn_df = ann.fit(X).kneighbors(X[:10])
    >>> [int(i[0]) for i in knn_df["indices"]] == list(range(10))
    True
    """

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._set_params(**kwargs)

    def _fit(self, dataset: DatasetLike) -> "ApproximateNearestNeighborsModel":
        from ..ops import ivf as ivf_ops

        X, ids, df, auto_ids = _extract_with_ids(self, dataset)
        # replicated-model contract in multi-process mode (see
        # NearestNeighbors._fit); each process builds the identical index
        X, ids = _gather_items(np.asarray(X), np.asarray(ids), auto_ids)
        X = np.ascontiguousarray(X, dtype=np.float32)
        algo = str(self._tpu_params.get("algorithm", "ivfflat")).lower()
        if algo not in _SUPPORTED_ANN_ALGOS:
            raise ValueError(
                f"algorithm '{algo}' is not supported; choose from "
                f"{_SUPPORTED_ANN_ALGOS}"
            )
        metric = str(self._tpu_params.get("metric", "euclidean"))
        if metric not in ("euclidean", "sqeuclidean", "cosine"):
            raise ValueError(
                f"metric '{metric}' is not supported; use euclidean, "
                "sqeuclidean, or cosine"
            )
        if metric == "cosine":
            # cuVS cosine == euclidean on unit vectors / 2: build the index
            # over normalized items (queries normalize at search)
            X = X / np.maximum(
                np.linalg.norm(X, axis=1, keepdims=True), 1e-12
            ).astype(np.float32)
        ap = dict(self._tpu_params.get("algo_params") or {})
        n = X.shape[0]
        nlist = int(ap.get("nlist", max(1, min(int(np.sqrt(n)), n))))
        nlist = max(1, min(nlist, n))
        attrs: Dict[str, Any] = {
            "item_features": X,
            "item_ids": ids,
            "n_cols": int(X.shape[1]),
            "dtype": str(X.dtype),
            "algorithm": algo,
            "nlist": nlist,
        }
        if algo == "cagra":
            from ..ops.cagra import build_cagra_graph
            from ..parallel.mesh import (
                _chunked_device_get,
                _chunked_device_put,
            )

            deg = int(ap.get("graph_degree", 32))
            deg = max(1, min(deg, n - 1))
            rounds = int(ap.get("nn_descent_niter", 8))
            sample = ap.get("nn_descent_sample")
            # bounded-piece upload of a BASELINE-scale item matrix
            # (10M x 128 = 5 GB): mesh._chunked_device_put
            graph = build_cagra_graph(
                _chunked_device_put(np.ascontiguousarray(X)),
                seed=0,
                deg=deg,
                rounds=max(rounds, 1),
                sample=None if sample is None else int(sample),
            )
            # bounded-slice fetch: a one-shot 1.28 GB graph download
            # crashed the worker after a fully successful 10M build
            attrs.update(cagra_graph=_chunked_device_get(graph))
        elif algo == "ivfflat":
            index = ivf_ops.build_ivfflat(X, nlist=nlist)
            attrs.update(
                ivf_centers=index.centers,
                ivf_buckets=index.buckets,
                ivf_bucket_ids=index.bucket_ids,
                ivf_bucket_valid=index.bucket_valid,
                ivf_sub_table=index.sub_table,
            )
        else:  # ivfpq
            M = int(ap.get("M", 8))
            d = X.shape[1]
            if d % M != 0:  # shrink M to a divisor (cuVS requires divisibility)
                M = next(m for m in range(min(M, d), 0, -1) if d % m == 0)
            n_bits = int(ap.get("n_bits", 8))
            if not 1 <= n_bits <= 8:
                # codes are stored uint8; >8 bits would silently wrap
                raise ValueError(f"ivfpq n_bits must be in [1, 8], got {n_bits}")
            index = ivf_ops.build_ivfpq(X, nlist=nlist, M=M, n_bits=n_bits)
            attrs.update(
                ivf_centers=index.centers,
                pq_codebooks=index.codebooks,
                pq_codes=index.codes,
                ivf_bucket_ids=index.bucket_ids,
                ivf_bucket_valid=index.bucket_valid,
                ivf_sub_table=index.sub_table,
                pq_M=M,
            )
        model = ApproximateNearestNeighborsModel(**attrs)
        return _finalize_nn_fit(self, model, df)

    def _fit_array(self, fit_input: FitInput) -> Dict[str, Any]:  # pragma: no cover
        raise NotImplementedError("fit is overridden; index build is host-orchestrated")

    def _create_model(self, attrs: Dict[str, Any]):  # pragma: no cover
        return ApproximateNearestNeighborsModel(**attrs)


class ApproximateNearestNeighborsModel(_ANNClass, _NNModelBase, _ANNParams):
    """Fitted ANN model (reference ApproximateNearestNeighborsModel
    knn.py:1223-1729)."""

    def __init__(self, **attrs: Any) -> None:
        super().__init__(**attrs)
        self.item_features: np.ndarray = np.asarray(attrs["item_features"])
        self.item_ids: np.ndarray = np.asarray(attrs["item_ids"])
        self.n_cols = int(attrs.get("n_cols", self.item_features.shape[1]))
        self.dtype = str(attrs.get("dtype", self.item_features.dtype))
        self.algorithm_: str = str(attrs.get("algorithm", "ivfflat"))
        self.nlist_: int = int(attrs.get("nlist", 1))
        if (
            self.algorithm_ in ("ivfflat", "ivfpq")
            and "ivf_sub_table" not in attrs
            and "ivf_centers" in attrs
        ):
            # models persisted before sub-list splitting: every list is
            # its own (only) sub-list — the identity table
            attrs["ivf_sub_table"] = np.arange(
                np.asarray(attrs["ivf_centers"]).shape[0], dtype=np.int32
            )[:, None]
        self._attrs = attrs
        self._item_df = None
        self._device_index = None  # lazily cached device-resident index

    def _staged_index(self, names):
        """The inverted file staged into HBM once and reused across
        kneighbors calls (replicated; queries are what gets sharded).
        Large arrays (a 10M-item inverted file is ~5+ GB) upload in
        bounded pieces (mesh._chunked_device_put)."""
        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel.mesh import _chunked_device_put

        if self._device_index is None or self._device_index[0] != names:
            from ..parallel import TpuContext

            with TpuContext(self.num_workers) as ctx:
                repl = NamedSharding(ctx.mesh, PartitionSpec())
            # every attribute gets the same replicated placement; the
            # helper one-shot-puts anything under the transfer ceiling
            staged = tuple(
                _chunked_device_put(
                    np.ascontiguousarray(np.asarray(self._attrs[n])), repl
                )
                for n in names
            )
            self._device_index = (names, staged)
        return self._device_index[1]

    def _search(self, Q: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Chunked search: bounds the per-dispatch candidate working set
        (IVF gathers nprobe·bucket·d floats per query, CAGRA beam·deg·d —
        at 10k+ queries one dispatch would materialize tens of GB)."""
        from ..parallel import TpuContext

        n_items = int(self.item_features.shape[0])
        if k > n_items:
            # search_cagra's top_k(beam) and the IVF shortlists all require
            # k <= n; fail with a clear message instead of an XLA error
            raise ValueError(
                f"k={k} exceeds the number of indexed items ({n_items})"
            )
        Q = np.ascontiguousarray(Q, dtype=np.float32)
        if self._metric() == "cosine":
            # normalize once for all chunks (index is built on unit vectors)
            Q = Q / np.maximum(
                np.linalg.norm(Q, axis=1, keepdims=True), 1e-12
            ).astype(np.float32)
        with TpuContext(self.num_workers) as ctx:
            mesh = ctx.mesh
        nq = int(Q.shape[0])
        per_q = self._per_query_candidate_bytes(k)
        from ..parallel.device_cache import device_hbm_bytes

        budget = device_hbm_bytes(mesh.devices.flat[0]) // 8
        # floor 1, not a fixed batch: a 64-query floor at BASELINE-scale
        # bucket sizes forced a working set far past HBM (10M ANN run)
        chunk = max(1, min(nq, budget // max(per_q, 1)))
        if nq <= chunk:
            return self._search_chunk(Q, k, mesh)
        outs = [
            self._search_chunk(Q[lo : lo + chunk], k, mesh)
            for lo in range(0, nq, chunk)
        ]
        return (
            np.concatenate([d for d, _ in outs]),
            np.concatenate([p for _, p in outs]),
        )

    def _per_query_candidate_bytes(self, k: int) -> int:
        ap = dict(self._tpu_params.get("algo_params") or {})
        d = int(self.n_cols)
        if self.algorithm_ == "cagra":
            deg = int(self._attrs["cagra_graph"].shape[1])
            beam = max(int(ap.get("itopk_size", 64)), k)
            width = beam * (1 + deg) + deg
        elif self.algorithm_ == "ivfflat":
            # the probe-rank fold visits ONE list per step: per-query
            # peak is a single (mb, d) gather + distances, not nprobe x
            mb = int(self._attrs["ivf_buckets"].shape[1])
            width = mb
        else:  # ivfpq: one (mb, M) code gather per step + the per-parent
            # ADC LUT block (nprobe, M, ksub) precomputed up front and
            # live across the whole fold loop (ops/ivf.py search_ivfpq)
            mb = int(self._attrs["pq_codes"].shape[1])
            M = int(self._attrs.get("pq_M", 8))
            ksub = int(self._attrs["pq_codebooks"].shape[1])
            nprobe = max(1, min(int(ap.get("nprobe", 20)), self.nlist_))
            return (mb * (M * 4 + 8) + nprobe * M * ksub) * 4
        # distances + gathered vectors + dedup/sort keys, ~2x slack
        return width * (d + 4) * 4 * 2

    def _search_chunk(
        self, Q: np.ndarray, k: int, mesh
    ) -> Tuple[np.ndarray, np.ndarray]:
        from ..ops import ivf as ivf_ops
        from ..parallel.mesh import RowStager

        qst = RowStager.for_replicated(Q.shape[0], mesh)
        Qs = qst.stage(Q, np.float32)
        ap = dict(self._tpu_params.get("algo_params") or {})
        nprobe = int(ap.get("nprobe", 20))
        # nprobe means DISTINCT coarse parent cells — sub-list splitting
        # (ops/ivf.py) is expanded inside the search via sub_table
        nprobe = max(1, min(nprobe, self.nlist_))
        if self.algorithm_ == "cagra":
            from ..ops.cagra import search_cagra

            items, graph = self._staged_index(("item_features", "cagra_graph"))
            beam = int(ap.get("itopk_size", 64))
            beam = max(beam, k)
            iters = int(ap.get("max_iterations", 12))
            d2, pos = search_cagra(
                Qs, items, graph, k=k, beam=beam, iters=max(iters, 1)
            )
        elif self.algorithm_ == "ivfflat":
            centers, buckets, bids, bvalid, stab = self._staged_index(
                ("ivf_centers", "ivf_buckets", "ivf_bucket_ids",
                 "ivf_bucket_valid", "ivf_sub_table")
            )
            d2, pos = ivf_ops.search_ivfflat(
                Qs, centers, buckets, bids, bvalid, stab,
                nprobe=nprobe, k=k,
            )
        else:
            centers, codebooks, codes, bids, bvalid, stab = (
                self._staged_index(
                    ("ivf_centers", "pq_codebooks", "pq_codes",
                     "ivf_bucket_ids", "ivf_bucket_valid", "ivf_sub_table")
                )
            )
            refine = int(ap.get("refine_ratio", 2))
            k2 = min(max(k * refine, k), self.item_features.shape[0])
            d2, pos = ivf_ops.search_ivfpq(
                Qs, centers, codebooks, codes, bids, bvalid, stab,
                nprobe=nprobe, k=k2,
            )
            return self._exact_rerank(Q, qst.fetch(pos), k)
        # CAGRA / IVF-Flat: the kernels rank by matmul-identity distances
        # (x2 + c2 - 2xc), whose f32 cancellation leaves ~1e-4 absolute
        # error (a point's own distance comes back ~0.008, not 0).  The
        # final top-k is re-scored in the cancellation-free diff form —
        # the same exact pass cuVS `refine` runs (reference
        # knn.py:1627-1657) — so reported distances are exact and
        # near-ties order correctly.
        return self._exact_rerank(Q, qst.fetch(pos), k)

    def _exact_rerank(
        self, Q: np.ndarray, pos: np.ndarray, k: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Exact diff-form re-score + re-rank of a (q, >=k) candidate id
        block; invalid slots (pos < 0) sort last and stay -1."""
        safe = np.maximum(pos, 0)
        cand = self.item_features[safe]  # (q, k2, d)
        diff = cand - Q[:, None, :]
        exact = (diff * diff).sum(axis=2).astype(np.float32)
        exact = np.where(pos >= 0, exact, np.inf)
        order = np.argsort(exact, axis=1, kind="stable")[:, :k]
        d2 = np.take_along_axis(exact, order, axis=1)
        out_pos = np.take_along_axis(pos, order, axis=1)
        return self._apply_metric(d2), out_pos

    def approxSimilarityJoin(self, query_df: DatasetLike, distCol: str = "distCol"):
        """Flattened approximate join (reference knn.py:1671-1729); slots
        with no reachable candidate are dropped."""
        _, _, knn_df = self.kneighbors(query_df)
        return _flatten_join(knn_df, distCol, drop_invalid=True)

    def _get_model_attributes(self) -> Dict[str, Any]:
        return dict(self._attrs)


__all__ = [
    "NearestNeighbors",
    "NearestNeighborsModel",
    "ApproximateNearestNeighbors",
    "ApproximateNearestNeighborsModel",
]
