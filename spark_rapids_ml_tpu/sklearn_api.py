#
# sklearn-style adapters — the zero-import-change surface.  The reference's
# install hook swaps pyspark.ml classes for accelerated ones
# (install.py:51-77); without Spark in this environment the host ML library
# is scikit-learn, so the same capability is a set of estimators with
# sklearn's constructor/fit(X, y)/predict surface backed by the TPU
# kernels.  `spark_rapids_ml_tpu.install` monkey-patches these over the
# sklearn modules; `python -m spark_rapids_ml_tpu script.py` runs an
# unmodified sklearn script against them (reference __main__.py).
#
from __future__ import annotations

from typing import Any, Optional

import numpy as np


class _FacadeBase:
    """get_params/set_params so sklearn.base.clone and the model-selection
    meta-estimators (GridSearchCV, cross_val_score, Pipeline) accept the
    facades after install()."""

    @classmethod
    def _param_names(cls):
        import inspect

        sig = inspect.signature(cls.__init__)
        return [
            p.name
            for p in sig.parameters.values()
            if p.name != "self" and p.kind is not p.VAR_KEYWORD
        ]

    def get_params(self, deep: bool = True):
        return {
            n: getattr(self, n) for n in self._param_names() if hasattr(self, n)
        }

    def set_params(self, **params: Any):
        known = set(self._param_names())
        unknown = {k: v for k, v in params.items() if k not in known}
        if unknown:
            # real sklearn raises here; the facade warns so grid searches
            # over unsupported params are at least visibly no-ops
            self._warn_ignored(unknown)
        for k, v in params.items():
            if k in known:
                setattr(self, k, v)
        return self

    def _warn_ignored(self, ignored: dict) -> None:
        """Unknown sklearn kwargs are accepted (so drop-in scripts run) but
        announced: silently diverging from sklearn behavior (class_weight=,
        dual=, solver=, ...) is worse than a warning."""
        # sklearn passes defaults explicitly through clone(); only values
        # that differ from "unset" are worth flagging
        noisy = {k: v for k, v in ignored.items() if v is not None}
        if noisy:
            import warnings

            warnings.warn(
                f"{type(self).__name__}: ignoring unsupported sklearn "
                f"parameters {sorted(noisy)}; results may differ from "
                f"sklearn if these were set deliberately.",
                UserWarning,
                stacklevel=3,
            )


def _max_features_to_strategy(mf: Any) -> str:
    """sklearn max_features -> Spark featureSubsetStrategy.  Note int 1
    means ONE feature per split; only None/float 1.0 mean all features."""
    if mf in ("sqrt", "log2", "all"):
        return str(mf)
    if mf is None or (isinstance(mf, float) and mf == 1.0):
        return "all"
    return str(mf)


class KMeans(_FacadeBase):
    """sklearn.cluster.KMeans-style facade over models.clustering.KMeans."""

    def __init__(
        self,
        n_clusters: int = 8,
        *,
        init: str = "k-means++",
        n_init: Any = "auto",
        max_iter: int = 300,
        tol: float = 1e-4,
        random_state: Optional[int] = None,
        **_ignored: Any,
    ) -> None:
        self._warn_ignored(_ignored)
        if not isinstance(init, str):
            raise NotImplementedError(
                "explicit initial centers (ndarray init) are not supported; "
                "use init='k-means++' or 'random'"
            )
        self.n_clusters = n_clusters
        self.init = init
        self.n_init = n_init
        self.max_iter = max_iter
        self.tol = tol
        self.random_state = random_state

    def fit(self, X, y=None, sample_weight=None) -> "KMeans":
        from .models.clustering import KMeans as TpuKMeans

        est = TpuKMeans(
            k=self.n_clusters,
            maxIter=self.max_iter,
            tol=self.tol,
            seed=self.random_state if self.random_state is not None else 42,
            initMode="random" if self.init == "random" else "k-means||",
        )
        X = np.asarray(X)
        if sample_weight is not None:
            import pandas as pd

            df = pd.DataFrame({"features": list(X), "w": sample_weight})
            est.setFeaturesCol("features").setWeightCol("w")
            self._model = est.fit(df)
        else:
            self._model = est.fit(X)
        self.cluster_centers_ = self._model.cluster_centers_
        self.inertia_ = self._model.inertia_
        self.n_iter_ = self._model.n_iter_
        self.labels_ = self.predict(X)
        return self

    def predict(self, X) -> np.ndarray:
        return self._model._transform_array(
            np.asarray(X, dtype=np.float32)
        )[self._model.getOrDefault("predictionCol")]

    def fit_predict(self, X, y=None, sample_weight=None) -> np.ndarray:
        return self.fit(X, y, sample_weight).labels_


class DBSCAN(_FacadeBase):
    """sklearn.cluster.DBSCAN-style facade over models.clustering.DBSCAN."""

    def __init__(
        self,
        eps: float = 0.5,
        *,
        min_samples: int = 5,
        metric: str = "euclidean",
        **_ignored: Any,
    ) -> None:
        self._warn_ignored(_ignored)
        self.eps = eps
        self.min_samples = min_samples
        self.metric = metric

    def fit(self, X, y=None) -> "DBSCAN":
        from .models.clustering import DBSCAN as TpuDBSCAN

        model = TpuDBSCAN(
            eps=self.eps, min_samples=self.min_samples, metric=self.metric
        ).fit(np.asarray(X))
        self.labels_ = model._transform_array(
            np.asarray(X, dtype=np.float32)
        )[model.getOrDefault("predictionCol")]
        return self

    def fit_predict(self, X, y=None) -> np.ndarray:
        return self.fit(X).labels_


class PCA(_FacadeBase):
    """sklearn.decomposition.PCA-style facade over models.feature.PCA."""

    def __init__(self, n_components: Any = None, **_ignored: Any) -> None:
        self._warn_ignored(_ignored)
        if n_components == "mle":
            raise NotImplementedError(
                "n_components='mle' is not supported; pass an int or a "
                "variance fraction in (0, 1)"
            )
        self.n_components = n_components

    def fit(self, X, y=None) -> "PCA":
        from .models.feature import PCA as TpuPCA

        X = np.asarray(X)
        nc = self.n_components
        full_k = min(X.shape)
        if nc is None:
            k = full_k
        elif isinstance(nc, float) and 0.0 < nc < 1.0:
            k = full_k  # variance-fraction selection: fit full, trim below
        else:
            k = int(nc)
        model = TpuPCA(k=k).fit(X)
        if isinstance(nc, float) and 0.0 < nc < 1.0:
            ratios = np.asarray(model.explained_variance_ratio_)
            keep = int(np.searchsorted(np.cumsum(ratios), nc) + 1)
            model = TpuPCA(k=keep).fit(X)
        self._model = model
        self.components_ = self._model.components_
        self.explained_variance_ = np.asarray(self._model.explained_variance_)
        self.explained_variance_ratio_ = np.asarray(
            self._model.explained_variance_ratio_
        )
        self.mean_ = np.asarray(self._model.mean_)
        return self

    def transform(self, X) -> np.ndarray:
        out = self._model._transform_array(np.asarray(X, dtype=np.float32))
        return np.asarray(out[self._model.getOrDefault("outputCol")])

    def fit_transform(self, X, y=None) -> np.ndarray:
        return self.fit(X).transform(X)


class LinearRegression(_FacadeBase):
    """sklearn.linear_model.LinearRegression-style facade."""

    def __init__(self, *, fit_intercept: bool = True, **_ignored: Any) -> None:
        self._warn_ignored(_ignored)
        self.fit_intercept = fit_intercept

    def fit(self, X, y, sample_weight=None) -> "LinearRegression":
        from .models.regression import LinearRegression as TpuLR

        est = TpuLR(regParam=0.0, fitIntercept=self.fit_intercept)
        self._model = _fit_supervised(est, X, y, sample_weight)
        self.coef_ = self._model.coef_
        self.intercept_ = self._model.intercept
        return self

    def predict(self, X) -> np.ndarray:
        return _predict(self._model, X)

    def score(self, X, y) -> float:
        from sklearn.metrics import r2_score

        return float(r2_score(y, self.predict(X)))


class LogisticRegression(_FacadeBase):
    """sklearn.linear_model.LogisticRegression-style facade."""

    def __init__(
        self,
        *,
        penalty: Optional[str] = "deprecated",  # sklearn 1.9's unset sentinel
        C: float = 1.0,
        l1_ratio: Optional[float] = None,
        fit_intercept: bool = True,
        max_iter: int = 100,
        tol: float = 1e-4,
        **_ignored: Any,
    ) -> None:
        self._warn_ignored(_ignored)
        self.penalty = penalty
        self.C = C
        self.l1_ratio = l1_ratio
        self.fit_intercept = fit_intercept
        self.max_iter = max_iter
        self.tol = tol

    def fit(self, X, y, sample_weight=None) -> "LogisticRegression":
        from .models.classification import LogisticRegression as TpuLogReg

        # sklearn penalty -> (regParam, elasticNetParam).  sklearn minimizes
        # C·Σᵢ logloss + penalty(β) while the backend objective
        # (ops/logistic.py) is (Σ wᵢ logloss)/W + regParam·penalty(β) with
        # W = Σ wᵢ; dividing sklearn's objective by C·W shows the equivalent
        # regParam is 1/(C·W), not 1/C.
        W = (
            float(np.sum(sample_weight))
            if sample_weight is not None
            else float(np.shape(X)[0])
        )
        inv_cw = 1.0 / (self.C * W) if self.C > 0 and W > 0 else 0.0
        if self.penalty is None or self.penalty == "none":
            reg, l1r = 0.0, 0.0
        elif self.penalty == "deprecated":
            # sklearn 1.9's unset sentinel: the l1_ratio-only API governs
            # (l1_ratio=1 == l1, 0/None == l2)
            reg = inv_cw
            l1r = float(self.l1_ratio) if self.l1_ratio is not None else 0.0
        elif self.penalty == "l2":
            # an explicitly named penalty wins over l1_ratio, matching
            # released sklearn (which ignores l1_ratio unless elasticnet)
            reg, l1r = inv_cw, 0.0
        elif self.penalty == "l1":
            reg, l1r = inv_cw, 1.0
        elif self.penalty == "elasticnet":
            if self.l1_ratio is None:
                raise ValueError(
                    "l1_ratio must be specified when penalty is elasticnet"
                )
            reg, l1r = inv_cw, float(self.l1_ratio)
        else:
            raise ValueError(f"Unsupported penalty: {self.penalty}")
        est = TpuLogReg(
            regParam=reg,
            elasticNetParam=l1r,
            fitIntercept=self.fit_intercept,
            maxIter=self.max_iter,
            tol=self.tol,
            standardization=False,
        )
        self._model = _fit_supervised(est, X, y, sample_weight)
        self.coef_ = self._model.coef_
        self.intercept_ = self._model.intercept_
        self.classes_ = np.asarray(self._model.classes_)
        return self

    def predict(self, X) -> np.ndarray:
        return _predict(self._model, X)

    def predict_proba(self, X) -> np.ndarray:
        out = self._model._transform_array(np.asarray(X, dtype=np.float32))
        return np.asarray(out[self._model.getOrDefault("probabilityCol")])

    def score(self, X, y) -> float:
        return float((self.predict(X) == np.asarray(y)).mean())


class RandomForestClassifier(_FacadeBase):
    """sklearn.ensemble.RandomForestClassifier-style facade."""

    def __init__(
        self,
        n_estimators: int = 100,
        *,
        max_depth: Optional[int] = None,
        criterion: str = "gini",
        max_features: Any = "sqrt",
        bootstrap: bool = True,
        random_state: Optional[int] = None,
        **_ignored: Any,
    ) -> None:
        self._warn_ignored(_ignored)
        self.n_estimators = n_estimators
        # sklearn's max_depth=None means unbounded; the histogram builder
        # unrolls one program body per level until the frontier is as wide
        # as the rows (ops/forest.py), so 16 (cuML's default) is the
        # practical cap here.  Pass max_depth explicitly for deeper trees.
        self.max_depth = max_depth if max_depth is not None else 16
        self.criterion = criterion
        self.max_features = max_features
        self.bootstrap = bootstrap
        self.random_state = random_state

    def fit(self, X, y, sample_weight=None) -> "RandomForestClassifier":
        from .models.classification import (
            RandomForestClassifier as TpuRFC,
        )

        est = TpuRFC(
            numTrees=self.n_estimators,
            maxDepth=self.max_depth,
            impurity=self.criterion,
            featureSubsetStrategy=_max_features_to_strategy(self.max_features),
            bootstrap=self.bootstrap,
            seed=self.random_state if self.random_state is not None else 42,
        )
        self._model = _fit_supervised(est, X, y, sample_weight)
        self.classes_ = np.arange(self._model.numClasses, dtype=float)
        self.feature_importances_ = self._model.featureImportances
        return self

    def predict(self, X) -> np.ndarray:
        return _predict(self._model, X)

    def predict_proba(self, X) -> np.ndarray:
        out = self._model._transform_array(np.asarray(X, dtype=np.float32))
        probs = np.asarray(out[self._model.getOrDefault("probabilityCol")])
        return probs / probs.sum(axis=1, keepdims=True)

    def score(self, X, y) -> float:
        return float((self.predict(X) == np.asarray(y)).mean())


class RandomForestRegressor(_FacadeBase):
    """sklearn.ensemble.RandomForestRegressor-style facade."""

    def __init__(
        self,
        n_estimators: int = 100,
        *,
        max_depth: Optional[int] = None,
        max_features: Any = 1.0,
        bootstrap: bool = True,
        random_state: Optional[int] = None,
        **_ignored: Any,
    ) -> None:
        self._warn_ignored(_ignored)
        self.n_estimators = n_estimators
        # depth default: see RandomForestClassifier.__init__
        self.max_depth = max_depth if max_depth is not None else 16
        self.max_features = max_features
        self.bootstrap = bootstrap
        self.random_state = random_state

    def fit(self, X, y, sample_weight=None) -> "RandomForestRegressor":
        from .models.regression import RandomForestRegressor as TpuRFR

        est = TpuRFR(
            numTrees=self.n_estimators,
            maxDepth=self.max_depth,
            featureSubsetStrategy=_max_features_to_strategy(self.max_features),
            bootstrap=self.bootstrap,
            seed=self.random_state if self.random_state is not None else 42,
        )
        self._model = _fit_supervised(est, X, y, sample_weight)
        self.feature_importances_ = self._model.featureImportances
        return self

    def predict(self, X) -> np.ndarray:
        return _predict(self._model, X)

    def score(self, X, y) -> float:
        from sklearn.metrics import r2_score

        return float(r2_score(y, self.predict(X)))


class NearestNeighbors(_FacadeBase):
    """sklearn.neighbors.NearestNeighbors-style facade."""

    def __init__(self, *, n_neighbors: int = 5, **_ignored: Any) -> None:
        self._warn_ignored(_ignored)
        self.n_neighbors = n_neighbors

    def fit(self, X, y=None) -> "NearestNeighbors":
        from .models.knn import NearestNeighbors as TpuNN

        self._model = TpuNN(k=self.n_neighbors).fit(np.asarray(X))
        return self

    def kneighbors(self, X=None, n_neighbors: Optional[int] = None,
                   return_distance: bool = True):
        if X is None:
            raise ValueError("X=None (self-query) is not supported")
        k = n_neighbors or self.n_neighbors
        dist, pos = self._model._search(np.asarray(X, dtype=np.float32), k)
        if return_distance:
            return dist, pos
        return pos


def _fit_supervised(est, X, y, sample_weight=None):
    if sample_weight is not None:
        import pandas as pd

        df = pd.DataFrame(
            {
                "features": list(np.asarray(X)),
                "label": np.asarray(y, dtype=np.float64),
                "w": np.asarray(sample_weight, dtype=np.float64),
            }
        )
        est.setFeaturesCol("features").setLabelCol("label").setWeightCol("w")
        return est.fit(df)
    return est.fit((np.asarray(X), np.asarray(y)))


def _predict(model, X) -> np.ndarray:
    out = model._transform_array(np.asarray(X, dtype=np.float32))
    return np.asarray(out[model.getOrDefault("predictionCol")])


__all__ = [
    "KMeans", "DBSCAN", "PCA", "LinearRegression", "LogisticRegression",
    "RandomForestClassifier", "RandomForestRegressor", "NearestNeighbors",
]
