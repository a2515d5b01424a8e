#
# The seeded data generators (benchmark/gen_data.py,
# benchmark/gen_data_distributed.py), and every estimator family fitted
# and applied through its public API on their rows.
#
import os
import sys

import numpy as np
import pandas as pd
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import gen_data  # noqa: E402


def test_gen_data_parquet(tmp_path):
    X, y = gen_data.gen_classification(100, 6, n_classes=3, seed=1)
    assert X.shape == (100, 6) and set(np.unique(y)) == {0.0, 1.0, 2.0}
    path = str(tmp_path / "d.parquet")
    gen_data.write_parquet(X, y, path)

    df = pd.read_parquet(path)
    assert len(df) == 100 and "label" in df.columns

    # scalar layout
    path2 = str(tmp_path / "d2.parquet")
    gen_data.write_parquet(X, None, path2, feature_layout="scalar")
    df2 = pd.read_parquet(path2)
    assert list(df2.columns) == [f"c{i}" for i in range(6)]


def test_gen_data_distributed_consistency(tmp_path):
    """Partition-decomposable generation: any partitioning of the same
    (kind, seed, shape) yields the same dataset, and the streaming fit
    recovers the shared structure."""
    import pyarrow.parquet as pq

    from benchmark.gen_data_distributed import generate_partitioned

    a = generate_partitioned(
        "regression", 2000, 8, str(tmp_path / "a"), parts=4, seed=7
    )
    t = pq.read_table(a)
    assert t.num_rows == 2000
    # two datagen workers writing interleaved parts == one worker
    b_dir = str(tmp_path / "b")
    generate_partitioned("regression", 2000, 8, b_dir, parts=4, seed=7,
                         part_offset=0, part_stride=2)
    generate_partitioned("regression", 2000, 8, b_dir, parts=4, seed=7,
                         part_offset=1, part_stride=2)
    tb = pq.read_table(b_dir)
    assert t.equals(tb)


def test_gen_data_distributed_streaming_fit(tmp_path):
    from benchmark.gen_data_distributed import RegressionGen, generate_partitioned
    from spark_rapids_ml_tpu.regression import LinearRegression

    out = generate_partitioned(
        "regression", 3000, 6, str(tmp_path / "reg"), parts=6, seed=3,
        noise=0.01,
    )
    model = LinearRegression().fit(out)  # parquet-path streaming ingest
    w = RegressionGen(6, noise=0.01).shared(3)
    np.testing.assert_allclose(model.coef_, w, rtol=0.05, atol=0.5)


def test_gen_data_distributed_kinds(tmp_path):
    import pyarrow.parquet as pq

    from benchmark.gen_data_distributed import GENERATORS, generate_partitioned

    for kind in GENERATORS:
        out = generate_partitioned(
            kind, 300, 5, str(tmp_path / kind), parts=3, seed=1
        )
        t = pq.read_table(out)
        assert t.num_rows == 300, kind


# -- every family on gen_data rows: 300 x 8, two devices --------------------

ROWS, COLS, WORKERS = 300, 8, 2


def _frame(X, y=None):
    cols = {"features": list(X)}
    if y is not None:
        cols["label"] = y
    return pd.DataFrame(cols)


def _classifier(name):
    from spark_rapids_ml_tpu import classification

    X, y = gen_data.gen_classification(ROWS, COLS, seed=0)
    est = {
        "logistic_regression": lambda: classification.LogisticRegression(
            maxIter=5, regParam=1e-4, num_workers=WORKERS),
        "random_forest_classifier":
            lambda: classification.RandomForestClassifier(
                numTrees=4, maxDepth=4, maxBins=64, seed=0,
                num_workers=WORKERS),
    }[name]()
    out = est.fit(_frame(X, y)).transform(_frame(X))
    assert {"prediction", "probability", "rawPrediction"} <= set(out.columns)
    assert set(np.unique(out["prediction"])) <= {0.0, 1.0}
    return np.stack(out["probability"].to_numpy())


def _regressor(name):
    from spark_rapids_ml_tpu import regression

    X, y = gen_data.gen_regression(ROWS, COLS, seed=0)
    est = {
        "linear_regression": lambda: regression.LinearRegression(
            regParam=1e-6, num_workers=WORKERS),
        "random_forest_regressor": lambda: regression.RandomForestRegressor(
            numTrees=4, maxDepth=4, maxBins=64, seed=0,
            num_workers=WORKERS),
    }[name]()
    out = est.fit(_frame(X, y)).transform(_frame(X))
    assert "prediction" in out.columns
    return out["prediction"].to_numpy(np.float64)[:, None]


def _clustering(name):
    from spark_rapids_ml_tpu.clustering import DBSCAN, KMeans

    X, _ = gen_data.gen_blobs(ROWS, COLS, seed=0)
    est = {
        "kmeans": lambda: KMeans(k=20, maxIter=5, seed=0,
                                 num_workers=WORKERS),
        "dbscan": lambda: DBSCAN(eps=2.0, min_samples=5,
                                 num_workers=WORKERS),
    }[name]()
    out = est.fit(_frame(X)).transform(_frame(X))
    return out["prediction"].to_numpy(np.float64)[:, None]


def _pca(name):
    from spark_rapids_ml_tpu.feature import PCA

    X, _ = gen_data.gen_low_rank_matrix(ROWS, COLS, seed=0)
    model = (PCA(k=3, num_workers=WORKERS).setInputCol("features")
             .setOutputCol("pca").fit(_frame(X)))
    return np.stack(model.transform(_frame(X))["pca"].to_numpy())


def _neighbors(name):
    from spark_rapids_ml_tpu.knn import (
        ApproximateNearestNeighbors,
        NearestNeighbors,
    )

    X, _ = gen_data.gen_blobs(ROWS, COLS, seed=0)
    est = {
        "nearest_neighbors": lambda: NearestNeighbors(
            k=16, num_workers=WORKERS),
        "approximate_nearest_neighbors": lambda: ApproximateNearestNeighbors(
            k=16, algorithm="ivfflat", algoParams={"nlist": 17, "nprobe": 4},
            num_workers=WORKERS),
    }[name]()
    _, _, knn = est.fit(_frame(X)).kneighbors(_frame(X[:50]))
    ids = np.stack(knn["indices"].to_numpy())
    assert ids.shape == (50, 16)
    assert ((ids >= 0) & (ids < ROWS)).all()
    return np.stack(knn["distances"].to_numpy())


def _umap(name):
    from spark_rapids_ml_tpu.umap import UMAP

    X, _ = gen_data.gen_blobs(ROWS, COLS, centers=10, seed=0)
    model = UMAP(n_neighbors=15, n_epochs=200, random_state=0,
                 num_workers=WORKERS).fit(_frame(X))
    return np.stack(model.transform(_frame(X))["embedding"].to_numpy())


FAMILIES = {
    "kmeans": (_clustering, (ROWS, 1)),
    "linear_regression": (_regressor, (ROWS, 1)),
    "logistic_regression": (_classifier, (ROWS, 2)),
    "pca": (_pca, (ROWS, 3)),
    "random_forest_classifier": (_classifier, (ROWS, 2)),
    "random_forest_regressor": (_regressor, (ROWS, 1)),
    "nearest_neighbors": (_neighbors, (50, 16)),
    "approximate_nearest_neighbors": (_neighbors, (50, 16)),
    "umap": (_umap, (ROWS, 2)),
    "dbscan": (_clustering, (ROWS, 1)),
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_each_family_fits_and_transforms_gen_data_rows(family):
    """One fit and one transform (kneighbors for the neighbour searches)
    per family through the public estimator on a 2-device mesh: the
    output has its shape and is finite."""
    run, shape = FAMILIES[family]
    out = run(family)
    assert out.shape == shape
    assert np.isfinite(out).all()
