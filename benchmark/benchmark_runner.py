#
# Benchmark CLI — the analog of reference python/benchmark/
# benchmark_runner.py (registry of 10 benchmarks, benchmark_runner.py:36-49)
# + the per-algo bench_*.py modules: each benchmark times fit (and
# transform where applicable) on the TPU backend (`--mode tpu`) or the
# sklearn CPU baseline (`--mode cpu`) and reports a quality score
# (inertia / accuracy / r2 / recall-vs-exact / trustworthiness), appending
# CSV rows like the reference's report files.
#
# Usage:
#   python -m benchmark.benchmark_runner kmeans --num_rows 100000 \
#       --num_cols 64 --mode tpu --num_workers 8 --report report.csv
#
from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

if __package__:
    from . import gen_data
    from .base import Report, with_benchmark
else:  # direct-script invocation (README: python benchmark/benchmark_runner.py)
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    from benchmark import gen_data
    from benchmark.base import Report, with_benchmark


def _tpu_ds(X, y=None, num_workers=None, label_dtype=None):
    import jax

    from spark_rapids_ml_tpu import DeviceDataset

    if jax.process_count() > 1:
        # pod runs (benchmark/pod/launch.py) generate the same global
        # dataset in every process; each stages ONLY its row slice — the
        # per-partition loading contract of RowStager multi-process mode
        if y is not None:
            X, y = _proc_slice(X, y)
        else:
            X = _proc_slice(X)
    return DeviceDataset.from_host(
        X, y=y, num_workers=num_workers, label_dtype=label_dtype
    )


def _proc_slice(X, y=None):
    """This process's contiguous row slice in a pod run (identity when
    single-process) — for workloads that fit host arrays directly."""
    import jax

    if jax.process_count() == 1:
        return (X, y) if y is not None else X
    n = X.shape[0]
    pid, n_proc = jax.process_index(), jax.process_count()
    base, rem = divmod(n, n_proc)
    lo = pid * base + min(pid, rem)
    hi = lo + base + (1 if pid < rem else 0)
    if y is not None:
        return X[lo:hi], y[lo:hi]
    return X[lo:hi]


def bench_pca(args, report: Report) -> None:
    X, _ = gen_data.gen_low_rank_matrix(args.num_rows, args.num_cols,
                                        seed=args.seed)
    k = args.k or 8
    if args.mode == "cpu":
        from sklearn.decomposition import PCA as SkPCA

        est = SkPCA(n_components=k)
        _, fit_s = with_benchmark("cpu fit", lambda: est.fit(X))
        _, tr_s = with_benchmark("cpu transform", lambda: est.transform(X))
        score = float(est.explained_variance_ratio_.sum())
    else:
        from spark_rapids_ml_tpu.feature import PCA

        ds = _tpu_ds(X, num_workers=args.num_workers)
        PCA(k=k).fit(ds)  # compile warmup
        model, fit_s = with_benchmark("tpu fit", lambda: PCA(k=k).fit(ds))
        _, tr_s = with_benchmark(
            "tpu transform", lambda: model._transform_array(X[:100_000])
        )
        score = float(np.sum(model.explained_variance_ratio_))
    report.add(benchmark="pca", mode=args.mode, num_rows=args.num_rows,
               num_cols=args.num_cols, fit_sec=fit_s, transform_sec=tr_s,
               score_name="explained_variance_ratio", score=score)


def bench_kmeans(args, report: Report) -> None:
    X, _ = gen_data.gen_blobs(args.num_rows, args.num_cols,
                              centers=args.k or 20, seed=args.seed)
    k = args.k or 20
    if args.mode == "cpu":
        from sklearn.cluster import KMeans as SkKMeans

        est = SkKMeans(n_clusters=k, n_init=1, max_iter=args.max_iter,
                       random_state=args.seed)
        _, fit_s = with_benchmark("cpu fit", lambda: est.fit(X))
        _, tr_s = with_benchmark(
            "cpu transform", lambda: est.predict(X[:100_000])
        )
        report.add(benchmark="kmeans", mode="cpu", num_rows=args.num_rows,
                   num_cols=args.num_cols, fit_sec=fit_s, transform_sec=tr_s,
                   score_name="inertia", score=float(est.inertia_))
        return
    from spark_rapids_ml_tpu.clustering import KMeans

    ds = _tpu_ds(X, num_workers=args.num_workers)

    def fit():
        return KMeans(k=k, maxIter=args.max_iter, seed=args.seed).fit(ds)

    fit()  # warmup compile
    model, fit_s = with_benchmark("tpu fit", fit)
    _, tr_s = with_benchmark(
        "tpu transform", lambda: model._transform_array(X[:100_000])
    )
    report.add(benchmark="kmeans", mode="tpu", num_rows=args.num_rows,
               num_cols=args.num_cols, fit_sec=fit_s, transform_sec=tr_s,
               score_name="inertia", score=float(model.inertia_))


def bench_dbscan(args, report: Report) -> None:
    X, _ = gen_data.gen_blobs(args.num_rows, args.num_cols, centers=20,
                              seed=args.seed)
    eps, min_samples = 2.0, 5
    if args.mode == "cpu":
        from sklearn.cluster import DBSCAN as SkDBSCAN

        est = SkDBSCAN(eps=eps, min_samples=min_samples)
        labels, fit_s = with_benchmark("cpu fit", lambda: est.fit_predict(X))
    else:
        from spark_rapids_ml_tpu.clustering import DBSCAN

        model = DBSCAN(eps=eps, min_samples=min_samples,
                       num_workers=args.num_workers).fit(X)
        model._transform_array(X)  # warmup compile
        labels, fit_s = with_benchmark(
            "tpu fit_predict",
            lambda: model._transform_array(X)[
                model.getOrDefault("predictionCol")],
        )
    n_clusters = int(np.max(labels)) + 1
    report.add(benchmark="dbscan", mode=args.mode, num_rows=args.num_rows,
               num_cols=args.num_cols, fit_sec=fit_s, transform_sec=0.0,
               score_name="n_clusters", score=n_clusters)


def bench_linear_regression(args, report: Report) -> None:
    X, y = gen_data.gen_regression(args.num_rows, args.num_cols,
                                   seed=args.seed)
    if args.mode == "cpu":
        from sklearn.linear_model import Ridge

        est = Ridge(alpha=1.0)
        _, fit_s = with_benchmark("cpu fit", lambda: est.fit(X, y))
        score = float(est.score(X, y))
    else:
        from spark_rapids_ml_tpu.regression import LinearRegression

        ds = _tpu_ds(X, y=y, num_workers=args.num_workers)

        def fit():
            return LinearRegression(regParam=1e-6).fit(ds)

        fit()
        model, fit_s = with_benchmark("tpu fit", fit)
        preds = model._transform_array(X[:200_000])[
            model.getOrDefault("predictionCol")]
        from sklearn.metrics import r2_score

        score = float(r2_score(y[:200_000], preds))
    report.add(benchmark="linear_regression", mode=args.mode,
               num_rows=args.num_rows, num_cols=args.num_cols, fit_sec=fit_s,
               transform_sec=0.0, score_name="r2", score=score)


def bench_logistic_regression(args, report: Report) -> None:
    X, y = gen_data.gen_classification(args.num_rows, args.num_cols,
                                       n_classes=args.n_classes,
                                       seed=args.seed)
    if args.mode == "cpu":
        from sklearn.linear_model import LogisticRegression as SkLR

        est = SkLR(max_iter=args.max_iter)
        _, fit_s = with_benchmark("cpu fit", lambda: est.fit(X, y))
        score = float(est.score(X, y))
    else:
        from spark_rapids_ml_tpu.classification import LogisticRegression

        ds = _tpu_ds(X, y=y, num_workers=args.num_workers,
                     label_dtype=np.float32)

        def fit():
            return LogisticRegression(maxIter=args.max_iter,
                                      regParam=1e-4).fit(ds)

        fit()
        model, fit_s = with_benchmark("tpu fit", fit)
        preds = model._transform_array(X[:200_000])[
            model.getOrDefault("predictionCol")]
        score = float((preds == y[:200_000]).mean())
    report.add(benchmark="logistic_regression", mode=args.mode,
               num_rows=args.num_rows, num_cols=args.num_cols, fit_sec=fit_s,
               transform_sec=0.0, score_name="accuracy", score=score)


def _bench_rf(args, report: Report, classification: bool) -> None:
    if classification:
        X, y = gen_data.gen_classification(args.num_rows, args.num_cols,
                                           n_classes=args.n_classes,
                                           seed=args.seed)
    else:
        X, y = gen_data.gen_regression(args.num_rows, args.num_cols,
                                       seed=args.seed)
    name = "random_forest_" + ("classifier" if classification else "regressor")
    n_trees, depth = args.num_trees, args.max_depth
    if args.mode == "cpu":
        from sklearn.ensemble import (
            RandomForestClassifier as SkC,
            RandomForestRegressor as SkR,
        )

        est = (SkC if classification else SkR)(
            n_estimators=n_trees, max_depth=depth, random_state=args.seed,
            n_jobs=-1,
        )
        _, fit_s = with_benchmark("cpu fit", lambda: est.fit(X, y))
        score = float(est.score(X, y))
    else:
        from spark_rapids_ml_tpu.classification import RandomForestClassifier
        from spark_rapids_ml_tpu.regression import RandomForestRegressor

        cls = RandomForestClassifier if classification else RandomForestRegressor
        ds = _tpu_ds(X, y=y, num_workers=args.num_workers)

        def fit():
            return cls(numTrees=n_trees, maxDepth=depth, maxBins=64,
                       seed=args.seed).fit(ds)

        fit()
        model, fit_s = with_benchmark("tpu fit", fit)
        preds = model._transform_array(X[:200_000])[
            model.getOrDefault("predictionCol")]
        if classification:
            score = float((preds == y[:200_000]).mean())
        else:
            from sklearn.metrics import r2_score

            score = float(r2_score(y[:200_000], preds))
    report.add(benchmark=name, mode=args.mode, num_rows=args.num_rows,
               num_cols=args.num_cols, fit_sec=fit_s, transform_sec=0.0,
               score_name="accuracy" if classification else "r2", score=score,
               extra={"num_trees": n_trees, "max_depth": depth})


def bench_random_forest_classifier(args, report):
    _bench_rf(args, report, True)


def bench_random_forest_regressor(args, report):
    _bench_rf(args, report, False)


def bench_nearest_neighbors(args, report: Report) -> None:
    X, _ = gen_data.gen_blobs(args.num_rows, args.num_cols, centers=20,
                              seed=args.seed)
    n_q = min(args.num_rows, 10_000)
    k = args.k or 16
    # column semantics match ANN below: fit_sec = index/fit time,
    # transform_sec = search time
    if args.mode == "cpu":
        from sklearn.neighbors import NearestNeighbors as SkNN

        est, fit_s = with_benchmark(
            "cpu fit", lambda: SkNN(n_neighbors=k, algorithm="brute").fit(X)
        )
        _, search_s = with_benchmark(
            "cpu kneighbors", lambda: est.kneighbors(X[:n_q])
        )
    else:
        from spark_rapids_ml_tpu.knn import NearestNeighbors

        model, fit_s = with_benchmark(
            "tpu fit",
            lambda: NearestNeighbors(
                k=k, num_workers=args.num_workers
            ).fit(_proc_slice(X)),
        )
        model._search(X[:n_q], k)  # warmup compile
        _, search_s = with_benchmark(
            "tpu kneighbors", lambda: model._search(X[:n_q], k)
        )
    report.add(benchmark="nearest_neighbors", mode=args.mode,
               num_rows=args.num_rows, num_cols=args.num_cols, fit_sec=fit_s,
               transform_sec=search_s, score_name="recall", score=1.0,
               extra={"k": k, "num_queries": n_q})


def bench_approximate_nearest_neighbors(args, report: Report) -> None:
    X, _ = gen_data.gen_blobs(args.num_rows, args.num_cols, centers=100,
                              seed=args.seed)
    n_q = min(args.num_rows, 5_000)
    k = args.k or 16
    if args.mode == "cpu":
        from sklearn.neighbors import NearestNeighbors as SkNN

        est = SkNN(n_neighbors=k, algorithm="brute").fit(X)
        _, fit_s = with_benchmark(
            "cpu kneighbors", lambda: est.kneighbors(X[:n_q])
        )
        report.add(benchmark="approximate_nearest_neighbors", mode="cpu",
                   num_rows=args.num_rows, num_cols=args.num_cols,
                   fit_sec=fit_s, transform_sec=0.0, score_name="recall",
                   score=1.0)
        return
    from spark_rapids_ml_tpu.knn import ApproximateNearestNeighbors

    if args.algorithm == "cagra":
        algo_params = {"graph_degree": 32}
    else:
        nlist = max(16, int(np.sqrt(args.num_rows)))
        algo_params = {"nlist": nlist, "nprobe": max(1, nlist // 16)}
    extra_cfg = {"algorithm": args.algorithm, **algo_params}
    model, build_s = with_benchmark(
        "tpu index build",
        lambda: ApproximateNearestNeighbors(
            k=k, algorithm=args.algorithm, algoParams=algo_params,
            num_workers=args.num_workers,
        ).fit(_proc_slice(X)),
    )
    model._search(X[:n_q], k)  # warmup compile
    (dist, pos), search_s = with_benchmark(
        "tpu search", lambda: model._search(X[:n_q], k)
    )
    # recall vs exact on a query subsample (reference utils_knn.py)
    from sklearn.neighbors import NearestNeighbors as SkNN

    n_chk = min(n_q, 500)
    _, want = SkNN(n_neighbors=k, algorithm="brute").fit(X).kneighbors(X[:n_chk])
    hits = sum(
        len(set(pos[i].tolist()) & set(want[i].tolist())) for i in range(n_chk)
    )
    recall = hits / (n_chk * k)
    report.add(benchmark="approximate_nearest_neighbors", mode="tpu",
               num_rows=args.num_rows, num_cols=args.num_cols,
               fit_sec=build_s, transform_sec=search_s, score_name="recall",
               score=recall, extra={**extra_cfg, "k": k})


def bench_umap(args, report: Report) -> None:
    n = min(args.num_rows, 100_000)  # single-worker fit strategy
    X, y = gen_data.gen_blobs(n, args.num_cols, centers=10, seed=args.seed)
    if args.mode == "cpu":
        report.add(benchmark="umap", mode="cpu", num_rows=n,
                   num_cols=args.num_cols, fit_sec=0.0, transform_sec=0.0,
                   score_name="skipped (no umap-learn in image)", score=0.0)
        return
    from spark_rapids_ml_tpu.umap import UMAP

    model, fit_s = with_benchmark(
        "tpu fit",
        lambda: UMAP(
            n_neighbors=15, n_epochs=200, random_state=args.seed
        ).fit(_proc_slice(X)),
    )
    _, tr_s = with_benchmark(
        "tpu transform", lambda: model._transform_array(X[:10_000])
    )
    from sklearn.manifold import trustworthiness

    sub = np.random.default_rng(0).choice(n, size=min(n, 2000), replace=False)
    score = float(trustworthiness(X[sub], model.embedding_[sub], n_neighbors=15))
    report.add(benchmark="umap", mode="tpu", num_rows=n,
               num_cols=args.num_cols, fit_sec=fit_s, transform_sec=tr_s,
               score_name="trustworthiness", score=score)


BENCHMARKS: Dict[str, Callable[[Any, Report], None]] = {
    "pca": bench_pca,
    "kmeans": bench_kmeans,
    "dbscan": bench_dbscan,
    "linear_regression": bench_linear_regression,
    "logistic_regression": bench_logistic_regression,
    "random_forest_classifier": bench_random_forest_classifier,
    "random_forest_regressor": bench_random_forest_regressor,
    "nearest_neighbors": bench_nearest_neighbors,
    "approximate_nearest_neighbors": bench_approximate_nearest_neighbors,
    "umap": bench_umap,
}


def main(argv: Optional[list] = None) -> None:
    from spark_rapids_ml_tpu._jax_env import configure_compile_cache

    configure_compile_cache()
    p = argparse.ArgumentParser(
        description="spark_rapids_ml_tpu benchmark runner "
        "(reference benchmark_runner.py registry)"
    )
    p.add_argument("benchmark", choices=sorted(BENCHMARKS) + ["all"])
    p.add_argument("--num_rows", type=int, default=100_000)
    p.add_argument("--num_cols", type=int, default=64)
    p.add_argument("--mode", choices=["tpu", "cpu"], default="tpu")
    p.add_argument("--num_workers", type=int, default=None)
    p.add_argument("--k", type=int, default=None,
                   help="clusters / components / neighbors")
    p.add_argument("--max_iter", type=int, default=30)
    p.add_argument("--num_trees", type=int, default=32)
    p.add_argument("--max_depth", type=int, default=10)
    p.add_argument("--n_classes", type=int, default=2)
    p.add_argument("--algorithm", choices=["ivfflat", "ivfpq", "cagra"],
                   default="ivfflat",
                   help="approximate_nearest_neighbors index type")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--report", default=None, help="CSV report path (append)")
    args = p.parse_args(argv)

    report = Report(args.report)
    names = sorted(BENCHMARKS) if args.benchmark == "all" else [args.benchmark]
    failed = []
    try:
        for name in names:
            print(f"=== {name} ({args.mode}, {args.num_rows}x{args.num_cols}) ===")
            t0 = time.perf_counter()
            try:
                BENCHMARKS[name](args, report)
            except Exception as e:  # keep collected rows on partial failure
                if args.benchmark != "all":
                    raise
                failed.append(name)
                print(f"!!! {name} failed: {e}")
            print(f"=== {name} done in {time.perf_counter() - t0:.1f}s ===")
    finally:
        report.write()
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
