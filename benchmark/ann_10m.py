#!/usr/bin/env python
#
# BASELINE-scale ANN: 10M x 128 build + search with measured recall
# (BASELINE.md names 10M x 128 for the neighbor-graph family).  Run-once
# like the rehearsal; on the chip, or — only when the caller pins
# JAX_PLATFORMS=cpu — on the CPU (hours).  Analog of the reference's ANN benchmark
# (python/benchmark/benchmark_runner.py approximate_nearest_neighbors +
# the recall-vs-sklearn evaluation of reference benchmark/test_gen_data.py).
#
#   python benchmark/ann_10m.py                      # full 10M x 128
#   ANN_ROWS=200000 python benchmark/ann_10m.py      # smoke
#
# Prints one JSON line: build sec, search qps, recall@k vs exact ground
# truth on ANN_QUERIES held-out queries, per algorithm (ivfflat, cagra).
#
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_ROWS = int(os.environ.get("ANN_ROWS", 10_000_000))
N_COLS = int(os.environ.get("ANN_COLS", 128))
N_QUERIES = int(os.environ.get("ANN_QUERIES", 10_000))
K = int(os.environ.get("ANN_K", 10))
ALGOS = os.environ.get("ANN_ALGOS", "ivfflat,cagra").split(",")


def main() -> None:
    import numpy as np

    from benchmark.base import require_tpu_unless_cpu_pinned
    from spark_rapids_ml_tpu._jax_env import configure_compile_cache

    configure_compile_cache()
    out: dict = {
        "metric": f"ann_{N_ROWS}x{N_COLS}",
        "unit": "recall@k / qps",
        "k": K,
        "n_queries": N_QUERIES,
        "platform": require_tpu_unless_cpu_pinned("ann_10m"),
    }
    from spark_rapids_ml_tpu.utils import host_load_metadata

    out.update(host_load_metadata())

    # clustered data (mixture of gaussians) so approximate recall is a
    # meaningful measure — iid-uniform makes every index look equally bad
    rng = np.random.default_rng(3)
    n_centers = 1000
    centers = rng.standard_normal((n_centers, N_COLS), dtype=np.float32) * 4.0
    t0 = time.time()
    X = np.empty((N_ROWS, N_COLS), np.float32)
    slab = 1_000_000
    for at in range(0, N_ROWS, slab):
        m = min(slab, N_ROWS - at)
        cid = rng.integers(0, n_centers, size=m)
        X[at:at + m] = (
            centers[cid]
            + rng.standard_normal((m, N_COLS), dtype=np.float32)
        )
    Q = (
        centers[rng.integers(0, n_centers, size=N_QUERIES)]
        + rng.standard_normal((N_QUERIES, N_COLS), dtype=np.float32)
    )
    out["gen_sec"] = round(time.time() - t0, 1)

    # exact ground truth from the framework's own exact kNN (blocked,
    # chip-tiled; the sklearn cross-check lives in tests/, not here —
    # at 10M x 128 sklearn brute would take far longer than the index).
    # ANN_GT_CACHE persists it so per-algo runs in SEPARATE processes
    # (one crashed build must not poison the next algo's backend — the
    # bench isolation lesson) don't re-pay the exact pass.  The data is
    # seed-deterministic, so a cache keyed on the config is exact.
    gt_cache = os.environ.get("ANN_GT_CACHE", "")
    if gt_cache and not gt_cache.endswith(".npz"):
        gt_cache += ".npz"  # np.savez appends it; keep load/save agreed
    cfg = np.asarray([N_ROWS, N_COLS, N_QUERIES, K])
    gt_idx = None
    if gt_cache and os.path.exists(gt_cache):
        try:
            with np.load(gt_cache) as z:
                if np.array_equal(z["cfg"], cfg):
                    gt_idx = z["gt"]
                    out["exact_ground_truth_cached"] = True
        except Exception:
            gt_idx = None  # truncated/foreign cache: recompute
    if gt_idx is None:
        from spark_rapids_ml_tpu.knn import NearestNeighbors

        t0 = time.perf_counter()
        exact = NearestNeighbors(k=K).fit(X)
        _, gt_idx = exact._search(Q, K)
        gt_idx = np.asarray(gt_idx)
        out["exact_ground_truth_sec"] = round(time.perf_counter() - t0, 1)
        del exact
        if gt_cache:
            tmp = gt_cache + ".tmp.npz"
            np.savez(tmp, cfg=cfg, gt=gt_idx)
            os.replace(tmp, gt_cache)  # a killed run can't truncate it
    gt_sets = [set(row) for row in np.asarray(gt_idx)]

    from spark_rapids_ml_tpu.knn import ApproximateNearestNeighbors

    for algo in ALGOS:
        algo = algo.strip()
        try:
            params = (
                {"nlist": min(1024, max(8, N_ROWS // 256)), "nprobe": 64}
                if algo.startswith("ivf")
                else {"graph_degree": 32, "nn_descent_niter": 8}
            )
            t0 = time.perf_counter()
            model = ApproximateNearestNeighbors(
                k=K, algorithm=algo, algoParams=params
            ).fit(X)
            build = time.perf_counter() - t0
            t0 = time.perf_counter()
            _, idx = model._search(Q, K)
            search = time.perf_counter() - t0
            idx = np.asarray(idx)
            recall = float(
                np.mean(
                    [len(gt_sets[i] & set(idx[i])) / K
                     for i in range(N_QUERIES)]
                )
            )
            out[f"{algo}_build_sec"] = round(build, 1)
            out[f"{algo}_search_qps"] = round(N_QUERIES / search, 1)
            out[f"{algo}_recall_at_{K}"] = round(recall, 4)
            print(
                f"{algo}: build {build:.1f}s, "
                f"{N_QUERIES / search:,.0f} qps, recall {recall:.4f}",
                file=sys.stderr, flush=True,
            )
            del model
        except Exception as e:  # record, keep going — run-once artifact
            out[f"{algo}_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        out["host_loadavg_end"] = [round(v, 2) for v in os.getloadavg()]
    except OSError:
        pass
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
