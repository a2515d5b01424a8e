#
# Synthetic dataset generation — the analog of reference
# python/benchmark/gen_data.py (sklearn-based Blobs/LowRankMatrix/
# Regression/Classification/Default generators, gen_data.py:49-471).
# Generates parquet with either an array-valued "features" column or
# per-feature scalar columns (the two input layouts the estimators take).
#
from __future__ import annotations

import argparse
import os
from typing import Optional

import numpy as np


def gen_blobs(n_rows: int, n_cols: int, *, centers: int = 20, cluster_std: float = 1.0,
              seed: int = 0):
    from sklearn.datasets import make_blobs

    X, y = make_blobs(
        n_samples=n_rows, n_features=n_cols, centers=centers,
        cluster_std=cluster_std, random_state=seed,
    )
    return X.astype(np.float32), y.astype(np.float64)


def gen_low_rank_matrix(n_rows: int, n_cols: int, *, effective_rank: Optional[int] = None,
                        seed: int = 0):
    from sklearn.datasets import make_low_rank_matrix

    X = make_low_rank_matrix(
        n_samples=n_rows, n_features=n_cols,
        effective_rank=effective_rank or max(1, n_cols // 10),
        random_state=seed,
    )
    return X.astype(np.float32), None


def gen_regression(n_rows: int, n_cols: int, *, n_informative: Optional[int] = None,
                   noise: float = 1.0, seed: int = 0):
    from sklearn.datasets import make_regression

    X, y = make_regression(
        n_samples=n_rows, n_features=n_cols,
        n_informative=n_informative or max(1, n_cols // 2),
        noise=noise, random_state=seed,
    )
    return X.astype(np.float32), y.astype(np.float64)


def gen_classification(n_rows: int, n_cols: int, *, n_classes: int = 2,
                       n_informative: Optional[int] = None, seed: int = 0):
    from sklearn.datasets import make_classification

    ninf = n_informative or max(int(np.ceil(np.log2(n_classes))) + 2, n_cols // 2)
    ninf = min(ninf, n_cols)
    # sklearn requires n_classes * n_clusters_per_class <= 2**n_informative
    clusters_per_class = 2 if n_classes * 2 <= 2**ninf else 1
    if n_classes > 2**ninf:
        raise ValueError(
            f"n_classes={n_classes} needs more informative features than "
            f"num_cols={n_cols} allows (n_classes <= 2**{ninf})"
        )
    X, y = make_classification(
        n_samples=n_rows, n_features=n_cols, n_informative=ninf,
        n_redundant=0, n_classes=n_classes,
        n_clusters_per_class=clusters_per_class, random_state=seed,
    )
    return X.astype(np.float32), y.astype(np.float64)


def gen_default(n_rows: int, n_cols: int, *, seed: int = 0):
    """Uniform random (reference DefaultDataGen)."""
    rng = np.random.default_rng(seed)
    return rng.random((n_rows, n_cols), dtype=np.float32), None


def classification_slab(n_cols: int, seed: int, index: int, rows: int):
    """Slab `index` of the seeded dense binary-classification stream:
    standard-normal f32 features, label = [x . true_w > 0].  Each slab
    draws from its own child of `seed`, so any slab regenerates alone
    (a caller checking a transform re-derives its rows without re-reading
    the file) and slabs generate in parallel."""
    true_w = np.random.default_rng(seed).standard_normal(n_cols).astype(
        np.float32
    )
    rng = np.random.default_rng([seed, 1 + index])
    X = rng.standard_normal((rows, n_cols), dtype=np.float32)
    return X, (X @ true_w > 0).astype(np.float64)


def write_classification_slabs(
    path: str, n_rows: int, n_cols: int, *, seed: int = 11,
    slab_rows: int = 50_000, workers: int = 1, **writer_kwargs,
) -> None:
    """Write `classification_slab`s to ONE parquet file, a row group per
    slab, holding at most `workers` slabs at a time: the way the refconfig
    1M x 3000 file (12 GB) is made on a host that could not hold it twice.
    `features` is a fixed-size-list column, `label` float64."""
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    import pyarrow as pa
    import pyarrow.parquet as pq

    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    sizes = [
        min(slab_rows, n_rows - at) for at in range(0, n_rows, slab_rows)
    ]
    workers = max(1, workers)
    writer = None
    futures: deque = deque()
    nxt = 0
    with ThreadPoolExecutor(workers) as pool:
        try:
            while nxt < len(sizes) or futures:
                while nxt < len(sizes) and len(futures) < workers:
                    futures.append(pool.submit(
                        classification_slab, n_cols, seed, nxt, sizes[nxt]
                    ))
                    nxt += 1
                Xs, ys = futures.popleft().result()
                t = pa.table({
                    "features": pa.FixedSizeListArray.from_arrays(
                        pa.array(Xs.reshape(-1)), n_cols
                    ),
                    "label": pa.array(ys),
                })
                if writer is None:
                    writer = pq.ParquetWriter(path, t.schema, **writer_kwargs)
                writer.write_table(t)
                del Xs, ys, t
        finally:
            if writer is not None:
                writer.close()


GENERATORS = {
    "blobs": gen_blobs,
    "low_rank_matrix": gen_low_rank_matrix,
    "regression": gen_regression,
    "classification": gen_classification,
    "default": gen_default,
}


def write_parquet(X: np.ndarray, y: Optional[np.ndarray], path: str,
                  feature_layout: str = "array") -> None:
    import pandas as pd

    if feature_layout == "array":
        df = pd.DataFrame({"features": list(X)})
    else:  # scalar columns (HasFeaturesCols layout)
        df = pd.DataFrame(X, columns=[f"c{i}" for i in range(X.shape[1])])
    if y is not None:
        df["label"] = y
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    df.to_parquet(path)


def main() -> None:
    p = argparse.ArgumentParser(description="Generate synthetic benchmark data")
    p.add_argument("kind", choices=sorted(GENERATORS))
    p.add_argument("--num_rows", type=int, default=5000)
    p.add_argument("--num_cols", type=int, default=3000)
    p.add_argument("--output_dir", required=True)
    p.add_argument("--feature_layout", choices=["array", "scalar"], default="array")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--n_classes", type=int, default=2)
    args = p.parse_args()

    kwargs = {"seed": args.seed}
    if args.kind == "classification":
        kwargs["n_classes"] = args.n_classes
    X, y = GENERATORS[args.kind](args.num_rows, args.num_cols, **kwargs)
    out = os.path.join(args.output_dir, f"{args.kind}.parquet")
    write_parquet(X, y, out, args.feature_layout)
    print(f"wrote {args.num_rows}x{args.num_cols} {args.kind} -> {out}")


if __name__ == "__main__":
    main()
