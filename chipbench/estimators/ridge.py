#
# chipbench/estimators/ridge.py: LinearRegression with an L2 penalty.
#
# Objective (Spark's): 1/(2n) sum_i (x_i.beta + b - y_i)^2
# + regParam (1 - elasticNetParam)/2 |beta|^2, intercept unpenalised, no
# standardisation.  With elasticNetParam = 0 it has a closed form over the
# centred normal equations, which is what the reference project's RidgeMG
# solves; maxIter and tol have no part in it.
#
from __future__ import annotations

import numpy as np

LABELS = "linear"  # chipbench/datagen.py label model
PROGRAMS = {"gram": ("linreg_sufficient_stats",)}


def build(params: dict, chips: int):
    from spark_rapids_ml_tpu.regression import LinearRegression

    return LinearRegression(num_workers=chips, **params)


def answer(model) -> dict:
    return {"theta": np.append(
        np.asarray(model.coefficients, np.float64).ravel(), float(model.intercept))}


def work(rows: int, cols: int, chips: int, params: dict) -> dict:
    """Least work per chip: the Gram and X^T y (2 rows cols^2 + 2 rows cols
    FLOP, one read of the rows), then one residual pass (a matvec, one more
    read) for the summary the fit returns."""
    share = rows / chips
    read = share * cols * 4.0
    gram = {"flops": 2.0 * share * cols * cols + 2.0 * share * cols, "bytes": read}
    resid = {"flops": 2.0 * share * cols, "bytes": read}
    return {
        "kernels": {"gram": gram},
        "fit": [dict(gram, count=1), dict(resid, count=1)],
    }


def block_stats(lowered: bool):
    """(X_block, y_block) -> the block's Gram, X^T y, column sums and label
    sum: f32 products at `highest`, or, when `lowered`, features and labels
    rounded to bfloat16 and the products accumulated in f32 (one MXU pass)."""
    import jax
    import jax.numpy as jnp

    def stats(Xb, yb):
        if lowered:
            Xq, yq = Xb.astype(jnp.bfloat16), yb.astype(jnp.bfloat16)
            gram = jnp.matmul(Xq.T, Xq, preferred_element_type=jnp.float32)
            sxy = jnp.matmul(yq, Xq, preferred_element_type=jnp.float32)
        else:
            hi = jax.lax.Precision.HIGHEST
            gram = jnp.matmul(Xb.T, Xb, precision=hi)
            sxy = jnp.matmul(yb, Xb, precision=hi)
        return gram, sxy, Xb.sum(axis=0), yb.sum()

    return stats


def reference(X, y, params: dict, lowered: bool = False) -> dict:
    """Normal equations from per-block f32 `highest` Grams added in float64
    on the host, solved in float64.  `lowered`: the Gram and cross products
    in bfloat16, the precision below the float32 the configuration states."""
    from chipbench import blocks

    n, d = X.shape
    block_rows = blocks.block_rows_of(X)
    call = blocks.block_caller(block_stats(lowered), X.sharding.mesh, block_rows, n_args=0)
    gram, sxy, s1, sy = blocks.sum_blocks(call, X, y, block_rows)
    mean, ymean = s1 / n, sy / n
    gram -= n * np.outer(mean, mean)
    sxy -= n * mean * ymean
    l2 = float(params["regParam"]) * (1.0 - float(params.get("elasticNetParam", 0.0)))
    beta = np.linalg.solve(gram + n * l2 * np.eye(d), sxy)
    return {"theta": np.append(beta, ymean - mean @ beta)}


def compare(ans: dict, ref: dict) -> dict:
    gap = np.linalg.norm(ans["theta"] - ref["theta"]) / np.linalg.norm(ref["theta"])
    return {"coef_gap": float(gap)}
