#
# chipbench/estimators/kmeans.py: KMeans, Lloyd's algorithm from a random
# start, as the reference project's kmeans benchmark row runs it
# (k=1000, tol=1e-20, initMode=random; cuML's KMeansMG computes in fp32).
#
# One iteration: every row goes to its nearest centre (squared distance by
# |x|^2 - 2 x.c + |c|^2), every centre becomes the weighted mean of its
# rows, a centre with no row keeps its place.  Stopping rule (Spark's): an
# iteration after which every centre has moved less than `tol` is the last;
# `maxIter` bounds them.  The fit returns the centres, the cost under the
# FINAL centres (one more assignment pass) and the count of iterations.
#
# Initial centres, the rule `KMeans`' docstring states for initMode=random,
# re-derived here with jax.random and numpy alone: with unit weights the
# rows rank 0..n-1 in dataset order; g = jax.random.gumbel(PRNGKey(seed),
# (n,), float32); centre i is the row holding the i-th largest g, ties to
# the lower rank.
#
from __future__ import annotations

import numpy as np

# XLA module names of the programs a Lloyd step runs in (the host-dispatched
# block programs and the centre update; the fused while_loop program), and of
# those one host-dispatched iteration waits for
PROGRAMS = {
    "lloyd_step": ("_lloyd_block_step", "_lloyd_block_cost",
                   "_lloyd_center_update", "kmeans_fit"),
    "lloyd_iter": ("_lloyd_block_step", "_lloyd_center_update"),
}


def build(params: dict, chips: int):
    """The estimator.  Until the model is made, its fit kernel's attributes
    carry the fitted numbers as ONE vector under `coef_`, the cost first and
    then the centres in index order: chipbench/tests' fault test alters
    `attrs["coef_"]` of whatever family it is given, its first number by 1 %
    (PERF.md §7: an adapter should name its state, then this goes).  For
    KMeans that number has to be the cost: 1 % of one coordinate of one
    centre is 8e-5 of the centres' norm, less than the rows a fit's float32
    rounding tips move them at 1M rows (1.4e-4 to 2.9e-4), so `centre_gap`
    cannot hold it, and says so (PERF.md §4).  One 12 MB copy a fit (~1 ms
    of 1.9 s), nothing in the timed kernels."""
    from spark_rapids_ml_tpu.clustering import KMeans

    est = KMeans(num_workers=chips, **params)
    fit_array, create_model = est._fit_array, est._create_model

    def fit_array_as_coef(fit_input):
        attrs = fit_array(fit_input)
        centres = attrs.pop("cluster_centers_")
        attrs["coef_"] = np.concatenate(
            [np.asarray([attrs.pop("inertia_")], centres.dtype), centres.ravel()])
        attrs["k"] = len(centres)
        return attrs

    def create_model_from_coef(attrs):
        state = attrs.pop("coef_")
        attrs["inertia_"] = float(state[0])
        attrs["cluster_centers_"] = state[1:].reshape(attrs.pop("k"), -1)
        return create_model(attrs)

    est._fit_array, est._create_model = fit_array_as_coef, create_model_from_coef
    return est


def answer(model) -> dict:
    """What a fit returned, as plain host numbers: nothing here costs a
    pass over the rows."""
    return {
        "centres": np.asarray(model.cluster_centers_, np.float64),
        "cost": float(model.summary.trainingCost),
        "n_iter": int(model.summary.numIter),
    }


def work(rows: int, cols: int, chips: int, params: dict) -> dict:
    """Least work per chip, from the shapes alone.  A Lloyd step needs the
    x.c products of the assignment (2 rows cols k FLOP) and one add per
    feature for the cluster sums (rows cols FLOP: the sums are a scatter,
    whatever the program multiplies), over one read of the rows; the final
    cost is one more assignment.  A fit is maxIter steps and that pass:
    exact where every fit runs every iteration, which the configuration's
    cut of maxIter is for."""
    share, k = rows / chips, int(params["k"])
    read = share * cols * 4.0
    assign = {"flops": 2.0 * share * cols * k, "bytes": read}
    step = {"flops": assign["flops"] + share * cols, "bytes": read}
    return {
        "kernels": {"lloyd_step": step, "lloyd_assign": assign},
        "fit": [dict(step, count=int(params["maxIter"])), dict(assign, count=1)],
    }


def _nearest(Xb, C, lowered: bool):
    """(label, squared distance) of each row of the block to its nearest
    centre.  f32 products at `highest`, or, when `lowered`, features and
    centres rounded to bfloat16 and the products accumulated in f32 (one
    MXU pass)."""
    import jax
    import jax.numpy as jnp

    if lowered:
        xc = jnp.matmul(Xb.astype(jnp.bfloat16), C.astype(jnp.bfloat16).T,
                        preferred_element_type=jnp.float32)
    else:
        xc = jnp.matmul(Xb, C.T, precision=jax.lax.Precision.HIGHEST)
    d2 = (Xb * Xb).sum(axis=1, keepdims=True) - 2.0 * xc + (C * C).sum(axis=1)
    return jnp.argmin(d2, axis=1), jnp.maximum(jnp.min(d2, axis=1), 0.0)


def block_step(lowered: bool):
    """(X_block, labels_block, C (k,d) f32) -> the block's cluster sums
    (k,d), counts (k,) and cost.  The sums are a one-hot product: at
    `highest`, or, when `lowered`, with the features rounded to bfloat16
    (the one-hot is exact there)."""
    import jax
    import jax.numpy as jnp

    def step(Xb, yb, C):
        label, d2 = _nearest(Xb, C, lowered)
        if lowered:
            onehot = jax.nn.one_hot(label, C.shape[0], dtype=jnp.bfloat16)
            sums = jnp.matmul(onehot.T, Xb.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        else:
            onehot = jax.nn.one_hot(label, C.shape[0], dtype=jnp.float32)
            sums = jnp.matmul(onehot.T, Xb, precision=jax.lax.Precision.HIGHEST)
        return sums, onehot.astype(jnp.float32).sum(axis=0), d2.sum()

    return step


def block_cost(lowered: bool):
    return lambda Xb, yb, C: (_nearest(Xb, C, lowered)[1].sum(),)


def initial_rows(seed: int, n: int, k: int) -> np.ndarray:
    """Dataset positions of the k initial centres, in centre order."""
    import jax
    import jax.numpy as jnp

    g = np.asarray(jax.random.gumbel(jax.random.PRNGKey(seed), (n,), jnp.float32))
    return np.argsort(-g, kind="stable")[:k]


def _rows_at(X, y, at: np.ndarray, block_rows: int) -> np.ndarray:
    """The rows of X at the dataset positions `at`, float64, block by block
    (a gather from the whole of X would copy it on a TPU)."""
    import jax
    import jax.numpy as jnp

    from chipbench import blocks

    mesh = X.sharding.mesh
    axis, shard_rows = mesh.axis_names[0], X.shape[0] // mesh.devices.size

    def pick(Xb, yb, at, first):
        rel = at - (jax.lax.axis_index(axis) * shard_rows + first)
        mine = (rel >= 0) & (rel < Xb.shape[0])
        rows = jnp.take(Xb, jnp.clip(rel, 0, Xb.shape[0] - 1), axis=0)
        return (jnp.where(mine[:, None], rows, 0.0),)

    call = blocks.block_caller(pick, mesh, block_rows, n_args=2)
    at = jnp.asarray(at, jnp.int32)
    parts = [call(X, y, np.int32(b), at, np.int32(b * block_rows))
             for b in range(shard_rows // block_rows)]
    # every row comes from one block of one device: the rest of the sum is zeros
    return sum(part.sum(axis=0, dtype=np.float64) for (part,) in jax.device_get(parts))


def reference(X, y, params: dict, lowered: bool = False) -> dict:
    """Plain Lloyd over the benchmark's own device rows: the initial rows
    re-derived, the products per 50,000-row block in f32 at `highest`, the
    sums, counts and cost added in float64 on the host, the centres updated
    and their shift measured in float64.  `lowered`: both products of a
    step in bfloat16, the precision below the float32 the configuration
    states.  Returns the answer's keys."""
    import jax.numpy as jnp

    from chipbench import blocks

    n, k, tol = X.shape[0], int(params["k"]), float(params["tol"])
    if params["initMode"] != "random":
        raise ValueError("the reference re-derives initMode='random' alone")
    block_rows, mesh = blocks.block_rows_of(X), X.sharding.mesh
    step = blocks.block_caller(block_step(lowered), mesh, block_rows, n_args=1)
    cost = blocks.block_caller(block_cost(lowered), mesh, block_rows, n_args=1)
    C = _rows_at(X, y, initial_rows(int(params["seed"]), n, k), block_rows)
    n_iter = 0
    for n_iter in range(1, int(params["maxIter"]) + 1):
        sums, counts, _ = blocks.sum_blocks(
            step, X, y, block_rows, jnp.asarray(C, jnp.float32))
        held = counts > 0
        new = np.where(held[:, None], sums / np.where(held, counts, 1.0)[:, None], C)
        shift2 = float(((new - C) ** 2).sum(axis=1).max())
        C = new
        if shift2 <= tol * tol:
            break
    (total,) = blocks.sum_blocks(cost, X, y, block_rows, jnp.asarray(C, jnp.float32))
    return {"centres": C, "cost": float(total), "n_iter": n_iter}


def compare(ans: dict, ref: dict) -> dict:
    """The numbers held to the configuration's `limits`: the centres (same
    order, because the same initial rows), the cost under the final centres,
    and the count of iterations."""
    gap = np.linalg.norm(ans["centres"] - ref["centres"]) / np.linalg.norm(ref["centres"])
    return {
        "centre_gap": float(gap),
        "cost_gap": float(abs(ans["cost"] - ref["cost"]) / abs(ref["cost"])),
        "iterations_off": float(abs(ans["n_iter"] - ref["n_iter"])),
    }
