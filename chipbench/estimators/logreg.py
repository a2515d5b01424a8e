#
# chipbench/estimators/logreg.py: the binomial LogisticRegression family.
#
# What the harness needs of an estimator family, and nothing of the program
# beyond `build`: how to build it, what an answer is, the least work its
# shapes demand (FLOP and bytes, functions of the configuration alone), and
# a plain reference of the same semantics with its lower-precision control.
#
# Objective (Spark's, binomial): mean_i softplus(-s_i (x_i.beta + b))
# + regParam/2 |beta|^2 with s = 2y - 1, intercept unpenalised, no
# standardisation.  Solver as the reference project runs it (cuML L-BFGS,
# lbfgs_memory=10, linesearch_max_iter=20): two-loop recursion over the last
# 10 pairs, scaled by s.y / y.y; Armijo backtracking (c1 = 1e-4, halving)
# from step 1, from 1/max(|p|, 1) in the first iteration; a pair is kept
# only when s.y > 1e-10.  With tol = 1e-30 every iteration runs.
#
from __future__ import annotations

from collections import deque

import numpy as np

LABELS = "sign"  # chipbench/datagen.py label model
# XLA module names of the programs that evaluate value + gradient (the
# host-dispatched jit, the fused while_loop program)
PROGRAMS = {"lbfgs_eval": ("vg_fn", "logreg_fit")}
HISTORY, LS_MAX, ARMIJO = 10, 20, 1e-4


def build(params: dict, chips: int):
    from spark_rapids_ml_tpu.classification import LogisticRegression

    return LogisticRegression(num_workers=chips, **params)


def answer(model) -> dict:
    """What a fit returned, as plain host numbers."""
    return {
        "theta": np.append(
            np.asarray(model.coefficients, np.float64).ravel(),
            float(model.intercept)),
        "history": [float(v) for v in model.summary.objectiveHistory],
        "n_iter": int(model.summary.totalIterations),
    }


def work(rows: int, cols: int, chips: int, params: dict) -> dict:
    """Least work per chip.  One evaluation needs the features once
    (rows x cols f32 read) and two matvecs (x.beta and r.X); a fit of
    maxIter iterations needs maxIter + 1 evaluations at the least."""
    share = rows / chips
    ev = {"flops": 4.0 * share * cols, "bytes": share * cols * 4.0}
    return {
        "kernels": {"lbfgs_eval": ev},
        "fit": [dict(ev, count=int(params["maxIter"]) + 1)],
    }


def block_value_grad(lowered: bool):
    """(X_block, y_block, theta (d+1,) f32) -> the block's sums of the loss,
    of d loss/d beta and of d loss/d b.  `lowered`: features, beta and the
    residual rounded to bfloat16, the products accumulated in f32, as one
    MXU pass does."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def vg(Xb, yb, theta):
        d = Xb.shape[1]
        s = 2.0 * yb - 1.0
        beta, b = theta[:d], theta[d]
        if lowered:
            Xq = Xb.astype(jnp.bfloat16)
            m = jnp.matmul(Xq, beta.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
        else:
            m = jnp.matmul(Xb, beta, precision=hi)
        z = s * (m + b)
        r = -s * jax.nn.sigmoid(-z)
        if lowered:
            g = jnp.matmul(r.astype(jnp.bfloat16), Xq,
                           preferred_element_type=jnp.float32)
        else:
            g = jnp.matmul(r, Xb, precision=hi)
        return jax.nn.softplus(-z).sum(), g, r.sum()

    return vg


def _oracle(X, y, lowered: bool):
    import jax.numpy as jnp

    from chipbench import blocks

    n, block_rows = X.shape[0], blocks.block_rows_of(X)
    call = blocks.block_caller(
        block_value_grad(lowered), X.sharding.mesh, block_rows, n_args=1)

    def evaluate(theta: np.ndarray):
        loss, g, gb = blocks.sum_blocks(
            call, X, y, block_rows, jnp.asarray(theta, jnp.float32))
        return float(loss) / n, np.append(g, gb) / n

    return evaluate


def _direction(g, pairs):
    q = g.copy()
    alphas = []
    for s, yv, rho in reversed(pairs):
        a = rho * (s @ q)
        q -= a * yv
        alphas.append(a)
    if pairs:
        s, yv, _ = pairs[-1]
        q *= (s @ yv) / max(yv @ yv, 1e-30)
    for (s, yv, rho), a in zip(pairs, reversed(alphas)):
        q += (a - rho * (yv @ q)) * s
    return -q


def reference(X, y, params: dict, lowered: bool = False) -> dict:
    """The fit in plain numpy float64 over an f32 `highest` oracle on the
    benchmark's own device rows.  Returns the answer's keys and `n_evals`."""
    d = X.shape[1]
    l2 = float(params["regParam"]) * (1.0 - float(params.get("elasticNetParam", 0.0)))
    evaluate = _oracle(X, y, lowered)
    n_evals = 0

    def full(theta):
        nonlocal n_evals
        n_evals += 1
        f, g = evaluate(theta)
        beta = theta[:d]
        g[:d] += l2 * beta
        return f + 0.5 * l2 * float(beta @ beta), g

    theta = np.zeros(d + 1)
    f, g = full(theta)
    history = [f]
    pairs: deque = deque(maxlen=HISTORY)
    for _ in range(int(params["maxIter"])):
        p = _direction(g, list(pairs))
        t = 1.0 if pairs else 1.0 / max(float(np.linalg.norm(p)), 1.0)
        for _ in range(LS_MAX + 1):
            theta_t = theta + t * p
            f_t, g_t = full(theta_t)
            if f_t <= f + ARMIJO * (g @ (theta_t - theta)):
                break
            t *= 0.5
        s, yv = theta_t - theta, g_t - g
        if s @ yv > 1e-10:
            pairs.append((s, yv, 1.0 / (s @ yv)))
        theta, f, g = theta_t, f_t, g_t
        history.append(f)
    return {"theta": theta, "history": history, "n_iter": len(history) - 1,
            "n_evals": n_evals}


def compare(ans: dict, ref: dict) -> dict:
    """The numbers held to the configuration's `limits`: the coefficients
    with the intercept, the objective the fit ended at, the objective after
    every iteration (root mean square of the relative gaps: the widest single
    gap swings threefold from seed to seed), and the count of iterations."""
    gap = np.linalg.norm(ans["theta"] - ref["theta"]) / np.linalg.norm(ref["theta"])
    k = min(len(ans["history"]), len(ref["history"]))
    rel = np.abs(np.asarray(ans["history"][:k]) - ref["history"][:k]) / np.abs(ref["history"][:k])
    return {
        "coef_gap": float(gap),
        "objective_gap": float(rel[-1]),
        "history_gap": float(np.sqrt(np.mean(rel * rel))),
        "iterations_off": float(abs(ans["n_iter"] - ref["n_iter"])
                                + abs(len(ans["history"]) - len(ref["history"]))),
    }
