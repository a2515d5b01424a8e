#
# chipbench/estimators/mlogreg.py: the multinomial LogisticRegression
# family (softmax over C classes, `family` auto with C > 2 labels).
#
# What the harness needs of an estimator family, and nothing of the program
# beyond `build`: how to build it, what an answer is, the least work its
# shapes demand, and a plain reference of the same semantics with its
# lower-precision control.
#
# Objective (Spark's multinomial): sum_i w_i (logsumexp_c(x_i.W_c + b_c)
# - (x_i.W_{y_i} + b_{y_i})) / sum_i w_i + regParam/2 |W|^2, intercepts
# unpenalised, no standardisation; Spark centres the fitted intercepts
# (their mean taken off: the softmax does not see it), and so does the
# reference.  Solver as `logreg.py` states it (L-BFGS, 10 pairs, Armijo
# backtracking by halving, at most 20 retries, the first step
# 1/max(|p|, 1)); with tol = 1e-30 every iteration runs.
#
# The oracle reads the device rows in blocks (the rows' count need not
# divide: the last block starts a block from the end and weighs the rows
# the block before it took 0).  Both products of a block are the sum of ALL
# NINE products of the operands' three bfloat16 parts (which add up to the
# float32 operands exactly), smallest first, as `pca.py exact_gram`:
# XLA's `highest` is 6-8e-6 low on a v5e (PERF.md §4).  Blocks add in
# float64 on the host.  The control (`lowered`) takes each product as ONE
# bfloat16 pass, what a float32 matmul at default precision is on a TPU.
#
from __future__ import annotations

from collections import deque

import numpy as np

from chipbench import manifest as mf

_logreg = mf.adapter("logreg")  # the two-loop direction, the comparison

# XLA module names of the programs that evaluate value + gradient (the
# host-dispatched jit, the fused while_loop program, the blocked
# evaluation where it runs as a program of its own)
PROGRAMS = {"lbfgs_eval": ("vg_fn", "logreg_fit", "_softmax_evaluate")}
HISTORY, LS_MAX, ARMIJO = _logreg.HISTORY, _logreg.LS_MAX, _logreg.ARMIJO
BLOCK_ROWS = 20_000  # the reference's rows to a block


def build(params: dict, chips: int):
    """The estimator (`numClasses` is the configuration's, read by `work`;
    the estimator learns it from the labels).  Until the model is made, its
    fit kernel's attributes carry the objective the fit ended at and then
    W under `coef_`, as ONE vector: chipbench/tests' fault test alters the
    first number of `attrs["coef_"]` by 1 % (PERF.md §7: an adapter should
    name its state, then this goes), and for a 1,000-class fit that number
    has to be the objective, as KMeans' is its cost: at the tests' toy size
    (4 rows a class) 1 % of one of 48,000 coefficients lies inside what a
    float32 rounding moves a 20-iteration trajectory (PERF.md §4).  One
    8 MB copy a fit, nothing in the timed kernels."""
    from spark_rapids_ml_tpu.classification import LogisticRegression

    est_params = {k: v for k, v in params.items() if k != "numClasses"}
    est = LogisticRegression(num_workers=chips, **est_params)
    fit_array, create_model = est._fit_array, est._create_model

    def fit_array_as_coef(fit_input):
        attrs = fit_array(fit_input)
        coef = np.asarray(attrs.pop("coef_"))
        attrs["coef_"] = np.concatenate(
            [np.asarray([attrs["objective"]], coef.dtype), coef.ravel()])
        attrs["coef_shape"] = coef.shape
        return attrs

    def create_model_from_coef(attrs):
        state = attrs.pop("coef_")
        attrs["objective"] = float(state[0])
        attrs["objective_history"] = attrs["objective_history"][:-1] + [float(state[0])]
        attrs["coef_"] = state[1:].reshape(attrs.pop("coef_shape"))
        return create_model(attrs)

    est._fit_array, est._create_model = fit_array_as_coef, create_model_from_coef
    return est


def answer(model) -> dict:
    """What a fit returned, as plain host numbers: W and the intercepts as
    one vector (coefficients first), the objective history, the iterations,
    and the run's `fact[lbfgs]` (None from a program that records none)."""
    W = np.asarray(model.coefficientMatrix, np.float64)
    b = np.asarray(model.interceptVector, np.float64)
    return {
        "theta": np.append(W.ravel(), b),
        "history": [float(v) for v in model.summary.objectiveHistory],
        "n_iter": int(model.summary.totalIterations),
        "fact": (model.fit_report() or {}).get("lbfgs"),
    }


def work(rows: int, cols: int, chips: int, params: dict) -> dict:
    """Least work per chip.  One evaluation needs the features once
    (rows x cols f32 read) and two products with the C x cols weights (the
    logits X W^T and the gradient R^T X): 4 rows cols C FLOP; a fit of
    maxIter iterations needs maxIter + 1 evaluations at the least."""
    share = rows / chips
    C = int(params["numClasses"])
    ev = {"flops": 4.0 * share * cols * C, "bytes": share * cols * 4.0}
    return {
        "kernels": {"lbfgs_eval": ev},
        "fit": [dict(ev, count=int(params["maxIter"]) + 1)],
    }


def _parts(x, n: int):
    """`n` bfloat16 parts of float32 `x`, cut by `reduce_precision` (a cast
    there and back is a pair XLA elides on a TPU)."""
    import jax
    import jax.numpy as jnp

    out, rest = [], x
    for _ in range(n):
        part = jax.lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
        out.append(part.astype(jnp.bfloat16))
        rest = rest - part
    return out


def product(a, b, dims, lowered: bool):
    """dot_general(a, b, dims) of float32 operands: all nine products of
    their three bfloat16 parts, each accumulated in f32, added smallest
    first; `lowered`, one product of their one-part roundings."""
    import jax
    import jax.numpy as jnp

    def dot(p, q):
        return jax.lax.dot_general(p, q, dims, preferred_element_type=jnp.float32)

    if lowered:
        return dot(_parts(a, 1)[0], _parts(b, 1)[0])
    pa, pb = _parts(a, 3), _parts(b, 3)
    terms = sorted(((i, j) for i in range(3) for j in range(3)), key=lambda t: -sum(t))
    out = None
    for i, j in terms:
        out = dot(pa[i], pb[j]) if out is None else out + dot(pa[i], pb[j])
    return out


def block_value_grad(lowered: bool):
    """(X_block, y_block, live (B,) bool, W (C, d) f32, b (C,) f32) -> the
    block's sums of w nll, of d/dW and of d/db, with w = live (the cells'
    weights are ones)."""
    import jax
    import jax.numpy as jnp

    def vg(Xb, yb, live, W, b):
        C = W.shape[0]
        wb = live.astype(jnp.float32)
        logits = product(Xb, W, (((1,), (1,)), ((), ())), lowered) + b
        z = logits - logits.max(axis=1, keepdims=True)
        e = jnp.exp(z)
        s = e.sum(axis=1, keepdims=True)
        hit = yb.astype(jnp.int32)[:, None] == jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
        nll = jnp.log(s[:, 0]) - jnp.where(hit, z, 0.0).sum(axis=1)
        r = wb[:, None] * (e / s - hit.astype(jnp.float32))
        g = product(r, Xb, (((0,), (0,)), ((), ())), lowered)
        return (wb * nll).sum(), g, r.sum(axis=0)

    return vg


def _block_caller(fn, mesh, block_rows: int):
    """jit of (X, y, k, W, b) -> fn(block k of each device's shard), the
    outputs stacked over the devices.  Block k starts at k * block_rows, or
    block_rows from the shard's end where that runs over."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]

    def local(Xl, yl, k, W, b):
        first = k * block_rows
        at = jnp.minimum(first, Xl.shape[0] - block_rows)
        Xb = jax.lax.dynamic_slice_in_dim(Xl, at, block_rows, 0)
        yb = jax.lax.dynamic_slice_in_dim(yl, at, block_rows, 0)
        live = at + jnp.arange(block_rows) >= first
        return jax.tree.map(lambda a: a[None], fn(Xb, yb, live, W, b))

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis, None), P(axis), P(), P(), P()),
        out_specs=P(axis), check_vma=False,
    ))


def _oracle(X, y, C: int, lowered: bool):
    import jax
    import jax.numpy as jnp

    mesh = X.sharding.mesh
    n, d = X.shape
    shard_rows = n // mesh.devices.size
    block_rows = min(BLOCK_ROWS, shard_rows)
    call = _block_caller(block_value_grad(lowered), mesh, block_rows)
    n_blocks = -(-shard_rows // block_rows)

    def evaluate(theta: np.ndarray):
        W = jnp.asarray(theta[:C * d].reshape(C, d), jnp.float32)
        b = jnp.asarray(theta[C * d:], jnp.float32)
        outs = jax.device_get([call(X, y, np.int32(k), W, b) for k in range(n_blocks)])
        loss, gW, gb = (sum(np.sum(o[i], axis=0, dtype=np.float64) for o in outs)
                        for i in range(3))
        return float(loss) / n, np.append(gW.ravel(), gb) / n

    return evaluate


def reference(X, y, params: dict, lowered: bool = False) -> dict:
    """The fit in plain numpy float64 over the blocked oracle on the
    benchmark's own device rows, the intercepts centred at the end.
    Returns the answer's keys, `n_evals` and `objective_at`, the objective
    at any coefficients (it holds the rows while it lives)."""
    C, d = int(params["numClasses"]), X.shape[1]
    l2 = float(params["regParam"]) * (1.0 - float(params.get("elasticNetParam", 0.0)))
    evaluate = _oracle(X, y, C, lowered)
    n_evals = 0

    def full(theta):
        nonlocal n_evals
        n_evals += 1
        f, g = evaluate(theta)
        W = theta[:C * d]
        g[:C * d] += l2 * W
        return f + 0.5 * l2 * float(W @ W), g

    theta = np.zeros(C * (d + 1))
    f, g = full(theta)
    history = [f]
    pairs: deque = deque(maxlen=HISTORY)
    for _ in range(int(params["maxIter"])):
        p = _logreg._direction(g, list(pairs))
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
    theta = theta.copy()
    theta[C * d:] -= theta[C * d:].mean()
    out = {"theta": theta, "history": history, "n_iter": len(history) - 1,
           "n_evals": n_evals}
    seen: dict = {}

    def objective_at(theta_fit: np.ndarray) -> float:
        """The objective at a fit's own coefficients (the softmax does not
        see the intercepts' centring), once for each distinct answer."""
        key = np.asarray(theta_fit, np.float64).tobytes()
        if key not in seen:
            seen[key] = full(np.asarray(theta_fit, np.float64))[0]
        return seen[key]

    out["objective_at"] = objective_at
    return out


def compare(ans: dict, ref: dict) -> dict:
    """The numbers held to the configuration's `limits`: `logreg.py`'s
    coefficients with the centred intercepts, root mean square of the
    relative gaps of the objective history and count of iterations, and,
    in place of its gap of the objectives the two fits ended at (that one
    follows the trajectory's own drift: PERF.md §4), the relative gap
    between the objective the fit reports and the reference's objective at
    the fit's own coefficients: how far the fit's evaluation is from exact
    where it ended."""
    out = _logreg.compare(ans, ref)
    exact = ref["objective_at"](ans["theta"])
    out["objective_gap"] = float(abs(ans["history"][-1] - exact) / abs(exact))
    return out
