#
# Distributed L-BFGS / OWL-QN — the TPU-native replacement for the solver
# inside `cuml.linear_model.logistic_regression_mg.LogisticRegressionMG`
# (invoked from reference classification.py:1046-1081; cuML runs L-BFGS for
# none/L2 and OWL-QN for L1/elastic-net, with `lbfgs_memory=10`,
# `linesearch_max_iter=20`, classification.py:1046-1052).
#
# TPU-first design: the WHOLE optimizer — two-loop recursion, backtracking
# line search, orthant projection, convergence tests — is one
# `lax.while_loop` under jit.  The loss closure evaluates over the
# row-sharded global data, so XLA inserts one gradient psum over ICI per
# function evaluation; optimizer state (m history pairs of flattened
# parameter size) is replicated.  Zero host round-trips for the entire fit.
#
from __future__ import annotations

import time
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp


class LbfgsResult(NamedTuple):
    w: jax.Array
    f: jax.Array
    n_iter: jax.Array
    converged: jax.Array
    history_f: jax.Array  # (max_iter+1,) full objective per iteration
    # (entry 0 = initial objective; entries past n_iter stay NaN) — the
    # source of Spark's LogisticRegressionTrainingSummary.objectiveHistory


def _pseudo_gradient(w: jax.Array, g: jax.Array, l1: jax.Array, l1_mask: jax.Array):
    """OWL-QN pseudo-gradient of f(w) + l1·‖w∘mask‖₁ (mask excludes
    intercept entries from the penalty, matching Spark)."""
    l1v = l1 * l1_mask
    gp_plus = g + l1v
    gp_minus = g - l1v
    pg = jnp.where(
        w > 0,
        gp_plus,
        jnp.where(
            w < 0,
            gp_minus,
            jnp.where(gp_minus > 0, gp_minus, jnp.where(gp_plus < 0, gp_plus, 0.0)),
        ),
    )
    return pg


def lbfgs_minimize(
    loss_fn: Callable[[jax.Array], jax.Array],
    w0: jax.Array,
    max_iter: int = 100,
    tol: float = 1e-6,
    history: int = 10,
    l1: float = 0.0,
    l1_mask: jax.Array = None,
    ls_max: int = 20,
) -> LbfgsResult:
    """Minimize loss_fn(w) + l1·‖w∘l1_mask‖₁ with L-BFGS (OWL-QN when l1>0).

    loss_fn must be smooth and differentiable (the L2 term belongs inside
    it); w0 is the flattened replicated parameter vector.  Runs as a single
    jitted while_loop.
    """
    n = w0.shape[0]
    m = history
    dtype = w0.dtype
    l1 = jnp.asarray(l1, dtype)
    if l1_mask is None:
        l1_mask = jnp.ones((n,), dtype)

    value_and_grad = jax.value_and_grad(loss_fn)

    def direction(pg, S, Y, rho, k):
        def bwd(j, carry):
            q, alpha = carry
            idx = (k - 1 - j) % m
            valid = j < jnp.minimum(k, m)
            a = jnp.where(valid, rho[idx] * (S[idx] @ q), 0.0)
            q = q - a * Y[idx]
            alpha = alpha.at[idx].set(a)
            return q, alpha

        q, alpha = jax.lax.fori_loop(0, m, bwd, (pg, jnp.zeros((m,), dtype)))
        newest = (k - 1) % m
        sy = S[newest] @ Y[newest]
        yy = Y[newest] @ Y[newest]
        gamma = jnp.where(k > 0, sy / jnp.maximum(yy, 1e-30), 1.0)
        r = gamma * q

        def fwd(j, r):
            idx = (k - m + j) % m
            valid = j >= (m - jnp.minimum(k, m))
            b = rho[idx] * (Y[idx] @ r)
            r = r + jnp.where(valid, alpha[idx] - b, 0.0) * S[idx]
            return r

        r = jax.lax.fori_loop(0, m, fwd, r)
        return -r

    def penalty(w):
        return (l1 * l1_mask * jnp.abs(w)).sum()

    def body(state):
        w, f, g, S, Y, rho, k, it, _, hist = state
        pg = _pseudo_gradient(w, g, l1, l1_mask)
        with jax.named_scope("lbfgs_two_loop"):
            p = direction(pg, S, Y, rho, k)
        # OWL-QN: force descent orthant agreement with -pseudo-gradient
        p = jnp.where(l1 > 0, jnp.where(p * (-pg) > 0, p, 0.0), p)
        # orthant for projection: sign(w), or sign(-pg) where w == 0
        xi = jnp.where(w != 0, jnp.sign(w), jnp.sign(-pg))

        # backtracking Armijo line search (ls_max halvings, cuML's
        # linesearch_max_iter analog).  Displacement form
        # φ(π(w+tp)) ≤ φ(w) + c₁·pg·(π(w+tp)−w) — required for OWL-QN
        # where the orthant projection changes the actual step.
        #
        # Data passes are the cost unit here (each loss evaluation sweeps
        # the sharded dataset): φ(w) comes FREE from the carried smooth
        # loss (+ the parameter-only penalty), and each trial evaluates
        # value_and_grad so the accepted point needs no re-evaluation.
        # The steady-state case (first trial accepted — the norm for a
        # well-scaled L-BFGS direction) costs 1 fwd+bwd instead of the
        # previous 3 fwd + 1 bwd; iterations that backtrack b times pay
        # (b+1) fwd+bwd vs (b+2) fwd + 1 bwd, a deliberate trade that
        # favors the accepted-first path (measured 1.86x end to end).
        t0 = jnp.where(k == 0, 1.0 / jnp.maximum(jnp.linalg.norm(p), 1.0), 1.0)
        fw_full = f + penalty(w)

        def project(w_t):
            return jnp.where(l1 > 0, jnp.where(w_t * xi >= 0, w_t, 0.0), w_t)

        def ls_cond(ls_state):
            t, w_t, f_t, g_t, j = ls_state
            armijo = f_t + penalty(w_t) <= fw_full + 1e-4 * (pg @ (w_t - w))
            return (~armijo) & (j < ls_max)

        def ls_body(ls_state):
            t, _, _, _, j = ls_state
            t = t * 0.5
            w_t = project(w + t * p)
            f_t, g_t = value_and_grad(w_t)
            return t, w_t, f_t, g_t, j + 1

        w_1 = project(w + t0 * p)
        f_1, g_1 = value_and_grad(w_1)
        t, w_new, f_new, g_new, _ = jax.lax.while_loop(
            ls_cond, ls_body, (t0, w_1, f_1, g_1, jnp.array(0, jnp.int32))
        )
        s = w_new - w
        y = g_new - g
        sy = s @ y
        update_ok = sy > 1e-10
        idx = k % m
        S = jnp.where(update_ok, S.at[idx].set(s), S)
        Y = jnp.where(update_ok, Y.at[idx].set(y), Y)
        rho = jnp.where(update_ok, rho.at[idx].set(1.0 / jnp.maximum(sy, 1e-30)), rho)
        k = jnp.where(update_ok, k + 1, k)

        new_full = f_new + penalty(w_new)
        old_full = f + penalty(w)
        rel_impr = (old_full - new_full) / jnp.maximum(jnp.abs(old_full), 1e-30)
        pg_new = _pseudo_gradient(w_new, g_new, l1, l1_mask)
        gnorm = jnp.linalg.norm(pg_new)
        converged = (gnorm <= tol * jnp.maximum(1.0, jnp.linalg.norm(w_new))) | (
            jnp.abs(rel_impr) <= tol
        )
        hist = hist.at[it + 1].set(new_full)
        return w_new, f_new, g_new, S, Y, rho, k, it + 1, converged, hist

    def cond(state):
        it, converged = state[7], state[8]
        return (it < max_iter) & (~converged)

    f0, g0 = value_and_grad(w0)
    hist0 = jnp.full((max_iter + 1,), jnp.nan, dtype).at[0].set(
        f0 + penalty(w0)
    )
    state0 = (
        w0,
        f0,
        g0,
        jnp.zeros((m, n), dtype),
        jnp.zeros((m, n), dtype),
        jnp.zeros((m,), dtype),
        jnp.array(0, jnp.int32),
        jnp.array(0, jnp.int32),
        jnp.array(False),
        hist0,
    )
    w, f, g, S, Y, rho, k, it, converged, hist = jax.lax.while_loop(
        cond, body, state0
    )
    return LbfgsResult(w=w, f=f, n_iter=it, converged=converged, history_f=hist)


def lbfgs_minimize_host(
    value_and_grad,  # theta (np (n,)) -> (f_smooth, grad (np (n,)))
    w0,
    max_iter: int = 100,
    tol: float = 1e-6,
    history: int = 10,
    l1: float = 0.0,
    l1_mask=None,
    ls_max: int = 20,
    checkpoint_path: str = None,
    checkpoint_tag: str = "",
):
    """HOST-driven L-BFGS/OWL-QN for EPOCH-STREAMING fits: the oracle is a
    full pass over out-of-core data (each evaluation re-streams parquet
    chunks through a donated device accumulator — streaming.py), so the
    optimizer state lives in numpy and every function evaluation is one
    dataset epoch.  Mirrors `lbfgs_minimize` (same two-loop recursion,
    Armijo displacement line search, orthant projection, convergence tests)
    so a streamed fit converges to the same optimum as the in-memory
    while_loop solver.  The analog of the reference's dataset-bounded-by-
    cluster-memory ingest (reference utils.py:403-522): dataset size here
    is bounded by DISK, not HBM x chips.

    `checkpoint_path`: long-running fits (epoch-streaming over hours, or
    the host-dispatched in-memory solver with `checkpoint_dir` set) write
    the full optimizer state after every accepted iteration via the
    shared checkpoint contract (resilience/checkpoint.py: atomic tmp +
    os.replace, rank-0 writer, in-file tag check) and a later call with
    the same path RESUMES the identical trajectory — the beyond-HBM
    analog of a training-job preemption recovery.  The file is removed on
    successful completion.

    Returns (w, n_iter, converged, history) with history the full
    (penalty-inclusive) objective per accepted iterate, entry 0 = initial.
    """
    import numpy as np

    from ..resilience import maybe_inject
    from ..resilience.checkpoint import (
        clear_checkpoint,
        load_checkpoint,
        save_checkpoint,
    )

    from ..tracing import record_span

    n = w0.shape[0]
    m = history
    l1 = float(l1)
    if l1_mask is None:
        l1_mask = np.ones((n,), np.float64)

    # the host's own share of the solve, one `lbfgs_host_step` span per
    # stretch between evaluations: the preamble before the first, the
    # two-loop / line-search bookkeeping / checkpoint write between two,
    # the tail after the last.  The oracle's spans are the oracle's own.
    host_since = time.time()

    def evaluate(w_t):
        nonlocal host_since
        record_span("lbfgs_host_step", host_since, time.time())
        out = value_and_grad(w_t)
        host_since = time.time()
        return out

    def full_term(w):
        return (l1 * l1_mask * np.abs(w)).sum()

    def pseudo_grad(w, g):
        l1v = l1 * l1_mask
        gp, gm = g + l1v, g - l1v
        return np.where(
            w > 0,
            gp,
            np.where(w < 0, gm, np.where(gm > 0, gm, np.where(gp < 0, gp, 0.0))),
        )

    S = np.zeros((m, n))
    Y = np.zeros((m, n))
    rho = np.zeros((m,))
    k = 0

    # a checkpoint is only trusted for the SAME problem: the tag binds it
    # to (data, params, shapes); anything else starts fresh (the tag check
    # lives in resilience/checkpoint.py load_checkpoint)
    resumed = (
        load_checkpoint(checkpoint_path, checkpoint_tag)
        if checkpoint_path
        else None
    )

    def direction(pg):
        q = pg.astype(np.float64).copy()
        alpha = np.zeros((m,))
        kk = min(k, m)
        for j in range(kk):
            idx = (k - 1 - j) % m
            a = rho[idx] * (S[idx] @ q)
            q -= a * Y[idx]
            alpha[idx] = a
        if k > 0:
            newest = (k - 1) % m
            sy = S[newest] @ Y[newest]
            yy = Y[newest] @ Y[newest]
            gamma = sy / max(yy, 1e-30)
        else:
            gamma = 1.0
        r = gamma * q
        for j in range(m - kk, m):
            idx = (k - m + j) % m
            b = rho[idx] * (Y[idx] @ r)
            r += (alpha[idx] - b) * S[idx]
        return -r

    if resumed is not None:
        w = np.asarray(resumed["w"])
        f = float(resumed["f"])
        g = np.asarray(resumed["g"])
        S[:] = resumed["S"]
        Y[:] = resumed["Y"]
        rho[:] = resumed["rho"]
        k = int(resumed["k"])
        it = int(resumed["it"])
        hist = [float(v) for v in resumed["hist"]]
        converged = bool(resumed["converged"])
        from ..tracing import event

        event("lbfgs_resume", detail=f"it={it}")
    else:
        w = np.asarray(w0, np.float64).copy()
        f, g = evaluate(w)
        hist = [float(f + full_term(w))]
        converged = False
        it = 0
    from ..telemetry import Heartbeat

    hb = Heartbeat("lbfgs", total=max_iter)
    while it < max_iter and not converged:
        maybe_inject("lbfgs_iteration")
        pg = pseudo_grad(w, g)
        p = direction(pg)
        if l1 > 0:
            p = np.where(p * (-pg) > 0, p, 0.0)
        xi = np.where(w != 0, np.sign(w), np.sign(-pg))

        def project(w_t):
            return np.where(w_t * xi >= 0, w_t, 0.0) if l1 > 0 else w_t

        t = 1.0 if k > 0 else 1.0 / max(np.linalg.norm(p), 1.0)
        fw_full = hist[-1]
        w_new, f_new, g_new = w, f, g
        for _ in range(ls_max + 1):
            w_t = project(w + t * p)
            f_t, g_t = evaluate(w_t)
            w_new, f_new, g_new = w_t, f_t, g_t
            if f_t + full_term(w_t) <= fw_full + 1e-4 * (pg @ (w_t - w)):
                break
            t *= 0.5

        s = w_new - w
        yv = g_new - g
        sy = s @ yv
        if sy > 1e-10:
            idx = k % m
            S[idx], Y[idx], rho[idx] = s, yv, 1.0 / max(sy, 1e-30)
            k += 1

        new_full = float(f_new + full_term(w_new))
        old_full = hist[-1]
        rel_impr = (old_full - new_full) / max(abs(old_full), 1e-30)
        pg_new = pseudo_grad(w_new, g_new)
        gnorm = np.linalg.norm(pg_new)
        converged = bool(
            gnorm <= tol * max(1.0, np.linalg.norm(w_new))
            or abs(rel_impr) <= tol
        )
        w, f, g = w_new, f_new, g_new
        hist.append(new_full)
        it += 1
        hb.beat(it, loss=new_full)
        if checkpoint_path:
            save_checkpoint(checkpoint_path, checkpoint_tag, {
                "w": w, "f": f, "g": g, "S": S, "Y": Y,
                "rho": rho, "k": k, "it": it,
                "hist": np.asarray(hist), "converged": converged,
            })
    # end-mark on normal completion: the solver gauges must not report
    # a finished fit as live (a mid-loop death keeps its last state
    # visible for the flight recorder's post-mortem)
    hb.close()
    if checkpoint_path:
        clear_checkpoint(checkpoint_path)
    record_span("lbfgs_host_step", host_since, time.time())
    return w, it, converged, hist
