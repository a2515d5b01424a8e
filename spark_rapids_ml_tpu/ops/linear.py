#
# Linear regression kernels — the TPU-native replacement for cuML's
# `LinearRegressionMG` (OLS eig solver), `RidgeMG`, and `CDMG` coordinate
# descent (dispatched by reg params at reference regression.py:544-627).
#
# TPU-first design: instead of three distributed solvers, ONE fused
# sufficient-statistics kernel makes a single pass over the row-sharded data
# (all matmuls, psum'd by XLA), and every solver variant — OLS, ridge,
# elastic-net — then operates on the replicated (d,d) system:
#   - OLS / ridge: closed-form solve of the (centered, optionally
#     standardized) normal equations.
#   - elastic-net: FISTA proximal gradient on the Gram system — same
#     optimum as coordinate descent for this convex objective, but with
#     O(d²) per-iteration cost independent of n and no data re-reads.
#
# Spark objective (matched): 1/(2n)·Σwᵢ(xᵢ·β - yᵢ)² + λ·[α‖β‖₁ + (1-α)/2‖β‖²]
# with λ=regParam, α=elasticNetParam; penalty applied to standardized
# coefficients when standardization=True (reference un-scaling,
# regression.py:532-543, 632-646; ridge α×=m regression.py:575-580 is this
# same n-scaling in sklearn units).
#
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Sample-weight/fold-mask contract (parallel/device_cache.py): the
# sufficient statistics weight every term by `w` and the host solver
# consumes only those weighted sums (n enters as sw = w.sum()), so a w=0
# row — zero padding OR a CV fold-mask hole — is mathematically absent.
# The device cache's masked fold views rely on this; new reductions must
# preserve it (tests/test_device_cache.py asserts the invariance).
SUPPORTS_ZERO_WEIGHT_ROWS = True


@jax.jit
def linreg_sufficient_stats(X: jax.Array, w: jax.Array, y: jax.Array):
    """One pass: weighted Gram, moment, and cross terms.  X (N_pad,d)
    row-sharded, w validity*sample weights, y labels (0 on padding)."""
    from .precision import stats_precision

    # the scope names the kernels in a profile (metadata only)
    with jax.named_scope("linreg_gram"):
        Xw = X * w[:, None]
        # the normal equations invert this Gram: f32-exact products by
        # default (cuML parity; see ops/precision.py stats_precision)
        hi = stats_precision()
        gram = jnp.matmul(Xw.T, X, precision=hi)  # (d,d) — MXU, psum over shards
        sxy = jnp.matmul(Xw.T, y, precision=hi)  # (d,)
        s1 = Xw.sum(axis=0)  # (d,)
        sw = w.sum()
        sy = (y * w).sum()
        syy = (y * y * w).sum()
    return gram, sxy, s1, sw, sy, syy


def _soft_threshold(v: np.ndarray, t: float) -> np.ndarray:
    return np.sign(v) * np.maximum(np.abs(v) - t, 0.0)


def _fortran_view(A: np.ndarray) -> np.ndarray:
    """What BLAS and LAPACK update in place: for a C-ordered buffer the
    transposed view, the same matrix where it is symmetric."""
    return A if A.flags.f_contiguous else A.T


def _normal_system(gram, sw: float, mean, scale, ridge: float) -> np.ndarray:
    """The Gram centred by `mean` and divided by `scale` x `scale` (each
    skipped where None) plus `ridge` on the diagonal, in float64, built in
    ONE new buffer: the caller's array is never written, and no other
    (d,d) array is made."""
    from scipy.linalg.blas import dger

    A = np.array(gram, dtype=np.float64)
    if mean is not None:
        # A -= sw·mean·meanᵀ, a rank-1 update in place
        dger(-sw, mean, mean, a=_fortran_view(A), overwrite_a=1)
    if scale is not None:
        A /= scale[:, None]
        A /= scale
    if ridge:
        A.flat[:: A.shape[0] + 1] += ridge
    return A


def _quadratic_form(gram: np.ndarray, v: np.ndarray) -> float:
    """vᵀ·gram·v in float64, the Gram widened a block of columns (~2 MB,
    which stays in cache) at a time: a whole float64 copy of a float32 Gram
    is one more (d,d) array to first-touch, ~90 ms against ~4 at d = 3000.
    Through scipy's BLAS, as the factorisation before it: numpy links an
    OpenBLAS of its own, and the workers of two pools, spinning after
    their last call, are more than the host's cores; the residual pass
    that follows then waited 25-35 ms for a core (PERF.md §6, PR 27)."""
    from scipy.linalg.blas import dgemv

    G = _fortran_view(gram)  # vᵀGv = vᵀGᵀv: either view serves
    cols = max(1, (1 << 18) // G.shape[0])
    return float(sum(
        dgemv(1.0, np.asarray(G[:, j : j + cols], np.float64), v, trans=1)
        @ v[j : j + cols]
        for j in range(0, G.shape[1], cols)
    ))


def solve_linear_host(
    gram: np.ndarray,
    sxy: np.ndarray,
    s1: np.ndarray,
    sw: float,
    sy: float,
    syy: float,
    reg_param: float,
    elasticnet_param: float,
    fit_intercept: bool,
    standardization: bool,
    tol: float,
    max_iter: int,
    checkpoint_path: str = None,
    checkpoint_tag: str = "",
) -> Tuple[np.ndarray, float, Dict[str, float]]:
    """Solve from sufficient statistics on the host in float64.

    `checkpoint_path`/`checkpoint_tag`: the FISTA elastic-net loop (the
    only iterative branch) persists its state per iteration via the
    shared contract (resilience/checkpoint.py) and resumes an
    interrupted solve; the closed-form branches have nothing to resume.

    Returns (coefficients (d,), intercept, diagnostics).
    """
    # only read, in the dtype it came in: at d = 3000 a (d,d) float64 is
    # 72 MB, first-touched page by page, and the ten temporaries of a build
    # by whole-array expressions took longer than the factorisation.  The
    # one such array made here is the system's buffer (`_normal_system`).
    gram = np.asarray(gram)
    sxy = np.asarray(sxy, np.float64)
    s1 = np.asarray(s1, np.float64)
    sw = float(sw)
    sy = float(sy)
    d = gram.shape[0]

    mean = s1 / sw
    ymean = sy / sw
    sxy_c = sxy - sw * mean * ymean if fit_intercept else sxy

    # Spark summarizer std (ddof=1) over the *centered* second moments
    gram_diag = np.asarray(np.diag(gram), np.float64)
    var = np.maximum(gram_diag / sw - mean**2, 0.0) * (sw / max(sw - 1.0, 1.0))
    std = np.sqrt(var)
    std = np.where(std == 0.0, 1.0, std)
    scale = std if standardization else np.ones(d)
    sxy_s = sxy_c / scale
    system = functools.partial(
        _normal_system, gram, sw,
        mean if fit_intercept else None, std if standardization else None,
    )

    l1 = reg_param * elasticnet_param
    l2 = reg_param * (1.0 - elasticnet_param)
    n_iter = 0

    if reg_param == 0.0:
        coef_s = np.linalg.lstsq(system(0.0), sxy_s, rcond=None)[0]
    elif l1 == 0.0:
        # ridge closed form; penalty in 1/(2n) objective units -> n·λ₂ on
        # the un-normalized Gram (the reference's alpha×=m, regression.py:575-580).
        # Symmetric positive definite by construction, so a Cholesky
        # factorisation solves it (half an LU's work, no pivoting).  Where
        # it is not numerically (a float32-accumulated Gram whose
        # near-collinear columns the penalty does not lift) the
        # factorisation says so and the pivoted LU takes it, as before.
        # Cholesky reads one triangle: a Gram whose [i,j] and [j,i] were
        # rounded apart (off the chip: 5e-9 of its norm) moves the
        # coefficients against the LU's by about as much.
        from scipy.linalg import LinAlgError, cho_factor, cho_solve

        from ..tracing import event

        try:
            factor = cho_factor(
                _fortran_view(system(sw * l2)),
                lower=True, overwrite_a=True, check_finite=False,
            )
        except LinAlgError:
            event("linreg_solver[lu_fallback]")
            coef_s = np.linalg.solve(system(sw * l2), sxy_s)
        else:
            event("linreg_solver[cholesky]")
            coef_s = cho_solve(factor, sxy_s, check_finite=False)
    else:
        # FISTA on f(β)=1/(2n)(βᵀGβ - 2bᵀβ) + λ₂/2‖β‖², prox for λ₁‖β‖₁
        from ..resilience import maybe_inject
        from ..resilience.checkpoint import (
            clear_checkpoint,
            load_checkpoint,
            save_checkpoint,
        )

        G = system(0.0)
        G /= sw
        b = sxy_s / sw
        L = float(np.linalg.eigvalsh(G)[-1]) + l2
        L = max(L, 1e-12)
        beta = np.zeros(d)
        z = beta.copy()
        t_mom = 1.0
        start_it = 0
        resumed = (
            load_checkpoint(checkpoint_path, checkpoint_tag)
            if checkpoint_path
            else None
        )
        if resumed is not None:
            beta = np.asarray(resumed["beta"])
            z = np.asarray(resumed["z"])
            t_mom = float(resumed["t_mom"])
            start_it = int(resumed["it"])
            # a checkpoint saved at it==max_iter (crash between the final
            # save and clear) skips the loop entirely — the diag count
            # must still report the iterations already run
            n_iter = start_it
            from ..tracing import event

            event("fista_resume", detail=f"it={start_it}")
        from ..telemetry import Heartbeat

        hb = Heartbeat("fista", total=max_iter)
        for it in range(start_it, max_iter):
            maybe_inject("linreg_fista")
            grad = G @ z - b + l2 * z
            beta_new = _soft_threshold(z - grad / L, l1 / L)
            t_new = 0.5 * (1.0 + np.sqrt(1.0 + 4.0 * t_mom * t_mom))
            z = beta_new + ((t_mom - 1.0) / t_new) * (beta_new - beta)
            delta = float(np.max(np.abs(beta_new - beta)))
            beta = beta_new
            t_mom = t_new
            n_iter = it + 1
            hb.beat(n_iter, detail=f"delta={delta:.3e}")
            if checkpoint_path:
                save_checkpoint(
                    checkpoint_path, checkpoint_tag,
                    {"beta": beta, "z": z, "t_mom": t_mom, "it": n_iter},
                )
            if delta <= tol * max(1.0, float(np.max(np.abs(beta)))):
                break
        # end-mark on normal completion (Heartbeat.close): a scrape
        # after the fit shows no live fista series
        hb.close()
        if checkpoint_path:
            clear_checkpoint(checkpoint_path)
        coef_s = beta

    coef = coef_s / scale
    intercept = float(ymean - mean @ coef) if fit_intercept else 0.0
    # training-summary statistics from the same sufficient stats (Spark's
    # LinearRegressionTrainingSummary surface): weighted
    # SSE = Σw(y - Xβ - b)² expanded in gram/cross/moment terms.
    # NOTE: this expansion subtracts near-equal accumulated terms; with
    # f32-accumulated inputs the absolute error is ~eps32·syy/sw, so
    # callers holding the data should overwrite with `summary_stats`'s
    # cancellation-free residual pass (models/regression.py does).
    sse = (
        syy
        - 2.0 * (coef @ sxy + intercept * sy)
        + _quadratic_form(gram, coef)
        + 2.0 * intercept * (s1 @ coef)
        + intercept * intercept * sw
    )
    sse = max(float(sse), 0.0)
    diag = {"n_iter": float(n_iter)}
    diag.update(_summary_from_sse(sse, sw, sy, syy, fit_intercept))
    return coef, intercept, diag


def _summary_from_sse(
    sse: float, sw: float, sy: float, syy: float, fit_intercept: bool
) -> Dict[str, float]:
    """Weighted mse/rmse/r2 from residual and label moments.  Spark
    semantics: SStot is through-origin (Σw·y²) when fitIntercept=False
    (RegressionMetrics throughOrigin); r2 is NaN when SStot == 0 but the
    model still mispredicts, 1.0 only for an exact fit."""
    sst = float(syy - sy * sy / sw) if fit_intercept else float(syy)
    sst = max(sst, 0.0)
    if sst > 0.0:
        r2 = 1.0 - sse / sst
    else:
        r2 = 1.0 if sse == 0.0 else float("nan")
    return {
        "mse": sse / sw,
        "rmse": float(np.sqrt(sse / sw)),
        "r2": r2,
    }


@jax.jit
def linreg_residual_sse(X: jax.Array, w: jax.Array, y: jax.Array,
                        coef: jax.Array, intercept):
    """Cancellation-free weighted SSE: one extra matvec over the staged
    data.  Residuals are computed directly, so precision tracks the
    residual magnitude instead of eps·Σw·y² (the one-pass expansion's
    floor)."""
    with jax.named_scope("linreg_residual"):
        r = y - (X @ coef + intercept)
        return (w * r * r).sum()


@jax.jit
def linreg_predict(X: jax.Array, coef: jax.Array, intercept):
    return X @ coef + intercept
