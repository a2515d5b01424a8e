#
# PCA kernel — the TPU-native replacement for `cuml.decomposition.pca_mg.
# PCAMG.fit` (called from reference feature.py:240-261).  The cuML MG kernel
# computes a distributed covariance then an eigendecomposition with NCCL
# reductions; here the Gram matrix of the row-sharded centered data is one
# jnp matmul (XLA inserts the psum over ICI) and the k×k eigh runs
# replicated on every chip.
#
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# Sample-weight/fold-mask contract (parallel/device_cache.py): every data
# reduction in this module weights rows by `w` and never uses a row COUNT
# as n (mean/cov divide by w.sum()), so a w=0 row — zero padding OR a CV
# fold-mask hole — is mathematically absent.  The device cache's masked
# fold views rely on this; new reductions must preserve it
# (tests/test_device_cache.py asserts the invariance).
SUPPORTS_ZERO_WEIGHT_ROWS = True


@partial(jax.jit, static_argnames=("k",))
def pca_fit(X: jax.Array, w: jax.Array, k: int):
    """Distributed PCA fit.

    X: (N_pad, d) rows sharded over the data axis, zero-padded.
    w: (N_pad,) validity weights (0 for padded rows).
    Returns (mean (d,), components (k,d), explained_variance (k,),
             explained_variance_ratio (k,), singular_values (k,)).

    The d×d covariance keeps all FLOPs in one MXU-friendly matmul; the
    eigendecomposition of the small replicated matrix matches the
    reference's strategy (distributed cov + replicated eig,
    SURVEY.md §2.11 row 1).
    """
    wsum = w.sum()
    mean = (X * w[:, None]).sum(axis=0) / wsum
    from .precision import stats_precision

    # sqrt-weighted centering keeps cov = A^T A symmetric in one matmul;
    # padded rows have w=0 and drop out.  stats_precision(): f32-exact
    # covariance by default (cuML parity; see ops/precision.py)
    A = (X - mean) * jnp.sqrt(w)[:, None]
    cov = jnp.matmul(A.T, A, precision=stats_precision()) / (wsum - 1.0)
    evals, evecs = jnp.linalg.eigh(cov)  # ascending order
    evals = evals[::-1]
    evecs = evecs[:, ::-1]
    components = _svd_flip(evecs[:, :k].T)  # (k, d), deterministic sign
    explained_variance = jnp.clip(evals[:k], 0.0, None)
    total_var = jnp.clip(evals, 0.0, None).sum()
    explained_variance_ratio = explained_variance / total_var
    singular_values = jnp.sqrt(explained_variance * (wsum - 1.0))
    return mean, components, explained_variance, explained_variance_ratio, singular_values


# ---------------------------------------------------------------------------
# Randomized (Halko) range-finder solver — the k<<d tradeoff the
# reference's cuML MG path makes: Gram work scales O(n d l) with
# l = k + oversamples instead of O(n d^2).  conf `pca_solver`
# (auto|full|randomized) + `pca_oversamples` + `pca_power_iters`.
# ---------------------------------------------------------------------------

def resolve_pca_solver(d: int, k: int, streamed: bool = False):
    """(solver, l, power_iters, reason) from the `pca_solver` conf.

    "auto" picks the randomized range-finder when its total Gram work —
    (2 + power_iters) passes at O(n d l) each — still undercuts the full
    O(n d^2) covariance by >= 4x, i.e. when d >= 4·l·(2 + power_iters);
    otherwise the exact full solver (identical to cuML PCAMG).
    `streamed=True` (the fused/streaming paths, where every randomized
    pass RE-READS the source — chunk decode is not free like a resident
    array) demands a 16x margin before auto switches.  The decision is
    the run's `pca_solver` fact, the fit report's `solver_decision`."""
    from ..config import get_config
    from ..tracing import fact

    mode = str(get_config("pca_solver")).lower()
    if mode not in ("auto", "full", "randomized"):
        raise ValueError(
            f"pca_solver must be auto|full|randomized, got {mode!r}"
        )
    oversamples = max(int(get_config("pca_oversamples")), 0)
    power_iters = max(int(get_config("pca_power_iters")), 0)
    l = min(k + oversamples, d)
    margin = 16 if streamed else 4
    threshold = margin * l * (2 + power_iters)
    if mode == "randomized":
        solver, reason = "randomized", "forced"
    elif mode == "full":
        solver, reason = "full", "forced"
    elif l < d and d >= threshold:
        solver, reason = "randomized", f"auto:d>={threshold}"
    else:
        solver, reason = "full", f"auto:d<{threshold}"
    fact(
        "pca_solver", solver=solver, reason=reason,
        d=int(d), k=int(k), l=int(l), power_iters=int(power_iters),
    )
    return solver, l, power_iters, reason


def _svd_flip(components, xp=jnp):
    """Deterministic sign: largest-|.| element of each component positive
    (cuML's signFlip, reference deprecated/native rapidsml_jni.cu:35;
    same convention as sklearn's svd_flip on components).  ONE owner for
    every solver — full, randomized, and the host (float64) streamed
    finalization (`xp=np`) — so components always compare 1:1 across
    paths."""
    k = components.shape[0]
    flip_idx = xp.argmax(xp.abs(components), axis=1)
    signs = xp.sign(components[xp.arange(k), flip_idx])
    signs = xp.where(signs == 0, 1.0, signs)
    return components * signs[:, None]


@partial(jax.jit, static_argnames=("k", "l", "power_iters"))
def pca_fit_randomized(
    X: jax.Array, w: jax.Array, k: int, l: int, power_iters: int
):
    """Randomized PCA fit on staged (row-sharded) data.

    Same contract and return signature as `pca_fit`, but the spectrum is
    extracted from an l-dimensional sketch: Y = (A^T A) Ω for a fixed
    Gaussian Ω (deterministic seed — same data, same components), then
    `power_iters` QR-renormalized subspace iterations, a final
    orthonormal basis Q, and the exact eigendecomposition of the small
    Q-projected covariance B^T B (B = A Q).  Every tall-skinny product is
    one MXU matmul over the sharded rows (XLA psums over ICI); only
    (d, l) / (l, l) intermediates replicate.  Total variance (for the
    explained-variance ratio) comes exactly from the per-column moments,
    no d x d matrix ever exists."""
    wsum = w.sum()
    mean = (X * w[:, None]).sum(axis=0) / wsum
    from .precision import stats_precision

    hi = stats_precision()
    A = (X - mean) * jnp.sqrt(w)[:, None]
    # deterministic sketch: a fixed key keeps refits of the same data
    # bit-identical (the fit must not be a random variable of wall time)
    omega = jax.random.normal(jax.random.PRNGKey(0), (X.shape[1], l), X.dtype)
    Y = jnp.matmul(A.T, jnp.matmul(A, omega, precision=hi), precision=hi)
    for _ in range(power_iters):
        Q, _ = jnp.linalg.qr(Y)
        Y = jnp.matmul(A.T, jnp.matmul(A, Q, precision=hi), precision=hi)
    Q, _ = jnp.linalg.qr(Y)  # (d, l) orthonormal range basis
    B = jnp.matmul(A, Q, precision=hi)  # (n, l)
    C = jnp.matmul(B.T, B, precision=hi) / (wsum - 1.0)  # (l, l)
    evals, evecs = jnp.linalg.eigh(C)  # ascending
    evals = evals[::-1]
    evecs = evecs[:, ::-1]
    components = _svd_flip((Q @ evecs)[:, :k].T)  # (k, d)
    explained_variance = jnp.clip(evals[:k], 0.0, None)
    # exact trace of the covariance from per-column moments
    total_var = (A * A).sum() / (wsum - 1.0)
    explained_variance_ratio = explained_variance / total_var
    singular_values = jnp.sqrt(explained_variance * (wsum - 1.0))
    return mean, components, explained_variance, explained_variance_ratio, singular_values


def pca_attrs_from_projected(
    Q: "jax.Array",
    SQ: "jax.Array",
    s1: "jax.Array",
    ssq: "jax.Array",
    sw: float,
    k: int,
):
    """Host (float64) finalization of the STREAMED randomized fit: the
    fused engine accumulates SQ = Σ w x (xᵀQ) per chunk
    (ops/stats.py `pca_projected_acc`), and this recovers the same small
    eigenproblem `pca_fit_randomized` solves on resident data —
    B^T B = Qᵀ (A^T A) Q with A^T A Q = SQ − sw·mean·(meanᵀQ).

    Returns (mean, components, explained_variance, ratio,
    singular_values) as float64 numpy arrays."""
    import numpy as np

    from .stats import total_variance

    Q = np.asarray(Q, np.float64)
    SQ = np.asarray(SQ, np.float64)
    s1 = np.asarray(s1, np.float64)
    sw = float(sw)
    mean = s1 / sw
    Yc = SQ - sw * np.outer(mean, mean @ Q)  # (A^T A) Q, centered
    C = (Q.T @ Yc) / max(sw - 1.0, 1.0)
    C = 0.5 * (C + C.T)  # symmetrize fp residue before eigh
    evals, evecs = np.linalg.eigh(C)
    evals = evals[::-1]
    evecs = evecs[:, ::-1]
    components = _svd_flip((Q @ evecs)[:, :k].T, xp=np)
    ev = np.clip(evals[:k], 0.0, None)
    total = max(total_variance(np.asarray(ssq), s1, sw), 1e-300)
    evr = ev / total
    sv = np.sqrt(ev * max(sw - 1.0, 0.0))
    return mean, components, ev, evr, sv


@jax.jit
def pca_transform(X: jax.Array, components: jax.Array):
    """Spark-semantics projection: X @ PC^T with NO mean removal.  cuML
    centers and the reference adds mean@PC^T back to match Spark
    (feature.py:447-459); projecting the raw X is the same result in one
    matmul."""
    return X @ components.T
