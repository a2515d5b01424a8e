#
# PCA kernel — the TPU-native replacement for `cuml.decomposition.pca_mg.
# PCAMG.fit` (called from reference feature.py:240-261).  The cuML MG kernel
# computes a distributed covariance then an eigendecomposition with NCCL
# reductions; here the second moments of the row-sharded data come from the
# rows as they lie (the linear-regression Gram's programs, about a shift
# near the mean, one sum across chips) and the top-k eigenpairs of the full
# (d,d) covariance from a block iteration that starts on the devices, where
# those moments lie, and ends in float64 on the host, where a residual test
# accepts the pairs or hands the matrix to LAPACK (`pca_eigensolve_resident`).
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


# `auto` keeps the exact route for resident rows while BOTH of its costs are
# a fraction of a second.  A device's share of the Gram, rows x d^2
# multiply-adds, is at most `_EXACT_RESIDENT_GRAM`: 1M x 3000 is 0.9e13 and
# 0.36 s of a v5e's time (PERF.md §5).  And d is at most
# `_EXACT_RESIDENT_COLS`, since nothing of the eigensolve shrinks with the
# rows: a (d,d) float32 Gram on every device, its float64 copy on the host
# and 4/3 d^3 FLOP of dsyevr there, 0.28 s at 3,000 columns on the chip's
# host (PERF.md §6), so by d^3 0.7 s, 67 MB and 134 MB at 4,096, where
# 50,000 columns would be 10 GB, 20 GB and an hour.  Inside both the exact
# answer costs what the range-finder's saving does not repay; past either
# the rule is the threshold below, as it was.
_EXACT_RESIDENT_GRAM = 1 << 44
_EXACT_RESIDENT_COLS = 4096


@jax.jit
def _pca_covariance_shift(X: jax.Array, w: jax.Array):
    """The weighted column means in the rows' own precision: the shift the
    covariance pass subtracts from every row as it reads it.  One read of
    the rows in place (a product with `w` fused into the sum)."""
    with jax.named_scope("pca_covariance"):
        return (X * w[:, None]).sum(axis=0) / w.sum()


def pca_scatter(X: jax.Array, w: jax.Array):
    """(scatter (d,d), s1 (d,), sw, shift (d,)) of the resident rows, on
    the devices: scatter = sum_i w_i (x_i - shift)(x_i - shift)^T and
    s1 = sum_i w_i (x_i - shift), from two reads of the rows where they
    lie and no centred copy of them.

    The second moments are the linear-regression Gram of rows without
    labels (`ops/linear.linreg_sufficient_stats`: the same row-block
    programs, `split_gram_half` where `gram_kernel_plan` says so, its
    `linreg_gram_kernel[...]` instant), so zero-weight rows are absent.
    They are taken about `shift`, the mean as float32 sums give it, so a
    column at 100 +- 1 loses no digit of its variance to cancellation;
    what the shift misses of the true mean, delta = s1 / sw, the host
    removes exactly: covariance = (scatter - sw delta delta^T) / (sw - 1)
    (`pca_eigensolve_host`)."""
    from .linear import linreg_sufficient_stats

    shift = _pca_covariance_shift(X, w)
    scatter, s1, sw = linreg_sufficient_stats(X, w, None, shift=shift)
    return scatter, s1, sw, shift


def pca_eigensolve_host(scatter, s1, sw: float, shift, k: int):
    """The top-k eigenpairs of the full covariance, in float64 on the host,
    from `pca_scatter`'s statistics as fetched: LAPACK's dsyevr (a
    tridiagonalisation of the whole matrix, then only the k pairs asked
    for) on the one float64 buffer the centring makes.  Through scipy's
    LAPACK, as the ridge solve (`ops/linear._quadratic_form` has why).

    Returns (mean, components (k,d), explained_variance,
    explained_variance_ratio, singular_values), float64: signs by
    `_svd_flip`, the variances over sw - 1, the ratio over the covariance's
    exact trace.  The streamed, fused and CSR fits finish here too, with
    no shift (`models/feature.PCA._attrs_from_moments`): their ratio was
    over the sum of ALL the eigenvalues, clipped at zero, which is the
    trace but for the negative ones rounding leaves (1e-16 of it)."""
    import numpy as np
    from scipy.linalg import eigh

    from .linear import _fortran_view, _normal_system

    sw = float(sw)
    delta = np.asarray(s1, np.float64) / sw
    mean = np.asarray(shift, np.float64) + delta
    A = _normal_system(np.asarray(scatter), sw, delta, None, 0.0)
    d = A.shape[0]
    total = float(np.trace(A))
    evals, evecs = eigh(
        _fortran_view(A), lower=True, overwrite_a=True, check_finite=False,
        subset_by_index=(d - k, d - 1), driver="evr",
    )
    components = _svd_flip(evecs[:, ::-1].T, xp=np)
    scattered = np.clip(evals[::-1], 0.0, None)
    ev = scattered / (sw - 1.0)
    evr = scattered / max(total, 1e-300)
    return mean, components, ev, evr, np.sqrt(scattered)


# ---------------------------------------------------------------------------
# The top k pairs of a RESIDENT covariance without a tridiagonalisation:
# subspace iteration in float32 on the devices that hold it, then a
# Rayleigh-Ritz polish in float64 on the host over the (d, b) block alone,
# held to a residual test; LAPACK (`pca_eigensolve_host`) is the fallback.
# ---------------------------------------------------------------------------

# The block is the k pairs and as many again, at least `_SUBSPACE_BLOCK`
# columns: the iteration gains lambda_{b+1} / lambda_k a step, so a wider
# block is fewer float64 sweeps of more columns each.  On a v5e's host at
# 3,000 columns with the reference's low-rank spectrum (PERF.md §6, PR 36)
# the whole eigensolve reads 50-54 ms at 16 columns (four sweeps), 47-51 at
# 32 (three; a seed in ten takes a fourth), 59-64 at 48 and 80-85 at 64
# (three): a sweep is 10-12 ms whatever the block, bound by the widened
# matrix's bytes, and the block's float64 QR grows from 1 to 10 ms.  The
# device loop is 1.9 ms for 16 steps at 32 columns (1.2 for 8, 3.2 for 32):
# float32 rounding over the gaps is reached by the eighth, and a spectrum
# that needs more than 16 does not finish inside the polish's cap either.
_SUBSPACE_BLOCK = 32
_SUBSPACE_DEVICE_STEPS = 16
# The route is tried from the width on at which it beat LAPACK on that host,
# k = 3 (dsyevr and its float64 copy against the device loop, the block's
# fetch and the polish): 2.4 ms against 3.9 at 256 columns, 4.9 against 5.5
# at 384, 8.5 against 5.8 at 512, 12.2 against 8.5 at 640, 32 against 11 at
# 1,024, 360 against 48 at 3,000.
_SUBSPACE_MIN_COLS = 512
# A pair is accepted when its residual over its gap to the nearest other Ritz
# value, which bounds the sine of its angle to the eigenvector, is at most
# this: a quarter of the float32 rounding the answers are cast to.  The
# polish makes at most `_SUBSPACE_POLISH_CAP` sweeps.
_SUBSPACE_TOL = 1e-8
_SUBSPACE_POLISH_CAP = 6
# float64 elements to a block of the polish's widening: 4 MB, which that
# host's cache holds between the block's three products (a sweep at 32
# columns: 13.7 ms with 2 MB blocks, 11.5 with 4, 10.9 with 8, 37 with 16)
_WIDEN_BLOCK = 1 << 19


def subspace_plan(d: int, k: int):
    """(block, device steps, why not) for the top k pairs of a (d,d)
    covariance, from k and d alone: the block iteration's shape, or block
    0 where LAPACK keeps the matrix and the reason its instant carries.
    The route is tried where the matrix is `_SUBSPACE_MIN_COLS` wide and
    the block (whole sublanes of 8) at most a quarter of it: k = None, k
    near d and every narrow matrix, whose tridiagonalisation is micro- to
    milliseconds, never trace the device program."""
    block = max(_SUBSPACE_BLOCK, 8 * -(-2 * k // 8))
    if d < _SUBSPACE_MIN_COLS:
        return 0, 0, f"narrow: d={d}<{_SUBSPACE_MIN_COLS}"
    if 4 * block > d:
        return 0, 0, f"k>d/8: k={k}, a block of {block}>d/4={d / 4:g}"
    return block, _SUBSPACE_DEVICE_STEPS, ""


@partial(jax.jit, static_argnames=("block", "steps"))
def _pca_subspace_iterate(scatter: jax.Array, block: int, steps: int):
    """`steps` of Q <- orth(scatter Q) from a fixed-key Gaussian (d, block)
    start (the same rows give the same bits), on the devices that hold
    `pca_scatter`'s (d,d) matrix, in float32 whatever its dtype (a start
    needs no more), the products at `highest`: an orthonormal (d, block)
    basis whose span holds the top eigenvectors to float32 rounding over
    their gaps.  The scatter is about the float32 mean, so the rank-one
    centring it lacks is rounding-sized; the polish applies it exactly."""
    with jax.named_scope("pca_subspace"):
        scatter = scatter.astype(jnp.float32)
        start = jax.random.normal(
            jax.random.PRNGKey(0), (scatter.shape[0], block), jnp.float32)

        def step(_, Q):
            return jnp.linalg.qr(
                jnp.matmul(scatter, Q, precision=jax.lax.Precision.HIGHEST))[0]

        return jax.lax.fori_loop(0, steps, step, start)


def _centred_product(G, sw: float, delta, Qt):
    """(A Q)^T for Q^T = `Qt` (b,d), Fortran-ordered float64, where
    A = sym(G) - sw delta delta^T and sym(G) is the symmetric matrix whose
    lower triangle is the Fortran-ordered view `G`'s: the triangle LAPACK
    reads in `pca_eigensolve_host`, so a Gram whose [i,j] and [j,i] were
    rounded apart (XLA's product) is one matrix on both routes.  `G` is
    widened a block of columns at a time from the diagonal down
    (`_WIDEN_BLOCK`): never a (d,d) float64 array, and half the widening.
    Through scipy's BLAS (`ops/linear._quadratic_form` has why)."""
    import numpy as np
    from scipy.linalg.blas import dgemm, dgemv, dger, dsymm

    d = G.shape[0]
    Wt = np.zeros_like(Qt)
    cols = max(1, _WIDEN_BLOCK // d)
    for lo in range(0, d, cols):
        hi = min(lo + cols, d)
        # the diagonal block, of which BLAS reads the lower triangle ...
        D = np.array(G[lo:hi, lo:hi], dtype=np.float64, order="F")
        dsymm(1.0, D, Qt[:, lo:hi], beta=1.0, c=Wt[:, lo:hi], side=1, lower=1,
              overwrite_c=1)
        if hi < d:
            # ... and the rows below it, as they lie and mirrored
            L = np.array(G[hi:, lo:hi], dtype=np.float64, order="F")
            dgemm(1.0, Qt[:, lo:hi], L, beta=1.0, c=Wt[:, hi:], trans_b=1, overwrite_c=1)
            dgemm(1.0, Qt[:, hi:], L, beta=1.0, c=Wt[:, lo:hi], overwrite_c=1)
    return dger(-sw, dgemv(1.0, Qt, delta), delta, a=Wt, overwrite_a=1)


def pca_eigensolve_polished(scatter, s1, sw: float, shift, k: int, start):
    """`pca_eigensolve_host`'s pairs of the same statistics, value for
    value, from a (d,b) block `start` whose span is near the top
    eigenvectors (`_pca_subspace_iterate`'s, as fetched), or None: in
    float64, repeat Q = orth(Q), W = A Q (`_centred_product`: the exact
    centring as a rank-one term), the (b,b) Rayleigh-Ritz problem of
    Q^T W, next Q = W, until each of the top k Ritz pairs' residual
    |A v - theta v| over its gap to the nearest other Ritz value is at
    most `_SUBSPACE_TOL`.  dsyevr is an iteration run to a tolerance too;
    this one is held to a float64 residual of the same matrix.

    Returns (answer or None, sweeps made, the last bound).  None where
    `_SUBSPACE_POLISH_CAP` sweeps do not reach the tolerance, or cannot at
    the rate the bound falls: eigenvalues without gaps (iid rows:
    lambda_{b+1} / lambda_k near 1; a multiple top eigenvalue) are
    LAPACK's."""
    import numpy as np
    from scipy.linalg import eigh, qr
    from scipy.linalg.blas import dgemm

    from ..tracing import trace
    from .linear import _fortran_view

    sw = float(sw)
    delta = np.asarray(s1, np.float64) / sw
    G = _fortran_view(np.asarray(scatter))
    Q = np.asarray(start, np.float64)
    bound = np.float64(np.inf)
    for sweep in range(1, _SUBSPACE_POLISH_CAP + 1):
        # one span a sweep (their count is the sweeps'): the host working
        with trace("pca_polish_sweep", detail="work"):
            Q = qr(Q, mode="economic", overwrite_a=True, check_finite=False)[0]
            Qt = np.asfortranarray(Q.T)
            Wt = _centred_product(G, sw, delta, Qt)
            theta, S = eigh(dgemm(1.0, Qt, Wt, trans_b=1), check_finite=False)
            theta, top = theta[::-1], S[:, ::-1][:, :k]
            Vt = dgemm(1.0, top, Qt, trans_a=1)  # (k,d): the Ritz vectors
            Rt = dgemm(1.0, top, Wt, trans_a=1) - theta[:k, None] * Vt
            apart = np.abs(theta[:k, None] - theta[None, :])
            apart[np.arange(k), np.arange(k)] = np.inf
            with np.errstate(all="ignore"):
                last, bound = bound, np.max(np.linalg.norm(Rt, axis=1) / apart.min(axis=1))
                # The bound falls by `bound / last` a sweep at best (the fast
                # modes die first): where the sweeps left cannot reach the
                # tolerance at that rate the try ends here, which is at the
                # cap, at a bound that stopped falling (or is no number), and
                # two sweeps into a spectrum without gaps.
                reach = bound * (bound / last) ** (_SUBSPACE_POLISH_CAP - sweep)
        if bound <= _SUBSPACE_TOL:
            break
        if not reach <= _SUBSPACE_TOL:
            return None, sweep, float(bound)
        Q = Wt.T
    total = float(np.asarray(G.diagonal(), np.float64).sum() - sw * (delta @ delta))
    scattered = np.clip(theta[:k], 0.0, None)
    mean = np.asarray(shift, np.float64) + delta
    return (
        (mean, _svd_flip(Vt, xp=np), scattered / (sw - 1.0),
         scattered / max(total, 1e-300), np.sqrt(scattered)),
        sweep, float(bound),
    )


def pca_eigensolve_resident(device_scatter: jax.Array, scatter, s1, sw: float,
                            shift, k: int):
    """The top-k eigenpairs of the full covariance from `pca_scatter`'s
    statistics, on the devices (`device_scatter`) and as fetched: the
    block iteration where `subspace_plan` tries it and its polish accepts,
    else LAPACK on the same fetched matrix, the failed try paid (a device
    program of milliseconds and at most `_SUBSPACE_POLISH_CAP` sweeps).
    Which one answered is the fit's instant
    `pca_eigensolver[subspace_polished|host_lapack]`; no conf key and no
    name of a data model decides it.  Returns `pca_eigensolve_host`'s
    tuple."""
    import numpy as np

    from ..tracing import event, trace

    d = int(scatter.shape[0])
    block, steps, why = subspace_plan(d, k)
    if block:
        # dispatch of the device loop to its block on the host: a wait
        with trace("pca_subspace_device", detail="wait"):
            start = np.asarray(_pca_subspace_iterate(device_scatter, block, steps))
        out, sweeps, bound = pca_eigensolve_polished(scatter, s1, sw, shift, k, start)
        tried = (f"block={block} device_steps={steps} polish_steps={sweeps} "
                 f"estimate={bound:.3g}")
        if out is not None:
            event(
                "pca_eigensolver[subspace_polished]",
                detail=f"{tried}: top {k} of the ({d},{d}) {scatter.dtype} covariance, "
                f"residual over gap <= {_SUBSPACE_TOL:g} in float64",
            )
            return out
        why = f"not_converged: {tried}"
    event(
        "pca_eigensolver[host_lapack]",
        detail=f"{why}: dsyevr in float64 on the fetched ({d},{d}) "
        f"{scatter.dtype} covariance, top {k}",
    )
    with trace("pca_lapack", detail="work"):
        return pca_eigensolve_host(scatter, s1, sw, shift, k)


# ---------------------------------------------------------------------------
# Randomized (Halko) range-finder solver — the k<<d tradeoff the
# reference's cuML MG path makes: Gram work scales O(n d l) with
# l = k + oversamples instead of O(n d^2).  conf `pca_solver`
# (auto|full|randomized) + `pca_oversamples` + `pca_power_iters`.
# ---------------------------------------------------------------------------

def resolve_pca_solver(d: int, k: int, streamed: bool = False,
                       resident_rows: int = None):
    """(solver, l, power_iters, reason) from the `pca_solver` conf.

    "auto" picks the randomized range-finder when its total Gram work —
    (2 + power_iters) passes at O(n d l) each — still undercuts the full
    O(n d^2) covariance by >= 4x, i.e. when d >= 4·l·(2 + power_iters);
    otherwise the exact full solver (identical to cuML PCAMG).
    `streamed=True` (the fused/streaming paths, where every randomized
    pass RE-READS the source — chunk decode is not free like a resident
    array) demands a 16x margin before auto switches.  `resident_rows`
    (the rows one device holds of a resident fit): while its share of the
    exact Gram, rows x d^2, is within `_EXACT_RESIDENT_GRAM` and d within
    `_EXACT_RESIDENT_COLS` (the (d,d) matrix and the host's d^3
    eigensolve do not shrink with the rows), both a fraction of a second,
    auto gives the reference's exact answer and not a 13-column sketch's
    (on the reference's low-rank rows that sketch is 1e-3 off where
    float32 is 1e-6, PERF.md §4); wider or longer resident rows keep the
    threshold.  The decision is the run's `pca_solver` fact, the fit
    report's `solver_decision`; its `reason` carries both bounds."""
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
    elif (resident_rows is not None and d <= _EXACT_RESIDENT_COLS
          and resident_rows * d * d <= _EXACT_RESIDENT_GRAM):
        solver, reason = "full", (
            f"auto:resident {int(resident_rows)}x{d}^2"
            f"<=2^{_EXACT_RESIDENT_GRAM.bit_length() - 1},d<={_EXACT_RESIDENT_COLS}")
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

    X: (N_pad, d) rows sharded over the data axis, zero-padded; w: (N_pad,)
    validity weights (0 for padded rows).  Returns (mean (d,), components
    (k,d), explained_variance (k,), explained_variance_ratio (k,),
    singular_values (k,)), as the exact route does, but the spectrum is
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
