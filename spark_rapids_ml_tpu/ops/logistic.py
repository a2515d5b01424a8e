#
# Logistic regression kernel — the TPU-native replacement for
# `LogisticRegressionMG` (L-BFGS/OWL-QN, reference classification.py:
# 1046-1081).  The loss/grad evaluate over the row-sharded global arrays
# (logits are one MXU matmul for dense rows, a gather-contract for ELL
# sparse rows; XLA psums the gradient over ICI — the NCCL allreduce inside
# the cuML kernel), and ops/lbfgs.py runs the whole solver as one compiled
# while_loop.
#
# Spark objective (matched): 1/Σw · Σᵢ wᵢ·logloss(xᵢ,yᵢ) +
#   regParam·[α‖β‖₁ + (1-α)/2‖β‖²], intercepts unpenalized; with
# standardization=True the penalty applies to standardized coefficients
# (features are standardized on-device up front, coefficients un-scaled
# after the solve — the reference does the same via _standardize_dataset,
# classification.py:1018-1028 + utils.py:876-982).
#
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .lbfgs import lbfgs_minimize

# Sample-weight/fold-mask contract (parallel/device_cache.py): the loss,
# gradient, label range, and standardization moments all weight rows by
# `w` and normalize by w.sum(), so a w=0 row — zero padding OR a CV
# fold-mask hole — is mathematically absent from the optimization.  The
# device cache's masked fold views rely on this; new reductions must
# preserve it (tests/test_device_cache.py asserts the invariance).
SUPPORTS_ZERO_WEIGHT_ROWS = True


def _theta_layout(C: int, d: int, dtype, fit_intercept: bool):
    """Single source of truth for the packed-theta layout — coefficients
    first, then intercepts — shared by the problem builders (fused
    solvers) and `logreg_fit_host_dispatch`.  C=1 is the binomial
    single-β family (scalar intercept); C>1 the softmax multinomial.
    Returns (n_coef, n_param, l1_mask, unpack)."""
    n_coef = C * d
    n_param = n_coef + (C if fit_intercept else 0)

    def unpack(theta):
        if C == 1:
            beta = theta[:d]
            b = theta[d] if fit_intercept else jnp.asarray(0.0, dtype)
            return beta, b
        Wm = theta[:n_coef].reshape(C, d)
        b = theta[n_coef:] if fit_intercept else jnp.zeros((C,), dtype)
        return Wm, b

    l1_mask = jnp.concatenate(
        [jnp.ones((n_coef,), dtype)]
        + ([jnp.zeros((n_param - n_coef,), dtype)] if fit_intercept else [])
    )
    return n_coef, n_param, l1_mask, unpack


def _eval_scope(loss_fn: Callable) -> Callable:
    """`loss_fn` under the named scope `lbfgs_eval`: the operations of the
    value and (through autodiff) of the gradient carry it in their
    metadata on both routes, so a profile finds the evaluation's kernels
    by a name that no rename of a jitted function changes.  Metadata only:
    the computation is the same."""

    def scoped(theta):
        with jax.named_scope("lbfgs_eval"):
            return loss_fn(theta)

    return scoped


def _binary_problem(
    margin_fn: Callable,  # beta (d,) -> margins (N_pad,)
    d: int,
    dtype,
    w: jax.Array,
    y: jax.Array,
    l2: float,
    fit_intercept: bool,
    one_pass: Optional[Callable] = None,
):
    """(loss_fn, unpack, l1_mask, n_param) for the Spark binomial family:
    a single coefficient vector β with margin m(x)+b and penalty on β
    (NOT the softmax-2 form, whose L2 optimum differs by a factor of 2 in
    the penalty).  Shared by the fused while_loop solver and the
    host-dispatched solver.

    `one_pass(w, sgn)`, where given, builds the data term in place of
    `margin_fn` and autodiff: (β, b) -> Σᵢ wᵢ·softplus(-sgnᵢ·mᵢ) with its
    own gradient (`ops/pallas_logistic.one_pass_data_term`, one read of
    the rows an evaluation where autodiff makes two)."""
    wsum = w.sum()
    sgn = 2.0 * y.astype(dtype) - 1.0  # {-1, +1}
    _, n_param, l1_mask, unpack = _theta_layout(1, d, dtype, fit_intercept)
    data_term = one_pass(w, sgn) if one_pass is not None else None

    def loss_fn(theta):
        beta, b = unpack(theta)
        if data_term is not None:
            nll_sum = data_term(beta, b)
        else:
            margin = margin_fn(beta) + b
            # log(1 + exp(-sgn*margin)), numerically stable via softplus
            nll_sum = (jax.nn.softplus(-sgn * margin) * w).sum()
        data_loss = nll_sum / wsum
        reg = 0.5 * l2 * (beta * beta).sum()
        return data_loss + reg

    return loss_fn, unpack, l1_mask, n_param


def _solve_binary(
    margin_fn: Callable,  # beta (d,) -> margins (N_pad,)
    d: int,
    dtype,
    w: jax.Array,
    y: jax.Array,
    l2: float,
    l1: float,
    fit_intercept: bool,
    tol: float,
    max_iter: int,
    history: int,
    ls_max: int,
    one_pass: Optional[Callable] = None,
):
    loss_fn, unpack, l1_mask, n_param = _binary_problem(
        margin_fn, d, dtype, w, y, l2, fit_intercept, one_pass
    )
    theta0 = jnp.zeros((n_param,), dtype)
    res = lbfgs_minimize(
        _eval_scope(loss_fn), theta0, max_iter=max_iter, tol=tol, history=history,
        l1=l1, l1_mask=l1_mask, ls_max=ls_max,
    )
    beta, b = unpack(res.w)
    return beta, b, res.f, res.n_iter, res.history_f


def _multinomial_problem(
    logits_fn: Callable,  # W (C,d) -> logits (N_pad, C)
    C: int,
    d: int,
    dtype,
    w: jax.Array,
    y: jax.Array,
    l2: float,
    fit_intercept: bool,
):
    """(loss_fn, unpack, l1_mask, n_param) for the softmax multinomial
    objective, shared by the fused and host-dispatched solvers."""
    wsum = w.sum()
    y1h = jax.nn.one_hot(y, C, dtype=dtype)
    _, n_param, l1_mask, unpack = _theta_layout(C, d, dtype, fit_intercept)

    def loss_fn(theta):
        Wm, b = unpack(theta)
        logits = logits_fn(Wm) + b
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -(y1h * logp).sum(axis=1)  # padding rows weighted 0
        data_loss = (nll * w).sum() / wsum
        reg = 0.5 * l2 * (Wm * Wm).sum()
        return data_loss + reg

    return loss_fn, unpack, l1_mask, n_param


def _solve_multinomial(
    logits_fn: Callable,  # W (C,d) -> logits (N_pad, C)
    C: int,
    d: int,
    dtype,
    w: jax.Array,
    y: jax.Array,
    l2: float,
    l1: float,
    fit_intercept: bool,
    tol: float,
    max_iter: int,
    history: int,
    ls_max: int,
):
    """Softmax multinomial solver body shared by the dense and ELL kernels."""
    loss_fn, unpack, l1_mask, n_param = _multinomial_problem(
        logits_fn, C, d, dtype, w, y, l2, fit_intercept
    )
    theta0 = jnp.zeros((n_param,), dtype)
    res = lbfgs_minimize(
        _eval_scope(loss_fn), theta0, max_iter=max_iter, tol=tol, history=history,
        l1=l1, l1_mask=l1_mask, ls_max=ls_max,
    )
    Wm, b = unpack(res.w)
    return Wm, b, res.f, res.n_iter, res.history_f


@partial(
    jax.jit,
    static_argnames=("n_classes", "fit_intercept", "max_iter", "history", "ls_max"),
)
def logreg_fit(
    X: jax.Array,
    w: jax.Array,
    y: jax.Array,
    n_classes: int,
    l2: float,
    l1: float,
    fit_intercept: bool = True,
    tol: float = 1e-6,
    max_iter: int = 100,
    history: int = 10,
    ls_max: int = 20,
):
    """Multinomial (n_classes>=2) logistic regression via L-BFGS/OWL-QN.

    X (N_pad,d) row-sharded (already standardized if requested); w validity*
    sample weights; y int class ids (0 on padding).

    Returns (W (n_classes,d), b (n_classes,), loss, n_iter).
    """
    # solver state never drops below f32 (bf16 feature STORAGE is fine —
    # the matmul accumulates f32 — but bf16 L-BFGS curvature pairs are not)
    dtype = jnp.promote_types(X.dtype, jnp.float32)
    return _solve_multinomial(
        lambda Wm: X @ Wm.T, n_classes, X.shape[1], dtype, w, y,
        l2, l1, fit_intercept, tol, max_iter, history, ls_max,
    )


def _one_pass_builder(one_pass, X):
    """`_binary_problem`'s `one_pass` for the rows `X`: None without a plan
    (`ops/pallas_logistic.one_pass_plan`)."""
    if one_pass is None:
        return None
    from .pallas_logistic import one_pass_data_term

    return partial(one_pass_data_term, one_pass, X)


def one_pass_program_bytes(shard_rows: int, d: int, history: int) -> int:
    """Upper bound of the bytes one device holds beside its `shard_rows` x
    `d` shard of the rows while the fused fit runs with a one-pass plan
    (`logreg_fit_binary(..., one_pass=plan)`), from its shapes: eight
    rows-length 4-byte vectors (the arguments `w` and `y`; `sgn`, their
    (1, rows) views and the margins' row as temporaries), the kernel's two
    (d, 128) accumulators and the L-BFGS state (2 x `history` pairs and a
    few vectors of d + 1).  Compiled for a v5e the temporaries read 14 B a
    row: 14.7 MB at 1M x 3000, 23.5 MB with `w` and `y`, where this gives
    35.4 MB (`tests/test_pallas_logistic.py` holds every compile under it)."""
    return 4 * (8 * shard_rows + 2 * 128 * d + (2 * history + 8) * (d + 1))


@partial(
    jax.jit,
    static_argnames=("fit_intercept", "max_iter", "history", "ls_max", "one_pass"),
)
def logreg_fit_binary(
    X: jax.Array,
    w: jax.Array,
    y: jax.Array,
    l2: float,
    l1: float,
    fit_intercept: bool = True,
    tol: float = 1e-6,
    max_iter: int = 100,
    history: int = 10,
    ls_max: int = 20,
    one_pass=None,
):
    """Dense binary fit; returns (coef (d,), intercept, loss, n_iter).
    `one_pass`: the plan `ops/pallas_logistic.one_pass_plan` read from
    these rows, None for autodiff."""
    dtype = jnp.promote_types(X.dtype, jnp.float32)
    return _solve_binary(
        lambda beta: X @ beta, X.shape[1], dtype, w, y,
        l2, l1, fit_intercept, tol, max_iter, history, ls_max,
        _one_pass_builder(one_pass, X),
    )


@partial(
    jax.jit,
    static_argnames=("d", "fit_intercept", "max_iter", "history", "ls_max"),
)
def logreg_fit_binary_ell(
    vals: jax.Array,  # (N_pad, K) ELL values, row-sharded
    cols: jax.Array,  # (N_pad, K) int32 column ids
    w: jax.Array,
    y: jax.Array,
    l2: float,
    l1: float,
    d: int = 0,
    fit_intercept: bool = True,
    tol: float = 1e-6,
    max_iter: int = 100,
    history: int = 10,
    ls_max: int = 20,
):
    """Binary logistic regression over ELL sparse features (the analog of
    the reference's CSR LogisticRegressionMG path, classification.py:
    1054-1055).  The margin is a gather-contract; autodiff turns its
    transpose into the scatter-add gradient, psum'd across shards."""
    from .sparse import ell_matvec

    return _solve_binary(
        lambda beta: ell_matvec(vals, cols, beta), d, vals.dtype, w, y,
        l2, l1, fit_intercept, tol, max_iter, history, ls_max,
    )


@partial(
    jax.jit,
    static_argnames=("n_classes", "d", "fit_intercept", "max_iter", "history",
                     "ls_max"),
)
def logreg_fit_ell(
    vals: jax.Array,
    cols: jax.Array,
    w: jax.Array,
    y: jax.Array,
    n_classes: int,
    l2: float,
    l1: float,
    d: int = 0,
    fit_intercept: bool = True,
    tol: float = 1e-6,
    max_iter: int = 100,
    history: int = 10,
    ls_max: int = 20,
):
    """Multinomial logistic regression over ELL sparse features."""
    from .sparse import ell_matmat

    return _solve_multinomial(
        lambda Wm: ell_matmat(vals, cols, Wm), n_classes, d, vals.dtype, w, y,
        l2, l1, fit_intercept, tol, max_iter, history, ls_max,
    )


def logreg_fit_host_dispatch(
    X: jax.Array,
    w: jax.Array,
    y: jax.Array,
    n_classes: int,
    l2: float,
    l1: float,
    fit_intercept: bool = True,
    tol: float = 1e-6,
    max_iter: int = 100,
    history: int = 10,
    ls_max: int = 20,
    binomial: bool = False,
    margin_fn: Callable = None,
    logits_fn: Callable = None,
    d: int = None,
    data=None,
    checkpoint_path: str = None,
    checkpoint_tag: str = "",
    one_pass=None,
):
    """HOST-driven L-BFGS over device-RESIDENT data: one dispatched
    value+grad program per evaluation instead of the whole solve in one
    while_loop program (`logreg_fit`/`logreg_fit_binary`).

    The fused solver's single program runs max_iter x line-search
    evaluations of device time — at e.g. the reference benchmark config
    (1M x 3000, maxIter=200, run_benchmark.sh:152-160) that is ~5e12+
    FLOPs, past the per-program budget (`dispatch_flops_limit` — sized
    for a development link that no longer exists, kept until
    re-justified on the chip or deleted, ROADMAP Design 3).  Here each
    dispatch is ONE
    evaluation (~2.4e10 FLOPs at that config) and the optimizer state
    lives on host — identical math via the shared problem builders, so
    the optimum matches the fused solver (same contract the
    epoch-streaming fit already satisfies).

    `margin_fn`/`logits_fn` take (data, beta|W) and `data` is the array
    pytree they consume (default: X itself).  Data MUST ride the jitted
    evaluation as arguments — jitting a closure over the concrete arrays
    captures them as lowered constants, which at the reference config is
    a 12 GB host-side materialization during lowering plus a 12 GB
    executable (jax's "large amount of constants were captured" warning);
    as arguments they stay device-resident buffers referenced per
    dispatch.

    `checkpoint_path`/`checkpoint_tag` flow to `lbfgs_minimize_host`:
    the optimizer state persists per accepted iteration and an
    interrupted fit resumes its trajectory (resilience/checkpoint.py).

    `one_pass`: the plan `ops/pallas_logistic.one_pass_plan` read from
    dense binomial rows `X`, None for autodiff.

    Returns (W (C,d) | coef (d,), b, loss, n_iter, history) matching the
    fused kernels' shapes for the same `binomial` flag.
    """
    import numpy as np

    from ..tracing import trace
    from .lbfgs import lbfgs_minimize_host

    dtype = jnp.promote_types(X.dtype, jnp.float32)
    if d is None:
        d = X.shape[1]
    operands = X if data is None else data
    mfn = margin_fn or (lambda dat, beta: dat @ beta)
    lfn = logits_fn or (lambda dat, Wm: dat @ Wm.T)

    # the packed layout's mask is made on the device and read back: a few
    # small programs and a fetch before the first evaluation
    with trace("lbfgs_layout", detail="work"):
        _, n_param, l1_mask, unpack = _theta_layout(
            1 if binomial else n_classes, d, dtype, fit_intercept
        )
        l1_mask = np.asarray(l1_mask, np.float64)

    @jax.jit
    def vg_fn(theta, dat, w_, y_):
        # problem built INSIDE the trace: dat/w_/y_ are tracers here, so
        # the shared builders close over arguments, not concrete arrays
        if binomial:
            loss_fn, _, _, _ = _binary_problem(
                lambda beta: mfn(dat, beta), d, dtype, w_, y_, l2,
                fit_intercept, _one_pass_builder(one_pass, dat),
            )
        else:
            loss_fn, _, _, _ = _multinomial_problem(
                lambda Wm: lfn(dat, Wm), n_classes, d, dtype, w_, y_, l2,
                fit_intercept,
            )
        return jax.value_and_grad(_eval_scope(loss_fn))(theta)

    def oracle(theta_np: np.ndarray):
        # one span per evaluation, dispatch to fetch: their count IS the
        # fit's evaluation count, and the first one holds the re-jit.
        # Beneath it the host works until the program is enqueued (the
        # arguments; in each fit's first the re-jit), then waits for its
        # value and gradient
        with trace("lbfgs_eval"):
            with trace("lbfgs_eval_dispatch", detail="work"):
                out = vg_fn(jnp.asarray(theta_np, dtype), operands, w, y)
            with trace("lbfgs_eval_wait", detail="wait"):
                f, g = jax.device_get(out)
        return float(f), np.asarray(g, np.float64)

    theta, n_iter, converged, hist = lbfgs_minimize_host(
        oracle,
        np.zeros((n_param,), np.float64),
        max_iter=max_iter,
        tol=tol,
        history=history,
        l1=l1,
        l1_mask=l1_mask,
        ls_max=ls_max,
        checkpoint_path=checkpoint_path,
        checkpoint_tag=checkpoint_tag,
    )
    # the host's answer goes back to the device in the fused kernels'
    # shapes (a put, the slices of `unpack`, the history's put), from where
    # the caller's `solve_fetch` brings it home again
    with trace("lbfgs_unpack", detail="work"):
        coef, b = unpack(jnp.asarray(theta, dtype))
        # hist already carries the FULL (penalty-inclusive) objective per
        # iteration; hist[-1] is the final loss — no recomputation pass
        out = coef, b, hist[-1], n_iter, jnp.asarray(hist, dtype)
    # this fit's evaluation program is dropped here, under a span, and not
    # at the return, where it ran under none: every fit jits its own, and
    # dropping one (the loaded executable with it) is milliseconds of the
    # host's
    with trace("lbfgs_release", detail="work"):
        del oracle, vg_fn
    return out


@jax.jit
def logreg_predict(X: jax.Array, Wm: jax.Array, b: jax.Array):
    """Returns (prediction, probability (N,C), rawPrediction (N,C))."""
    logits = X @ Wm.T + b
    probs = jax.nn.softmax(logits, axis=-1)
    preds = jnp.argmax(logits, axis=1).astype(jnp.int32)
    return preds, probs, logits


@jax.jit
def binary_predict(X: jax.Array, coef: jax.Array, intercept):
    """Spark binomial form: margin m = x·β + b, raw = [-m, m],
    prob = [1-σ(m), σ(m)]."""
    margin = X @ coef + intercept
    p1 = jax.nn.sigmoid(margin)
    raw = jnp.stack([-margin, margin], axis=1)
    probs = jnp.stack([1.0 - p1, p1], axis=1)
    preds = (margin > 0).astype(jnp.int32)
    return preds, probs, raw
