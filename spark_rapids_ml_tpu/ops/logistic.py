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
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .lbfgs import lbfgs_minimize
from .linear import _bf16_parts, _on_tpu, _split_product

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
    data_term: Optional[Callable] = None,
):
    """(loss_fn, unpack, l1_mask, n_param) for the softmax multinomial
    objective, shared by the fused and host-dispatched solvers.

    `data_term(W, b)`, where given, is Σᵢ wᵢ·nllᵢ with its own gradient
    (`softmax_data_term`: the rows in blocks, no (rows, C) array); else
    autodiff of `logits_fn`'s logits over all the rows at once."""
    wsum = w.sum()
    _, n_param, l1_mask, unpack = _theta_layout(C, d, dtype, fit_intercept)
    if data_term is None:
        y1h = jax.nn.one_hot(y, C, dtype=dtype)

        def data_term(Wm, b):
            logits = logits_fn(Wm) + b
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -((y1h * logp).sum(axis=1) * w).sum()  # padding rows weighted 0

    def loss_fn(theta):
        Wm, b = unpack(theta)
        data_loss = data_term(Wm, b) / wsum
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
    data_term: Optional[Callable] = None,
):
    """Softmax multinomial solver body shared by the dense and ELL kernels.
    Returns (W, b, loss, n_iter, history, evaluations)."""
    loss_fn, unpack, l1_mask, n_param = _multinomial_problem(
        logits_fn, C, d, dtype, w, y, l2, fit_intercept, data_term
    )
    theta0 = jnp.zeros((n_param,), dtype)
    res = lbfgs_minimize(
        _eval_scope(loss_fn), theta0, max_iter=max_iter, tol=tol, history=history,
        l1=l1, l1_mask=l1_mask, ls_max=ls_max, count_evals=True,
    )
    Wm, b = unpack(res.w)
    return Wm, b, res.f, res.n_iter, res.history_f, res.n_evals


# -- the multinomial evaluation in row blocks --------------------------------
#
# Autodiff of the softmax objective builds (rows, C) logits, log-softmax and
# one-hot labels over all the rows at once: at 1,281,167 x 1,000 each is 5.1
# GB beside 10.5 GB of rows on a 15.75 GB chip.  `softmax_data_term` reads
# the resident rows in place, one `dynamic_slice` of `block_rows` rows a
# step of a `fori_loop` (the Gram's block programs and `take_rows` read them
# so), and per block takes the logits, the log-sum-exp, the residual w P,
# the gradient's part (w P)^T X_b and the value's sum: nothing of (rows, C)
# beyond one block.  The rows' count need not divide: the last block starts
# `block_rows` from the end and drops by weight the rows a block before it
# took.
#
# The gradient is (w (P - Y))^T X, and its labels' part (w Y)^T X is a
# constant of the fit, taken once before the solver's loop and exactly
# (`_label_sums`: the one-hot labels are exact in one bfloat16 part, w x in
# three).  Cut into two bfloat16 parts with the labels in it, each row's
# own-class residual P_y - 1 ~ -0.999 would keep 16 bits of ~1 and lose
# P_y's change between two nearby points: the first curvature pair of a
# 1,000-class fit read 9e-4 of the objective off its float32 value at
# iteration 2 that way (PERF.md §6), so far that any change of the
# float32 rounding flips the reference's closest line-search decisions
# (PERF.md §6).  Cut without them, w P keeps 16 bits of itself.
#
# Both products are float32-faithful on a TPU, where a float32 matmul at
# default precision is ONE bfloat16 pass: each operand is cut into two
# bfloat16 parts by `reduce_precision` (a cast to bfloat16 and back is a
# pair XLA elides there) and three part products, hi.hi + hi.lo + lo.hi
# (what `Precision.HIGH` asks of a TPU), are added in f32, smallest first.
# Three and not XLA's six: on the chip three already hold a fit inside the
# float32 limits that one bfloat16 pass fails, and six take 1.8x the time
# (PERF.md §6).  `_split_product` takes the part products a_i.b_j with
# i + j < parts, so one part would be the default pass and three XLA's six.
_PARTS = 2
_PASSES = _PARTS * (_PARTS + 1) // 2  # bfloat16 MXU passes a product takes
# rows to a block at most: 16,384 x 1,000 logits are 65 MB, and the block's
# two products at 2,048 features are tens of milliseconds of MXU work behind
# each loop step
_SOFTMAX_MAX_BLOCK_ROWS = 16_384
# rows to a block of the labels' sums: the block's w x and its three
# bfloat16 parts are 0.17 GB at 2,048 features
_LABEL_BLOCK_ROWS = 8_192


class SoftmaxBlocks(NamedTuple):
    """The blocked evaluation takes this multinomial fit's evaluations:
    `block_rows` rows of each device's shard a step; `mesh` is the mesh the
    rows are sharded over, None where they lie on one device.  (Where the
    rows allow it the one-pass kernel takes them instead:
    `ops/pallas_softmax.SoftmaxTiles`.)"""

    block_rows: int
    mesh: Optional[Mesh] = None
    evaluation = "softmax_blocks"  # the name `lbfgs_eval_kernel[...]` gives

    def shard(self, X, w, y, Wm, b):
        """One device's (value, gradients but for the labels' part) over
        its shard of the rows."""
        return _softmax_shard(X, w, y, Wm, b, self.block_rows)

    def held_bytes(self, d: int, C: int) -> int:
        """What the evaluation holds beside the rows and the L-BFGS pairs:
        a dozen vectors of C (d + 1), their accumulators and one block's
        temporaries (`softmax_row_bytes`, which over-counts what XLA keeps
        of a block)."""
        return 4 * 12 * C * (d + 1) + self.block_rows * softmax_row_bytes(d, C)


def _softmax_shard(X, w, y, Wm, b, block_rows: int):
    """One device's (Σᵢ wᵢ nllᵢ, Σᵢ wᵢ Pᵢ xᵢ (C, d), Σᵢ wᵢ Pᵢ (C,)) over
    its shard of the rows, `block_rows` rows a step: the value and the
    gradients but for their labels' part (`_label_sums`)."""
    rows, d = X.shape
    C = Wm.shape[0]
    W_parts = _bf16_parts(Wm.astype(jnp.float32), _PARTS)
    classes = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    offsets = jax.lax.broadcasted_iota(jnp.int32, (block_rows,), 0)

    def step(k, carry):
        value, gW, gb = carry
        first = k * block_rows
        at = jnp.minimum(first, rows - block_rows)
        Xb = jax.lax.dynamic_slice_in_dim(X, at, block_rows, 0)
        yb = jax.lax.dynamic_slice_in_dim(y, at, block_rows, 0).astype(jnp.int32)
        wb = jax.lax.dynamic_slice_in_dim(w, at, block_rows, 0).astype(jnp.float32)
        # the last block's rows that the block before it took
        wb = jnp.where(at + offsets >= first, wb, 0.0)
        X_parts = _bf16_parts(Xb.astype(jnp.float32), _PARTS)
        logits = _split_product(X_parts, W_parts, (((1,), (1,)), ((), ()))) + b
        z = logits - logits.max(axis=1, keepdims=True)
        e = jnp.exp(z)
        s = e.sum(axis=1, keepdims=True)
        hit = yb[:, None] == classes
        nll = jnp.log(s[:, 0]) - jnp.where(hit, z, 0.0).sum(axis=1)
        r = wb[:, None] * (e / s)  # the labels' part is `_label_sums`'
        grad = _split_product(_bf16_parts(r, _PARTS), X_parts, (((0,), (0,)), ((), ())))
        return value + (wb * nll).sum(), gW + grad, gb + r.sum(axis=0)

    zero = (jnp.zeros((), jnp.float32), jnp.zeros((C, d), jnp.float32),
            jnp.zeros((C,), jnp.float32))
    return jax.lax.fori_loop(0, -(-rows // block_rows), step, zero)


def _label_shard(X, w, y, C: int):
    """One device's labels' part of the gradient, a constant of the fit:
    (Σᵢ wᵢ [yᵢ = c] xᵢ (C, d), Σᵢ wᵢ [yᵢ = c] (C,)) over its shard,
    exact but for float32's own sums: the one-hot labels are exact in one
    bfloat16 part and wᵢ xᵢ in three, so the three part products (three
    MXU passes once a fit) drop nothing."""
    rows, d = X.shape
    block_rows = min(rows, _LABEL_BLOCK_ROWS)
    classes = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    offsets = jax.lax.broadcasted_iota(jnp.int32, (block_rows,), 0)

    def step(k, carry):
        M, m = carry
        first = k * block_rows
        at = jnp.minimum(first, rows - block_rows)
        Xb = jax.lax.dynamic_slice_in_dim(X, at, block_rows, 0)
        yb = jax.lax.dynamic_slice_in_dim(y, at, block_rows, 0).astype(jnp.int32)
        wb = jax.lax.dynamic_slice_in_dim(w, at, block_rows, 0).astype(jnp.float32)
        wb = jnp.where(at + offsets >= first, wb, 0.0)
        hit = yb[:, None] == classes
        labels = hit.astype(jnp.bfloat16)
        parts = _bf16_parts(wb[:, None] * Xb.astype(jnp.float32), 3)
        block = None
        for part in reversed(parts):  # smallest first
            term = jax.lax.dot_general(labels, part, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
            block = term if block is None else block + term
        return M + block, m + jnp.where(hit, wb[:, None], 0.0).sum(axis=0)

    zero = (jnp.zeros((C, d), jnp.float32), jnp.zeros((C,), jnp.float32))
    return jax.lax.fori_loop(0, -(-rows // block_rows), step, zero)


def _over_shards(plan, local, X, w, y, *replicated):
    """`local(X, w, y, *replicated)` on each device's shard of the rows,
    one psum joining them where `plan.mesh` shards them."""
    if plan.mesh is None:
        return local(X, w, y, *replicated)
    axis = plan.mesh.axis_names[0]
    return jax.shard_map(
        lambda *args: jax.lax.psum(local(*args), axis), mesh=plan.mesh,
        in_specs=(P(axis, None), P(axis), P(axis)) + (P(),) * len(replicated),
        out_specs=P(), check_vma=False,
    )(X, w, y, *replicated)


@partial(jax.jit, static_argnames=("plan", "C"))
def _label_sums(plan, X, w, y, C: int):
    """The labels' part of the gradient over all the rows
    (`_label_shard`)."""
    return _over_shards(plan, partial(_label_shard, C=C), X, w, y)


@partial(jax.jit, static_argnames=("plan",))
def _softmax_evaluate(plan, X, w, y, Wm, b, labels=None):
    """(value, gradient in W, gradient in b) over all the rows: every
    device runs the blocks of its shard (a `SoftmaxBlocks` plan) or the
    one-pass kernel over its tiles (`ops/pallas_softmax.SoftmaxTiles`), one
    psum joins them, and the labels' part (`_label_sums`, taken here where
    the caller has not) comes off the gradients.  A jit of its own, so that
    a fit's re-jit of its evaluation (`logreg_fit_host_dispatch`) finds it
    traced."""
    if labels is None:
        labels = _label_sums(plan, X, w, y, Wm.shape[0])
    value, gW, gb = _over_shards(plan, plan.shard, X, w, y, Wm, b)
    return value, gW - labels[0], gb - labels[1]


def softmax_data_term(plan, X: jax.Array, w: jax.Array, y: jax.Array, C: int,
                      labels=None):
    """(W, b) -> Σᵢ wᵢ·(logsumexp(xᵢWᵀ + b) - (xᵢWᵀ + b)_yᵢ) over ALL the
    rows of a C-class fit, whose forward pass takes the gradient in the
    same read of each block and keeps it as its residual:
    `jax.value_and_grad` of a loss built on it reads the rows once an
    evaluation.  The labels' part of the gradient (`_label_sums`) is taken
    here, once, where the caller does not hand it over."""
    if labels is None:
        labels = _label_sums(plan, X, w, y, C)

    @jax.custom_vjp
    def data_term(Wm, b):
        return _softmax_evaluate(plan, X, w, y, Wm, b, labels)[0]

    def forward(Wm, b):
        value, gW, gb = _softmax_evaluate(plan, X, w, y, Wm, b, labels)
        return value, (gW, gb)

    def backward(grads, ct):
        return ct * grads[0], ct * grads[1]

    data_term.defvjp(forward, backward)
    return data_term


def _softmax_term(plan, X, w, y, C: int, labels=None):
    """`_multinomial_problem`'s `data_term` for the rows `X`: None without
    a plan (autodiff)."""
    return None if plan is None else softmax_data_term(plan, X, w, y, C, labels)


def softmax_row_bytes(d: int, C: int) -> int:
    """Bytes one row of a block holds while it is evaluated: its two
    bfloat16 parts of d, and eight (C,)-rows of f32 (the part products, the
    logits, their exponentials, the residual, the residual's parts)."""
    return 2 * 2 * d + 4 * 8 * C


def softmax_block_rows(X: jax.Array, C: int) -> int:
    """Rows to a block of the blocked evaluation, near-equal blocks over a
    device's shard in whole tiles of 8 rows (the last block may overlap the
    one before it): as many as half the memory the device has left beside
    its shard pays for (`softmax_row_bytes`), and no more than
    `_SOFTMAX_MAX_BLOCK_ROWS`."""
    from ..parallel.device_cache import bytes_beside

    shard_rows = int(X.addressable_shards[0].data.shape[0])
    per_row = softmax_row_bytes(int(X.shape[1]), C)
    limit = max(1, min(bytes_beside(X) // 2 // per_row, _SOFTMAX_MAX_BLOCK_ROWS,
                       shard_rows))
    even = -(-shard_rows // -(-shard_rows // limit))
    return min(shard_rows, -(-even // 8) * 8)


def softmax_program_bytes(shard_rows: int, d: int, C: int, history: int, plan) -> int:
    """Upper bound of the bytes one device holds beside its `shard_rows` x
    `d` shard of the rows while the fused multinomial fit runs with the
    evaluation `plan` (`logreg_fit(..., softmax=plan)`), from its shapes:
    four rows-length 4-byte vectors (`w`, `y` and their views), the L-BFGS
    pairs (2 x `history` vectors of C (d + 1)) and what the evaluation
    holds (`plan.held_bytes`).  `tests/test_pallas_logistic.py` holds the
    compiles for a v5e under it."""
    return 4 * 2 * history * C * (d + 1) + 4 * 4 * shard_rows + plan.held_bytes(d, C)


def softmax_plan(X: jax.Array, n_classes: int) -> Tuple[Optional[tuple], str]:
    """Which evaluation takes a dense multinomial fit's evaluations, read
    from the input alone, and why (the `detail` of the fit's
    `lbfgs_eval_kernel[...]` instant): float32 rows on TPUs, on one device
    or sharded by rows over a mesh's first axis, take the one-pass kernel
    (`ops/pallas_softmax.SoftmaxTiles`) where they lie row-major and C and
    d fit its VMEM plan, else the blocks (`SoftmaxBlocks`).  Elsewhere
    autodiff keeps them (its products at `highest`)."""
    facts = f"{X.dtype} {tuple(X.shape)}, multinomial, {n_classes} classes"
    if X.dtype != jnp.float32:
        return None, f"{facts}: the blocks read float32 rows"
    if not _on_tpu(X):
        return None, f"{facts}: backend {jax.default_backend()}, not a TPU"
    mesh = None
    if len(X.devices()) > 1:
        sharding = X.sharding
        spec = tuple(getattr(sharding, "spec", ()))
        if not (isinstance(sharding, NamedSharding)
                and spec[:1] == (sharding.mesh.axis_names[0],) and not any(spec[1:])):
            return None, f"{facts}: rows not sharded over a mesh's first axis alone ({sharding})"
        mesh = sharding.mesh
    from .pallas_softmax import tiles_plan

    where = f"passes={_PASSES} on {len(X.devices())} TPU(s)"
    tiles, why = tiles_plan(X, n_classes, mesh)
    if tiles is not None:
        return tiles, f"{facts}: {why}, {where}"
    plan = SoftmaxBlocks(softmax_block_rows(X, n_classes), mesh)
    return plan, (
        f"{facts}: {why}; C={n_classes}, block_rows={plan.block_rows}, {where}"
    )


# autodiff's products: a float32 matmul at default precision is ONE
# bfloat16 pass on a TPU
_HIGHEST = jax.lax.Precision.HIGHEST


def _dense_logits(X, Wm):
    return jnp.matmul(X, Wm.T, precision=_HIGHEST)


@partial(
    jax.jit,
    static_argnames=("n_classes", "fit_intercept", "max_iter", "history", "ls_max",
                     "softmax"),
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
    softmax: Optional[SoftmaxBlocks] = None,
):
    """Multinomial (n_classes>=2) logistic regression via L-BFGS/OWL-QN.

    X (N_pad,d) row-sharded (already standardized if requested); w validity*
    sample weights; y int class ids (0 on padding).  `softmax`: the plan
    `softmax_plan` read from these rows (the blocked evaluation), None for
    autodiff.

    Returns (W (n_classes,d), b (n_classes,), loss, n_iter, history,
    evaluations).
    """
    # solver state never drops below f32 (bf16 feature STORAGE is fine —
    # the matmul accumulates f32 — but bf16 L-BFGS curvature pairs are not)
    dtype = jnp.promote_types(X.dtype, jnp.float32)
    return _solve_multinomial(
        partial(_dense_logits, X), n_classes, X.shape[1], dtype, w, y,
        l2, l1, fit_intercept, tol, max_iter, history, ls_max,
        _softmax_term(softmax, X, w, y, n_classes),
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
    softmax: Optional[SoftmaxBlocks] = None,
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
    dense binomial rows `X`; `softmax`: the plan `softmax_plan` read from
    dense multinomial rows `X`; None for autodiff.

    Returns (W (C,d) | coef (d,), b, loss, n_iter, history, evaluations)
    matching the fused kernels' shapes for the same `binomial` flag.
    """
    import numpy as np

    from ..tracing import trace
    from .lbfgs import lbfgs_minimize_host

    dtype = jnp.promote_types(X.dtype, jnp.float32)
    if d is None:
        d = X.shape[1]
    operands = X if data is None else data
    mfn = margin_fn or (lambda dat, beta: dat @ beta)
    lfn = logits_fn or _dense_logits

    # the packed layout's mask is made on the device and read back: a few
    # small programs and a fetch before the first evaluation
    with trace("lbfgs_layout", detail="work"):
        _, n_param, l1_mask, unpack = _theta_layout(
            1 if binomial else n_classes, d, dtype, fit_intercept
        )
        l1_mask = np.asarray(l1_mask, np.float64)
        # the labels' part of a multinomial gradient, once a fit
        labels = None if softmax is None else _label_sums(softmax, X, w, y, n_classes)

    @jax.jit
    def vg_fn(theta, dat, w_, y_, labels_=None):
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
                fit_intercept, _softmax_term(softmax, dat, w_, y_, n_classes, labels_),
            )
        return jax.value_and_grad(_eval_scope(loss_fn))(theta)

    n_evals = 0

    def oracle(theta_np: np.ndarray):
        nonlocal n_evals
        n_evals += 1
        # one span per evaluation, dispatch to fetch: their count IS the
        # fit's evaluation count, and the first one holds the re-jit.
        # Beneath it the host works until the program is enqueued (the
        # arguments; in each fit's first the re-jit), then waits for its
        # value and gradient
        with trace("lbfgs_eval"):
            with trace("lbfgs_eval_dispatch", detail="work"):
                out = vg_fn(jnp.asarray(theta_np, dtype), operands, w, y, labels)
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
        out = coef, b, hist[-1], n_iter, jnp.asarray(hist, dtype), n_evals
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
