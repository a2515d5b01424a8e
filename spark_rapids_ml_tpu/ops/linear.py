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

from ..telemetry.locks import named_lock

# Sample-weight/fold-mask contract (parallel/device_cache.py): the
# sufficient statistics weight every term by `w` and the host solver
# consumes only those weighted sums (n enters as sw = w.sum()), so a w=0
# row — zero padding OR a CV fold-mask hole — is mathematically absent.
# The device cache's masked fold views rely on this; new reductions must
# preserve it (tests/test_device_cache.py asserts the invariance).
SUPPORTS_ZERO_WEIGHT_ROWS = True


# Columns to a panel of the split Gram's two symmetric products (h^T h and
# m^T m), a multiple of the MXU's 128: a panel multiplies itself and the
# panels to its right only, so over p panels those products cost (p+1)/2p of
# a whole pass.  On a v5e at 1M x 3000 (PERF.md §6, PR 34) the statistics
# take 378 ms at 1,024 columns, 369 at 768, 360 at 512, 359 at 384, 372 at
# 256, and 614 ms as XLA's `highest` makes them.
_GRAM_PANEL_COLS = 512

# rows to a block of the split Gram, past which a block only costs memory:
# 65,536 x 3,000 x 3,000 is 1.2 TFLOP a pass, ~20 ms of MXU work a block
# behind every ~100 us dispatch (76,924 and 83,334 rows read no faster)
_MAX_GRAM_BLOCK_ROWS = 65_536


def _moments(X, w, y, precision):
    """(sxy, s1, sw, sy, syy) of weighted rows: every term carries `w`.
    Rows without labels (`y` None: PCA's covariance) give (s1, sw)."""
    Xw = X * w[:, None]
    s1, sw = Xw.sum(axis=0), w.sum()  # (d,), ()
    if y is None:
        return s1, sw
    return (
        jnp.matmul(Xw.T, y, precision=precision),  # (d,)
        s1,
        sw,
        (y * w).sum(),
        (y * y * w).sum(),
    )


@jax.jit
def _linreg_sufficient_stats_xla(X: jax.Array, w: jax.Array, y, shift=None):
    """The statistics in one program over all the rows, the Gram one
    `jnp.matmul` at `stats_precision()`; of the rows less `shift` where one
    is given."""
    from .precision import stats_precision

    # the scope names the kernels in a profile (metadata only)
    with jax.named_scope("linreg_gram"):
        # the normal equations invert this Gram: f32-exact products by
        # default (cuML parity; see ops/precision.py stats_precision)
        hi = stats_precision()
        if shift is not None:
            X = X - shift
        gram = jnp.matmul((X * w[:, None]).T, X, precision=hi)  # (d,d) — MXU, psum over shards
        return (gram, *_moments(X, w, y, hi))


def _bf16_parts(Z: jax.Array, n: int = 3):
    """`n` bfloat16 arrays that add up to the float32 `Z` (at n = 3 to its
    last bit but one or two: 24 significand bits in three of 8)."""
    parts, rest = [], Z
    for _ in range(n):
        # reduce_precision, not a cast there and back: XLA may elide that
        # pair (xla_allow_excess_precision) and leave no remainder
        part = jax.lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
        parts.append(part.astype(jnp.bfloat16))
        rest = rest - part
    return parts


def _split_product(a_parts, b_parts, dims) -> jax.Array:
    """dot_general(a, b, dims) of float32 operands from as many bfloat16
    parts each (`_bf16_parts(x, n)`): the f32 sum of the part products
    a_i.b_j with i + j < n, smallest first (one part: the one default pass;
    two: lo.hi + hi.lo + hi.hi; three: XLA's six of `highest`), each one
    exact bfloat16 MXU pass accumulated in f32."""
    n = len(a_parts)
    terms = sorted(((i, j) for i in range(n) for j in range(n) if i + j < n),
                   key=lambda t: (-t[0] - t[1], -t[0]))
    out = None
    for i, j in terms:
        part = jax.lax.dot_general(a_parts[i], b_parts[j], dims,
                                   preferred_element_type=jnp.float32)
        out = part if out is None else out + part
    return out


def _rows_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """a^T b of bfloat16 (rows, p) and (rows, q): one MXU pass, every
    product exact in the float32 it is accumulated in."""
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def split_gram_half(Z: jax.Array, panel_cols: int = _GRAM_PANEL_COLS) -> jax.Array:
    """A (d,d) float32 `A` with `A + A.T` the Gram Z^T Z of the float32
    rows `Z`, as exact as XLA's `highest` makes it, in 3-and-a-bit bfloat16
    MXU passes for its six.

    `highest` splits each operand into three bfloat16 parts (z = h + m + l)
    and adds the six products h^T h, h^T m, m^T h, m^T m, h^T l, l^T h.  For
    the Gram of ONE matrix m^T h is the transpose of h^T m and l^T h of
    h^T l: with P = h^T m + h^T l the same six terms are
    h^T h + m^T m + P + P^T, four products.  h^T h and m^T m are symmetric
    themselves, so a panel of `panel_cols` columns multiplies only itself
    and the columns to its right, and its diagonal block is halved, since
    `A + A.T` counts that one twice.  The caller adds the halves of its
    row blocks and transposes once: `A + A.T` is symmetric bit for bit."""
    h, m, l = _bf16_parts(Z)
    d = Z.shape[1]
    stairs = []
    for lo in range(0, d, panel_cols):
        width = min(panel_cols, d - lo)
        sym = (_rows_dot(h[:, lo:lo + width], h[:, lo:])
               + _rows_dot(m[:, lo:lo + width], m[:, lo:]))
        sym = jnp.concatenate([0.5 * sym[:, :width], sym[:, width:]], axis=1)
        stairs.append(jnp.pad(sym, ((0, 0), (lo, 0))))
    return _rows_dot(h, m) + _rows_dot(h, l) + jnp.concatenate(stairs)


def _linreg_sufficient_stats_block(acc, X, w, y, start, fresh_from, shift=None,
                                   *, rows: int, panel_cols: int):
    """One row block's share of the statistics, added to acc = (half Gram
    (d,d), sxy, s1, sw, sy, syy), each with or without a leading axis of
    one (a device's own accumulators, `_split_block_program`); without
    labels (`y` None) acc = (half Gram, s1, sw).  The block is rows
    [start, start + rows) of a device's own X, w and y: slices, so no
    second copy of the rows.  Rows before `fresh_from` count for nothing:
    the last block of a shard that the blocks do not tile starts early and
    overlaps the one before it, so one program serves every block.  With a
    `shift` (d,) the statistics are of the block's rows less it: the
    subtraction is of a slice, inside the pass that reads it."""
    with jax.named_scope("linreg_gram"):
        Xb = jax.lax.dynamic_slice(
            X, (start, jnp.zeros((), jnp.int32)), (rows, X.shape[1]))
        if shift is not None:
            Xb = Xb - shift
        wb = jax.lax.dynamic_slice(w, (start,), (rows,))
        yb = None if y is None else jax.lax.dynamic_slice(y, (start,), (rows,))
        wb = jnp.where(start + jnp.arange(rows, dtype=jnp.int32) >= fresh_from, wb, 0.0)
        # Z^T Z = X^T diag(w) X; a zero-weight row is a row of zeros in Z
        half = split_gram_half(Xb * jnp.sqrt(wb)[:, None], panel_cols)
        part = (half, *_moments(Xb, wb, yb, jax.lax.Precision.HIGHEST))
        return jax.tree.map(lambda a, p: a + p.reshape(a.shape), acc, part)


@jax.jit
def _linreg_sufficient_stats_finish(acc):
    """The statistics from the accumulators of the row blocks: those with
    a leading device axis are summed over it, the one sum that crosses
    chips, and the half Gram meets its transpose."""
    with jax.named_scope("linreg_gram"):
        if acc[0].ndim == 3:
            acc = jax.tree.map(lambda a: a.sum(axis=0), acc)
        half, *moments = acc
        return (half + half.T, *moments)


@functools.lru_cache(maxsize=None)
def _split_block_program(mesh, rows: int, panel_cols: int,
                         labelled: bool = True, shifted: bool = False):
    """`_linreg_sufficient_stats_block` over blocks of `rows` rows, jitted
    under that name (the benchmark finds the Gram's device time by it), the
    accumulators donated.  With a mesh the rows are sharded over its first
    axis: every device slices the block out of ITS shard (`start` counts
    from the shard's first row) into its own accumulators, stacked on a
    leading device axis, and nothing crosses chips until
    `_linreg_sufficient_stats_finish` sums that axis.  `labelled`: whether
    its `y` is an array or None; `shifted`: whether a `shift` follows
    `fresh_from` (a mesh's program has to know its arguments)."""
    block = functools.wraps(_linreg_sufficient_stats_block)(functools.partial(
        _linreg_sufficient_stats_block, rows=rows, panel_cols=panel_cols))
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        axis = mesh.axis_names[0]
        block = jax.shard_map(
            block, mesh=mesh,
            in_specs=(P(axis), P(axis, None), P(axis), P(axis) if labelled else None,
                      P(), P()) + ((P(),) if shifted else ()),
            out_specs=P(axis), check_vma=False,
        )
    return jax.jit(block, donate_argnums=(0,))


def gram_block_rows(X: jax.Array, shard_rows: int) -> int:
    """Rows to a block of the split Gram, equal blocks that cover the
    `shard_rows` rows a device holds: as many as half the memory the device
    has left beside its shard pays for (a row's slice times sqrt(w) and its
    three bfloat16 parts, 10 bytes a feature; an upper count, XLA fuses
    some of it into the products), and no more than `_MAX_GRAM_BLOCK_ROWS`."""
    from ..parallel.device_cache import bytes_beside

    per_row = (X.dtype.itemsize + 6) * int(X.shape[1])
    limit = max(1, min(bytes_beside(X) // 2 // per_row, _MAX_GRAM_BLOCK_ROWS, shard_rows))
    return -(-shard_rows // -(-shard_rows // limit))


def linreg_stats_split(X: jax.Array, w: jax.Array, y, mesh=None,
                       block_rows: int = None, panel_cols: int = _GRAM_PANEL_COLS,
                       shift=None):
    """The statistics of float32 rows with the Gram as `split_gram_half`
    makes it, in row blocks read in place: the bfloat16 parts of 1M x 3000
    rows are 18 GB, so they exist one block at a time (default
    `gram_block_rows`: what fits beside the rows).  The host dispatches
    one program a block; a `while_loop` over them would copy its invariant
    rows (`parallel/device_cache.fused_program_fits`).  `mesh`: the mesh
    whose first axis the rows are sharded over, None for one device.  `y`
    None: rows without labels, (gram, s1, sw).  `shift` (d,): the
    statistics of the rows less it."""
    d = int(X.shape[1])
    lead, placed = (), {}
    if mesh is not None:  # one accumulator a device, stacked and so sharded
        from jax.sharding import NamedSharding, PartitionSpec

        axis = mesh.axis_names[0]
        lead = (mesh.shape[axis],)
        placed = {"device": NamedSharding(mesh, PartitionSpec(axis))}
    shard_rows = int(X.shape[0]) // (lead[0] if lead else 1)
    rows = max(1, min(int(block_rows or gram_block_rows(X, shard_rows)), shard_rows))
    program = _split_block_program(
        mesh, rows, int(panel_cols), y is not None, shift is not None)
    shapes = ((d, d), (d,), (d,), (), (), ()) if y is not None else ((d, d), (d,), ())
    acc = tuple(jnp.zeros(lead + shape, jnp.float32, **placed) for shape in shapes)
    more = () if shift is None else (shift,)
    for fresh_from in range(0, shard_rows, rows):
        start = min(fresh_from, shard_rows - rows)
        acc = program(acc, X, w, y, np.int32(start), np.int32(fresh_from), *more)
    return _linreg_sufficient_stats_finish(acc)


def _on_tpu(X: jax.Array) -> bool:
    return all(dev.platform == "tpu" for dev in X.devices())


def gram_kernel_plan(X: jax.Array):
    """(kernel, mesh, why): which Gram a fit's statistics take, read from
    the rows and the precision level alone, and why (the `detail` of the
    fit's `linreg_gram_kernel[...]` instant).  `symmetric_split` for
    float32 rows wider than one panel at `stats_precision` `highest` on
    TPUs, where XLA makes a `highest` product of six bfloat16 passes; `xla`,
    the one `jnp.matmul` at the stated precision, anywhere else: a lower
    level is fewer passes already, a CPU's or GPU's `highest` is a native
    float32 product that a split only adds work to, and on a v5e at 1M
    rows the split is 28.2 ms for XLA's 30.9 at 640 columns but 29.8 for
    21.1 at 512 (57 for 71 at 1,000, 175 for 278 at 2,000; PERF.md §6)."""
    from jax.sharding import NamedSharding

    from .precision import stats_precision

    precision = stats_precision()
    facts = f"{X.dtype} {tuple(X.shape)} at {precision.name.lower()}"
    if X.dtype != jnp.float32:
        return "xla", None, f"{facts}: the split is of float32 into bfloat16 parts"
    if precision != jax.lax.Precision.HIGHEST:
        return "xla", None, f"{facts}: fewer passes than the split already"
    if not _on_tpu(X):
        return "xla", None, (
            f"{facts}: backend {jax.default_backend()}, not a TPU: `highest` is "
            "a native float32 product there"
        )
    if X.shape[1] <= _GRAM_PANEL_COLS:
        return "xla", None, (
            f"{facts}: one panel of {_GRAM_PANEL_COLS} columns has no symmetric "
            "half to skip, and the parts' traffic costs more than two passes there"
        )
    if len(X.devices()) == 1:
        return "symmetric_split", None, f"{facts}: Z^T Z of one matrix on one TPU"
    sharding = X.sharding
    if isinstance(sharding, NamedSharding):
        mesh, spec = sharding.mesh, tuple(sharding.spec)
        if spec[:1] == (mesh.axis_names[0],) and not any(spec[1:]):
            return "symmetric_split", mesh, (
                f"{facts}: Z^T Z of one matrix, rows over {len(X.devices())} TPUs, "
                "one sum across them"
            )
    return "xla", None, f"{facts}: rows not sharded over a mesh's first axis alone ({sharding})"


def linreg_sufficient_stats(X: jax.Array, w: jax.Array, y, shift=None):
    """Weighted Gram, moment, and cross terms (gram, sxy, s1, sw, sy, syy)
    in one read of the rows.  X (N_pad,d) row-sharded, w validity*sample
    weights (non-negative), y labels (0 on padding).  Which Gram kernel ran
    is a fact of the fit: the instant `linreg_gram_kernel[symmetric_split|xla]`
    (`gram_kernel_plan`).  The Gram's second caller is PCA's covariance
    (`ops/pca.pca_scatter`): no labels (`y` None gives (gram, s1, sw)), and
    a `shift` (d,) near the mean that every row loses as it is read."""
    from ..tracing import event

    kernel, mesh, why = gram_kernel_plan(X)
    event(f"linreg_gram_kernel[{kernel}]", detail=why)
    if kernel == "xla":
        return _linreg_sufficient_stats_xla(X, w, y, shift)
    return linreg_stats_split(X, w, y, mesh, shift=shift)


def _soft_threshold(v: np.ndarray, t: float) -> np.ndarray:
    return np.sign(v) * np.maximum(np.abs(v) - t, 0.0)


def _fortran_view(A: np.ndarray) -> np.ndarray:
    """What BLAS and LAPACK update in place: for a C-ordered buffer the
    transposed view, the same matrix where it is symmetric."""
    return A if A.flags.f_contiguous else A.T


def _normal_system(gram, sw: float, mean, scale, ridge: float,
                   out: np.ndarray = None) -> np.ndarray:
    """The Gram centred by `mean` and divided by `scale` x `scale` (each
    skipped where None) plus `ridge` on the diagonal, in float64, written
    into `out` (a C-ordered float64 (d,d) buffer; a new one where None) a
    block of rows (~2 MB, which stays in cache) at a time: the Gram widened
    into the block, then centred by a rank-1 update, scaled, the ridge
    added.  Every element is written, so whatever the buffer held before (a
    factor, another system) cannot reach this one; the caller's array is
    never written, and no other (d,d) array is made."""
    from scipy.linalg.blas import dger

    d = gram.shape[0]
    if out is None:
        out = np.empty((d, d))
    rows = max(1, (1 << 18) // d)
    for r0 in range(0, d, rows):
        r1 = min(r0 + rows, d)
        block = out[r0:r1]
        np.copyto(block, gram[r0:r1])
        if mean is not None:
            dger(-sw, mean, mean[r0:r1], a=block.T, overwrite_a=1)
        if scale is not None:
            block /= scale[r0:r1, None]
            block /= scale
        if ridge:
            k = np.arange(r1 - r0)
            block[k, r0 + k] += ridge
    return out


class _SystemWorkspace:
    """The buffer of one `solve_linear_host`'s float64 (d,d) system, kept
    in ONE slot across solves.  A new one is 72 MB at 3,000 columns:
    mapped fresh, first-touched page by page as it is written (~70 ms a
    fit on a v5e's host) and unmapped after (~8 ms).  A solve of the slot's
    d writes its system into the slot's buffer; one of another d replaces
    it.  The slot is taken without waiting: a solve that finds it held (a
    second thread's) makes a buffer of its own, as every solve did before.
    The slot keeps what the last solve held at its peak, so a run of solves
    peaks no higher than one did; but nothing releases it, so d^2 x 8 bytes
    stay resident for the life of the process (72 MB at 3,000 columns,
    3.2 GB at 20,000), beneath whatever runs next: another estimator's fit,
    or PCA's eigensolve with a (d,d) system of its own.

    A context: the slot is held from the first `system` to `close`, which
    the exit calls too."""

    _lock = named_lock("linreg_system_workspace")
    _kept = None  # the slot: a C-ordered float64 (d,d) array, or None

    def __init__(self):
        self._buf = None  # this solve's buffer, once it is assembled into
        self._holds = False

    def system(self, gram, sw: float, mean, scale, ridge: float) -> np.ndarray:
        """The system in this solve's buffer; the instant
        `linreg_solve_workspace[reused|new]` says how the buffer was had."""
        from ..tracing import event

        d = gram.shape[0]
        cls = _SystemWorkspace
        why = ""
        if self._buf is None:
            self._holds = cls._lock.acquire(blocking=False)
            if not self._holds:
                why, self._buf = "busy", np.empty((d, d))
            else:
                if cls._kept is None or cls._kept.shape != (d, d):
                    why = "first" if cls._kept is None else "shape"
                    cls._kept = None  # the old buffer unmapped before the new is made
                    cls._kept = np.empty((d, d))
                self._buf = cls._kept
        event(f"linreg_solve_workspace[{'new' if why else 'reused'}]",
              detail=f"d={d} {why}".rstrip())
        return _normal_system(gram, sw, mean, scale, ridge, out=self._buf)

    def close(self) -> None:
        self._buf = None
        if self._holds:
            self._holds = False
            _SystemWorkspace._lock.release()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


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
    # one such array is the system's buffer, kept across solves
    # (`_SystemWorkspace`).
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
    workspace = _SystemWorkspace()
    system = functools.partial(
        workspace.system, gram, sw,
        mean if fit_intercept else None, std if standardization else None,
    )

    l1 = reg_param * elasticnet_param
    l2 = reg_param * (1.0 - elasticnet_param)
    n_iter = 0

    # every branch builds its float64 system under `linreg_solve_assemble`
    # (the widening copy into the kept (d,d) buffer, first touched by the
    # solve that made it) and solves it under `linreg_solve_factor`: both
    # the host working, inside the caller's `linreg_host_solve`
    from ..tracing import event, trace

    def assembled(ridge: float) -> np.ndarray:
        with trace("linreg_solve_assemble", detail="work"):
            return system(ridge)

    with workspace:
        if reg_param == 0.0:
            A = assembled(0.0)
            with trace("linreg_solve_factor", detail="work"):
                coef_s = np.linalg.lstsq(A, sxy_s, rcond=None)[0]
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

            A = assembled(sw * l2)
            with trace("linreg_solve_factor", detail="work"):
                try:
                    factor = cho_factor(
                        _fortran_view(A),
                        lower=True, overwrite_a=True, check_finite=False,
                    )
                except LinAlgError:
                    coef_s = None  # the factorisation wrote over its system
                else:
                    event("linreg_solver[cholesky]")
                    coef_s = cho_solve(factor, sxy_s, check_finite=False)
            if coef_s is None:
                event("linreg_solver[lu_fallback]")
                A = assembled(sw * l2)
                with trace("linreg_solve_factor", detail="work"):
                    coef_s = np.linalg.solve(A, sxy_s)
        else:
            # FISTA on f(β)=1/(2n)(βᵀGβ - 2bᵀβ) + λ₂/2‖β‖², prox for λ₁‖β‖₁
            from ..resilience import maybe_inject
            from ..resilience.checkpoint import (
                clear_checkpoint,
                load_checkpoint,
                save_checkpoint,
            )

            with trace("linreg_solve_assemble", detail="work"):
                G = system(0.0)
                G /= sw
                b = sxy_s / sw
            # the solve of this branch: the step size (an eigenvalue of the
            # system) and the proximal iterations
            with trace("linreg_solve_factor", detail="work"):
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

        # the system's references are dropped here, under a span, and the
        # slot given back: its buffer stays mapped for the next solve (one
        # of its own, where the slot was held, is unmapped here)
        with trace("linreg_solve_release", detail="work"):
            A = factor = G = None  # whichever this branch made
            workspace.close()
    coef = coef_s / scale
    intercept = float(ymean - mean @ coef) if fit_intercept else 0.0
    # training-summary statistics from the same sufficient stats (Spark's
    # LinearRegressionTrainingSummary surface): weighted
    # SSE = Σw(y - Xβ - b)² expanded in gram/cross/moment terms.
    # NOTE: this expansion subtracts near-equal accumulated terms; with
    # f32-accumulated inputs the absolute error is ~eps32·syy/sw, so
    # callers holding the data should overwrite with `summary_stats`'s
    # cancellation-free residual pass (models/regression.py does).
    with trace("linreg_solve_summary", detail="work"):
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
