#
# One-pass value+gradient of the binomial logistic data term — a Pallas TPU
# kernel that brings each tile of the resident rows into VMEM ONCE and
# takes both products from it: the margin X.beta + b and, after the
# row-wise softplus and its derivative, the gradient r.X and sum r.
#
# Why a kernel at all: XLA compiles `value_and_grad` of the loss as two
# `multiply_reduce_fusion`s that each stream all of X from HBM, each at
# 92 % of the memory peak (15.9 ms at 1M x 3000 on a v5e, PERF.md §5).
# The kernels are finished; the second read is the cost.  An evaluation is
# 12 GB of reads against ~12e9 VPU operations here, so memory stays the
# bound with one read.
#
# The rows are read AS THEY LIE.  The (1M, 3000) f32 rows of the cells lie
# column-major on a v5e (minor dimension rows, tiles of 8 features x 128
# rows: 3,000 columns are not padded to 3,072), so the kernel takes `X.T`
# — (d, rows) row-major is the same bytes, a bitcast inside the jit — and
# tiles along the lane axis: rows on lanes, so the margin, the weights and
# the residual are lane-dense (1, tile) vectors and the gradient
# accumulates into a (d, 128) buffer whose lanes are summed once, outside.
# Asking for the rows in another layout than the one they lie in makes XLA
# copy all of X (22.6 GB asked of a 15.75 GB chip at 1M x 3000,
# `ops/kmeans.py take_rows`), and which layout a shape gets is the
# runtime's choice (row-major where d is a multiple of 128): the plan
# reads it from the array (`_rows_minor`) and takes column-major rows alone.
#
# All arithmetic is float32 on the VPU (broadcast-multiply and add, as
# XLA's own fusions do): nothing is rounded to bfloat16.
#
# Who takes it is read from the input (`one_pass_plan`): dense float32
# rows of a binomial fit on a TPU.  Everything else keeps autodiff of
# `ops/logistic._binary_problem`'s loss.
#
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

_LANES = 128
_SUBLANES = 8
# bytes of one VMEM buffer of rows (the pipeline holds two): 3,000 features
# x 512 rows.  The tile's length is not a lever: an evaluation takes the
# same 16.0 ms at 256, 512 and 1,024 rows a tile (PERF.md §6, PR 32)
_TILE_BYTES = 6 << 20
_MAX_TILE = 1024
# features past which even a 128-row tile overflows `_TILE_BYTES`
_MAX_FEATURES = _TILE_BYTES // (4 * _LANES)
_VMEM_LIMIT_BYTES = 48 << 20


class OnePass(NamedTuple):
    """The one-pass kernel takes this fit's evaluations; `mesh` is the mesh
    the rows are sharded over, None where they lie on one device.
    `interpret` is the tests': `one_pass_plan` never sets it."""

    mesh: Optional[Mesh]
    interpret: bool = False


def _tile_rows(d: int) -> int:
    lanes = _TILE_BYTES // (4 * d) // _LANES * _LANES
    return max(_LANES, min(_MAX_TILE, lanes))


def _slab_rows(d: int) -> int:
    """Features to one loop step: the most vreg rows (of 8), up to 16, that
    divide d's (375 = 25 x 15 at d = 3000)."""
    groups = d // _SUBLANES
    return _SUBLANES * max(k for k in range(1, 17) if groups % k == 0)


def _fold_lanes(v, tile: int):
    """(r, tile) -> (r, 128): the lane chunks added."""
    out = v[:, :_LANES]
    for at in range(_LANES, tile, _LANES):
        out = out + v[:, at:at + _LANES]
    return out


def _kernel(rows: int, d: int, tile: int):
    slab = _slab_rows(d)
    n_slabs = d // slab
    # rows that do not fill the last tile: what lies past the end is
    # whatever the buffer held, and is cut out BY INDEX before any
    # arithmetic, never multiplied by a zero.  One select an element on
    # every tile (the VPU has the room, PERF.md §6) buys one kernel body:
    # a fit traces and lowers it anew (`logreg_fit_host_dispatch`)
    ragged = rows % tile != 0

    def kernel(b_ref, xt_ref, w_ref, s_ref, beta_ref, grad_ref, stat_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            grad_ref[...] = jnp.zeros_like(grad_ref)
            stat_ref[...] = jnp.zeros_like(stat_ref)

        if ragged:
            lane = i * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
            live = lane < rows

        def cut(v):
            return jnp.where(live, v, 0.0) if ragged else v

        def features(j):
            at = pl.multiple_of(j * slab, slab)
            return at, cut(xt_ref[pl.ds(at, slab), :])

        def margin_step(j, acc):
            at, xs = features(j)
            # beta lies on every lane of its 128: the same vreg per chunk
            bs = pltpu.repeat(beta_ref[pl.ds(at, slab), :], tile // _LANES, axis=1)
            p = (xs * bs).reshape(slab // _SUBLANES, _SUBLANES, tile)
            return acc + p.sum(axis=0)

        acc = jax.lax.fori_loop(
            0, n_slabs, margin_step, jnp.zeros((_SUBLANES, tile), jnp.float32)
        )
        m = acc.sum(axis=0, keepdims=True) + b_ref[0, 0]  # (1, tile)
        s, w = cut(s_ref[...]), cut(w_ref[...])
        # w softplus(-s m) and its derivative in m, from one exp
        z = -s * m
        e = jnp.exp(-jnp.abs(z))
        nll = w * (jnp.maximum(z, 0.0) + jnp.log1p(e))
        r = -s * w * jnp.where(z >= 0.0, 1.0, e) / (1.0 + e)
        stat_ref[0:1, :] += _fold_lanes(nll, tile)
        stat_ref[1:2, :] += _fold_lanes(r, tile)

        def grad_step(j, carry):
            at, xs = features(j)
            grad_ref[pl.ds(at, slab), :] += _fold_lanes(xs * r, tile)
            return carry

        jax.lax.fori_loop(0, n_slabs, grad_step, 0)

    return kernel


def shard_value_and_grad(
    Xt: jax.Array,  # (d, rows) f32: the shard's rows, transposed
    w: jax.Array,  # (rows,)
    sgn: jax.Array,  # (rows,) 2 y - 1
    beta: jax.Array,  # (d,)
    b: jax.Array,  # ()
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One device's (sum_i w_i softplus(-s_i m_i), sum_i w_i dl_i x_i (d,),
    sum_i w_i dl_i) with m = x.beta + b and dl = d softplus(-s m) / dm,
    from one read of the rows.  `interpret`: Pallas' interpreter, for the
    CPU tests."""
    d, rows = Xt.shape
    tile = _tile_rows(d)
    row_spec = pl.BlockSpec((1, tile), lambda i: (0, i))
    whole = pl.BlockSpec((d, _LANES), lambda i: (0, 0))
    # traced with x64 off: a process that has it on (a float64 fit) makes
    # every Python index of the kernel 64-bit, which Mosaic does not lower
    with jax.enable_x64(False):
        grad, stat = pl.pallas_call(
            _kernel(rows, d, tile),
            grid=(pl.cdiv(rows, tile),),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),  # b
                pl.BlockSpec((d, tile), lambda i: (0, i)),
                row_spec,  # w
                row_spec,  # sgn
                whole,  # beta on every lane
            ],
            out_specs=[whole, pl.BlockSpec((_SUBLANES, _LANES), lambda i: (0, 0))],
            out_shape=[
                jax.ShapeDtypeStruct((d, _LANES), jnp.float32),
                jax.ShapeDtypeStruct((_SUBLANES, _LANES), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                # the accumulators stay resident across the row tiles
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_VMEM_LIMIT_BYTES,
            ),
            cost_estimate=pl.CostEstimate(
                flops=4 * d * rows, transcendentals=2 * rows,
                bytes_accessed=4 * (d * rows + 2 * rows + 2 * d * _LANES),
            ),
            name="logistic_value_and_grad",
            interpret=interpret,
        )(
            b.reshape(1, 1),
            Xt,
            w.reshape(1, rows),
            sgn.reshape(1, rows),
            jnp.broadcast_to(beta[:, None], (d, _LANES)),
        )
    return stat[0].sum(), grad.sum(axis=1), stat[1].sum()


@partial(jax.jit, static_argnames=("plan",))
def _evaluate(plan: OnePass, X, w, sgn, beta, b):
    """[gradient (d,), sum r, value] over all the rows: every device runs
    the kernel on its shard and one psum joins them.  A jit of its own,
    so that a fit's re-jit of its evaluation (`logreg_fit_host_dispatch`)
    finds the kernel traced."""

    def local(Xl, wl, sl, beta, b):
        value, grad, grad_b = shard_value_and_grad(
            Xl.T, wl, sl, beta, b, interpret=plan.interpret
        )
        return jnp.concatenate([grad, grad_b[None], value[None]])

    if plan.mesh is None:
        return local(X, w, sgn, beta, b)
    axis = plan.mesh.axis_names[0]
    return jax.shard_map(
        lambda *args: jax.lax.psum(local(*args), axis), mesh=plan.mesh,
        in_specs=(P(axis, None), P(axis), P(axis), P(), P()),
        out_specs=P(), check_vma=False,
    )(X, w, sgn, beta, b)


def one_pass_data_term(plan: OnePass, X: jax.Array, w: jax.Array, sgn: jax.Array):
    """(beta, b) -> sum_i w_i softplus(-sgn_i (x_i.beta + b)) over ALL the
    rows, whose forward pass runs the kernel and keeps the gradient as its
    residual: `jax.value_and_grad` of a loss built on it reads X once."""
    d = X.shape[1]

    @jax.custom_vjp
    def data_term(beta, b):
        return _evaluate(plan, X, w, sgn, beta, b)[d + 1]

    def forward(beta, b):
        out = _evaluate(plan, X, w, sgn, beta, b)
        return out[d + 1], (out[:d], out[d])

    def backward(grads, ct):
        return ct * grads[0], ct * grads[1]

    data_term.defvjp(forward, backward)
    return data_term


def _on_tpu(X: jax.Array) -> bool:
    return all(dev.platform == "tpu" for dev in X.devices())


def _rows_minor(layout) -> bool:
    """Whether rows of this layout (`X.format.layout`, every shard's) lie
    column-major, rows the minor dimension, so that `X.T` is the same
    bytes."""
    return tuple(layout.major_to_minor) == (1, 0)


def one_pass_plan(X: jax.Array, binomial: bool) -> Tuple[Optional[OnePass], str]:
    """Whether the one-pass kernel takes a dense fit's evaluations, read
    from the input alone, and why (the `detail` of the fit's
    `lbfgs_eval_kernel[...]` instant).  The kernel exists for one layout
    and dtype: float32 rows of a binomial fit that lie column-major on one
    TPU, or sharded by rows over a mesh's first axis."""
    d = int(X.shape[1])
    facts = f"{X.dtype} {tuple(X.shape)}, {'binomial' if binomial else 'multinomial'}"
    if not binomial:
        return None, f"{facts}: the (rows, C) logits are a matmul, another kernel"
    if X.dtype != jnp.float32:
        return None, f"{facts}: the kernel reads float32 rows"
    if d % _SUBLANES or d > _MAX_FEATURES:
        return None, (
            f"{facts}: the kernel tiles features by {_SUBLANES}, up to {_MAX_FEATURES}"
        )
    if not _on_tpu(X):
        return None, f"{facts}: backend {jax.default_backend()}, not a TPU"
    layout = X.format.layout
    if not _rows_minor(layout):
        return None, (
            f"{facts}: the rows lie major_to_minor={tuple(layout.major_to_minor)}, "
            f"not column-major: the kernel's (d, rows) view would be a copy of them"
        )
    if len(X.devices()) == 1:
        return OnePass(None), f"{facts}: column-major on one TPU, rows on lanes"
    sharding = X.sharding
    if isinstance(sharding, NamedSharding):
        mesh, spec = sharding.mesh, tuple(sharding.spec)
        if spec[:1] == (mesh.axis_names[0],) and not any(spec[1:]):
            return OnePass(mesh), (
                f"{facts}: column-major shards of rows over {mesh.devices.size} TPUs, "
                f"one psum an evaluation"
            )
    return None, f"{facts}: rows not sharded over a mesh's first axis alone ({sharding})"
