#
# The multinomial evaluation in one pass over the rows — a Pallas TPU kernel
# that brings each tile of the resident rows into VMEM ONCE and does all of
# its work there: the cut into two bfloat16 parts, the logits as three part
# products, the softmax, the residual w P and its cut, the gradient's three
# part products into an accumulator that stays in VMEM across the tiles.
#
# Why a kernel: XLA runs the blocked evaluation (`ops/logistic._softmax_shard`)
# as a dozen fusions a block that hand (block, d) parts and (block, C)
# temporaries to each other through HBM.  At 1,281,167 x 2,048 with 1,000
# classes the six part products take ~172 ms of a 220 ms evaluation on a
# v5e; the cut of the rows into parts, a relayout of those parts and the
# softmax's elementwise passes the other ~48 (PERF.md §5).  Here the rows
# are read once and nothing of a tile's size goes back to HBM.
#
# The arithmetic is the blocked evaluation's: float32 rows cut into two
# bfloat16 parts bit-equal to `ops/linear._bf16_parts(x, 2)`, each product
# the three part products lo.hi + hi.lo + hi.hi added smallest first in
# float32, the softmax in float32, the labels' part of the gradient the
# fit's constant (`ops/logistic._label_sums`).  The tile is computed
# transposed, classes
# on sublanes and rows on lanes: the logits are W X^T (the rows' parts the
# product's transposed operand), the labels and weights lane-dense (1, tile)
# rows, the softmax's sums run down the sublanes, and the gradient is R^T X,
# a plain product of the residual as it lies with the rows as they lie.
#
# The rows are read AS THEY LIE: row-major, which they are on a v5e where d
# is a multiple of 128 (PERF.md §6).  `ops/logistic.softmax_plan`
# reads the layout from the array and takes nothing else: asking for another
# layout would copy all of them.
#
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from .linear import _bf16_parts, _split_product
from .pallas_logistic import _LANES, _fold_lanes

# the tile lengths tried, longest first.  At 2,048 features and 1,000
# classes on a v5e an evaluation takes 170.7 ms at 256 rows a tile, 172.7 at
# 128, 195.0 at 512, 207.6 at 384 and 190.2 at 1,024 (PERF.md §6):
# longer tiles cost the products more than they save of the steps
_TILES = (256, 128)
# what the kernel may ask of the v5e's 128 MiB of VMEM
_VMEM_LIMIT_BYTES = 100 << 20
# the (C, tile) float32 temporaries counted for the plan: the logits, their
# exponentials, the residual
_CLASS_TEMPS = 3
# the (C, d) float32 temporaries: the gradient's part products before they
# are added to the accumulator, which the compiler spills to VMEM where d is
# wide (69 MB of spill slots at 8,192 features and 1,000 classes)
_GRAD_TEMPS = 2
# tiles whose gradients one (C, d) partial sum takes before it is added,
# compensated, to the whole: 5,005 tiles a shard at the ImageNet probe's
# shape make 79 such additions, as many as its 79 row blocks make in XLA's
_FLUSH_TILES = 64


class SoftmaxTiles(NamedTuple):
    """The one-pass kernel takes this multinomial fit's evaluations:
    `tile_rows` rows of each device's shard a grid step; `mesh` is the mesh
    the rows are sharded over, None where they lie on one device.
    `interpret` is the tests': `ops/logistic.softmax_plan` never sets it."""

    tile_rows: int
    mesh: Optional[Mesh] = None
    interpret: bool = False
    evaluation = "softmax_tiles"  # the name `lbfgs_eval_kernel[...]` gives

    def shard(self, X, w, y, Wm, b):
        """One device's (value, gradients but for the labels' part) over
        its shard of the rows."""
        return shard_value_and_grad(X, w, y, Wm, b, self.tile_rows, self.interpret)

    def held_bytes(self, d: int, C: int) -> int:
        """What the evaluation holds in HBM beside the rows and the L-BFGS
        pairs: 32 vectors of C (d + 1) (compiled for a v5e at the ImageNet
        probe's shape the fused program holds 31: 428 MB in all with the
        pairs), W's parts and the gradient the kernel writes
        (`hbm_bytes`)."""
        return 4 * 32 * C * (d + 1) + hbm_bytes(d, C)


def padded_classes(C: int) -> int:
    """Classes as the kernel holds them: whole lane tiles of 128."""
    return -(-C // _LANES) * _LANES


def vmem_bytes(tile: int, d: int, C: int) -> int:
    """Upper bound of the VMEM the kernel holds at `tile` rows a step: the
    double-buffered float32 tile and its two parts; W's two parts and the
    float32 gradient's partial sum, total and compensation, one buffer
    each; `_CLASS_TEMPS` (C, tile)
    and `_GRAD_TEMPS` (C, d) float32 temporaries, and the small
    accumulators with their compensations.  Compiled for a v5e the kernel
    needs 38-40 MiB at 256 rows, 2,048 features and 1,000 classes (this
    gives 58), 68-70 at 3,584 features (99), 79-81 at 128 rows and 3,840
    features (98): the least `vmem_limit_bytes` it compiles under."""
    Cp = padded_classes(C)
    return (tile * d * (2 * 4 + 2 * 2) + Cp * d * (2 * 2 + 3 * 4 + _GRAD_TEMPS * 4)
            + _CLASS_TEMPS * Cp * tile * 4 + 2 * (Cp + 8) * _LANES * 4 + 4 * 8 * tile * 4)


def tile_rows(d: int, C: int) -> Optional[int]:
    """The longest tile whose VMEM plan fits `_VMEM_LIMIT_BYTES`, None where
    none does (the gradient's accumulator alone is C x d x 4 bytes).  The
    plan is a v5e's: 128 MiB of VMEM, of which the kernel asks at most
    `_VMEM_LIMIT_BYTES`."""
    for tile in _TILES:
        if vmem_bytes(tile, d, C) <= _VMEM_LIMIT_BYTES:
            return tile
    return None


def hbm_bytes(d: int, C: int) -> int:
    """What an evaluation holds in HBM beside the rows: W's two bfloat16
    parts padded to `padded_classes`, the float32 gradient the kernel
    writes, and its first C rows, the gradient handed on."""
    return padded_classes(C) * d * (2 * 2 + 4) + C * d * 4


def tiles_plan(X: jax.Array, C: int, mesh: Optional[Mesh]):
    """(SoftmaxTiles or None, why) for the float32 rows `X` of a C-class fit
    on TPUs, sharded over `mesh`'s first axis or on one device: the kernel
    reads row-major rows whose features fill whole lane tiles, with C and d
    within its VMEM plan, which assumes a v5e's 128 MiB of VMEM
    (`tile_rows`)."""
    d = int(X.shape[1])
    if d % _LANES:
        return None, (
            f"d={d} is not a multiple of {_LANES}: the one-pass kernel tiles features by it"
        )
    order = tuple(X.format.layout.major_to_minor)
    if order != (0, 1):
        return None, (
            f"the rows lie major_to_minor={order}, not row-major: the one-pass "
            f"kernel's tiles would be a copy of them"
        )
    tile = tile_rows(d, C)
    if tile is None:
        least = vmem_bytes(_TILES[-1], d, C)
        return None, (
            f"C={C}, d={d}: the one-pass kernel's VMEM plan is {least / 2**20:.0f} MiB "
            f"at {_TILES[-1]} rows a tile, over its {_VMEM_LIMIT_BYTES / 2**20:.0f}"
        )
    return SoftmaxTiles(tile, mesh), (
        f"C={C}, tile_rows={tile}, classes_padded={padded_classes(C)}"
    )


def bf16_parts(x):
    """(hi, lo), two bfloat16 arrays with hi + lo the float32 `x` to 16
    significand bits: `ops/linear._bf16_parts(x, 2)` bit for bit.  There
    `reduce_precision` cuts them, because XLA may elide a conversion to
    bfloat16 and back; Mosaic keeps the pair, and its conversion rounds to
    nearest even (on a v5e every bit of both parts equals XLA's, ties of
    both parities among them: PERF.md §6)."""
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


_NT = (((1,), (1,)), ((), ()))  # a . b^T
_NN = (((1,), (0,)), ((), ()))  # a . b


def add_compensated(sum_ref, lost_ref, v):
    """sum_ref += v, Kahan's way: `lost_ref` carries what each addition
    rounded off into the next.  The intercepts' gradient and the value add
    one (C, 128) fold a tile, 5,005 times at the ImageNet probe's shape:
    summed plainly their error was 1.6-2.4 times the row blocks' (PERF.md
    §6).  The gradient in W adds a partial sum every `_FLUSH_TILES`."""
    y = v - lost_ref[...]
    t = sum_ref[...] + y
    lost_ref[...] = (t - sum_ref[...]) - y
    sum_ref[...] = t


def _kernel(rows: int, tile: int):
    # rows that do not fill the last tile: what lies past the end is
    # whatever the buffer held (NaN among it), cut out BY INDEX before any
    # arithmetic, in the rows as in their weights and labels
    ragged = rows % tile != 0

    def kernel(x_ref, w_ref, y_ref, wh_ref, wl_ref, b_ref, gw_ref, gb_ref, stat_ref,
               gw_total, gw_lost, gb_lost, stat_lost):
        i = pl.program_id(0)
        last = pl.num_programs(0) - 1

        @pl.when(i == 0)
        def _init():
            for ref in (gw_ref, gb_ref, stat_ref, gw_total, gw_lost, gb_lost, stat_lost):
                ref[...] = jnp.zeros_like(ref)

        if ragged:
            # in the last tile's own buffers, so that no other tile pays
            @pl.when(i == last)
            def _cut_the_tail():
                live = rows - i * tile
                down = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) < live
                across = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) < live
                x_ref[...] = jnp.where(down, x_ref[...], 0.0)
                w_ref[...] = jnp.where(across, w_ref[...], 0.0)
                y_ref[...] = jnp.where(across, y_ref[...], 0)

        w, y = w_ref[...], y_ref[...]
        xp = bf16_parts(x_ref[...])
        # (C, tile): classes on sublanes, rows on lanes; padded classes carry
        # an intercept of -inf, so their exponentials are 0
        logits = _split_product((wh_ref[...], wl_ref[...]), xp, _NT) + b_ref[...]
        z = logits - logits.max(axis=0, keepdims=True)
        e = jnp.exp(z)
        s = e.sum(axis=0, keepdims=True)
        hit = jax.lax.broadcasted_iota(jnp.int32, z.shape, 0) == y
        nll = jnp.log(s) - jnp.where(hit, z, 0.0).sum(axis=0, keepdims=True)
        # the residual w P: the labels' part, w Y, is the fit's constant
        # (`ops/logistic._label_sums`), not cut here
        r = w * (e * (1.0 / s))
        add_compensated(stat_ref, stat_lost, _fold_lanes(w * nll, tile))
        add_compensated(gb_ref, gb_lost, _fold_lanes(r, tile))
        # the partial sum in the output's buffer and the total in scratch:
        # 2.3 ms an evaluation sooner on a v5e than the other way round, to
        # the same bits (PERF.md §6)
        gw_ref[...] += _split_product(bf16_parts(r), xp, _NN)

        @pl.when((i % _FLUSH_TILES == _FLUSH_TILES - 1) | (i == last))
        def _flush():
            add_compensated(gw_total, gw_lost, gw_ref[...])
            gw_ref[...] = jnp.zeros_like(gw_ref)

        @pl.when(i == last)
        def _out():
            gw_ref[...] = gw_total[...]

    return kernel


def shard_value_and_grad(
    X: jax.Array,  # (rows, d) f32, row-major
    w: jax.Array,  # (rows,)
    y: jax.Array,  # (rows,) class ids
    Wm: jax.Array,  # (C, d)
    b: jax.Array,  # (C,)
    tile: int,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One device's (sum_i w_i nll_i, sum_i w_i P_i x_i (C, d),
    sum_i w_i P_i (C,)) over its shard of the rows, from one read of them:
    the value and the gradients but for their labels' part
    (`ops/logistic._label_sums`).  `interpret`: Pallas' interpreter, for
    the CPU tests."""
    rows, d = X.shape
    C = Wm.shape[0]
    Cp = padded_classes(C)
    Wh, Wl = (jnp.pad(p, ((0, Cp - C), (0, 0)))
              for p in _bf16_parts(Wm.astype(jnp.float32), 2))
    b_col = jnp.pad(b.astype(jnp.float32), (0, Cp - C),
                    constant_values=-jnp.inf).reshape(Cp, 1)
    row_spec = pl.BlockSpec((1, tile), lambda i: (0, i))

    def whole(shape):
        return pl.BlockSpec(shape, lambda i: (0, 0))

    # traced with x64 off: a process that has it on (a float64 fit) makes
    # every Python index of the kernel 64-bit, which Mosaic does not lower
    with jax.enable_x64(False):
        gW, gb, stat = pl.pallas_call(
            _kernel(rows, tile),
            grid=(pl.cdiv(rows, tile),),
            in_specs=[
                pl.BlockSpec((tile, d), lambda i: (i, 0)),
                row_spec,  # w
                row_spec,  # y
                whole((Cp, d)),  # W's parts
                whole((Cp, d)),
                whole((Cp, 1)),  # b
            ],
            out_specs=[whole((Cp, d)), whole((Cp, _LANES)), whole((1, _LANES))],
            out_shape=[
                jax.ShapeDtypeStruct((Cp, d), jnp.float32),
                jax.ShapeDtypeStruct((Cp, _LANES), jnp.float32),
                jax.ShapeDtypeStruct((1, _LANES), jnp.float32),
            ],
            # the gradient's total, and what the compensated sums of the
            # gradients and the value rounded off
            scratch_shapes=[pltpu.VMEM((Cp, d), jnp.float32),
                            pltpu.VMEM((Cp, d), jnp.float32),
                            pltpu.VMEM((Cp, _LANES), jnp.float32),
                            pltpu.VMEM((1, _LANES), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                # the accumulators stay resident across the row tiles
                dimension_semantics=("arbitrary",),
                # what the plan says the kernel holds
                vmem_limit_bytes=min(vmem_bytes(tile, d, C), _VMEM_LIMIT_BYTES),
            ),
            cost_estimate=pl.CostEstimate(
                flops=12 * rows * d * Cp, transcendentals=rows * (Cp + 1),
                bytes_accessed=4 * (rows * d + 2 * rows + 2 * Cp * d),
            ),
            name="softmax_value_and_grad",
            interpret=interpret,
        )(
            X,
            w.astype(jnp.float32).reshape(1, rows),
            y.astype(jnp.int32).reshape(1, rows),
            Wh,
            Wl,
            b_col,
        )
    return stat[0].sum(), gW[:C], gb[:C].sum(axis=1)
