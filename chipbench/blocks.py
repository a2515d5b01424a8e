#
# chipbench/blocks.py: run a plain function over device rows one row block
# at a time, so that a reference fits beside the rows it checks and its
# per-block results can be added in float64 on the host.
#
# Each device slices block k out of its own shard (no copy of the rows, no
# traffic between chips); the per-device results come back stacked on a
# leading axis.  A reshape of the rows into blocks would cost a second copy
# of them on a TPU (measured by a compile for a described v5e: 11.45 GB of
# HLO temp beside 11.18 GB of arguments).
#
from __future__ import annotations

# rows to a block: 50,000 x 3,000 f32 is 0.6 GB beside 12 GB of rows, and a
# `highest` Gram of it accumulates few enough rows in f32 to stay a reference
BLOCK_ROWS = 50_000


def block_rows_for(shard_rows: int, want: int) -> int:
    """The largest divisor of `shard_rows` that is at most `want`."""
    want = max(1, min(int(want), shard_rows))
    for b in range(want, 0, -1):
        if shard_rows % b == 0:
            return b
    return 1


def block_rows_of(X) -> int:
    """The block size for the device rows `X`: whole blocks to a shard."""
    return block_rows_for(X.shape[0] // X.sharding.mesh.devices.size, BLOCK_ROWS)


def block_caller(fn, mesh, block_rows: int, n_args: int):
    """jit of (X, y, k, *args) -> fn(X_block, y_block, *args) per device,
    outputs stacked over the devices.  X (rows, cols) and y (rows,) are
    sharded by rows over the mesh's one axis; args are replicated."""
    import jax
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]

    def local(Xl, yl, k, *args):
        Xb = jax.lax.dynamic_slice_in_dim(Xl, k * block_rows, block_rows, 0)
        yb = jax.lax.dynamic_slice_in_dim(yl, k * block_rows, block_rows, 0)
        return jax.tree.map(lambda a: a[None], fn(Xb, yb, *args))

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P()) + (P(),) * n_args,
        out_specs=P(axis), check_vma=False,
    ))


def sum_blocks(call, X, y, block_rows: int, *args):
    """Sum over every block and device, in float64 on the host, of each
    output of `call` (a block_caller over blocks of `block_rows_of(X)`)."""
    import jax
    import numpy as np

    shard_rows = X.shape[0] // X.sharding.mesh.devices.size
    outs = [call(X, y, np.int32(k), *args) for k in range(shard_rows // block_rows)]
    total = None
    for out in jax.device_get(outs):
        part = [np.sum(a, axis=0, dtype=np.float64) for a in out]
        total = part if total is None else [t + p for t, p in zip(total, part)]
    return total
