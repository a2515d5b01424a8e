#
# chipbench/datagen.py: the cell's rows, made ON the devices from --seed in
# one jitted call (the benchmark's "weights" are its data).
#
# The data model is the repo's own seeded stream (benchmark/gen_data.py
# `classification_slab`): standard-normal f32 features and a hidden
# direction `true_w`; labels are [x . true_w > 0] ("sign") for a
# classifier, x . true_w + N(0, 1) ("linear") for a regressor.  The draw
# differs from numpy's (jax's threefry, one key per device and row block),
# the distribution is the same.
#
# Traffic that fits from host memory draws the same model with numpy
# (`host_rows`); the comparison then puts those rows on the devices itself.
#
# Each device fills its own shard in row blocks: one jax.random.normal of a
# whole 6-12 GB shard would need its random bits beside its output.
#
from __future__ import annotations

import os

import numpy as np

from chipbench.blocks import block_rows_for

# rows drawn, or sent to a device, at a time: 0.3 GB of f32 at 3,000 columns
BLOCK_ROWS = 25_000


def _check_labels(labels: str) -> None:
    if labels not in ("sign", "linear"):
        raise ValueError(f"labels must be 'sign' or 'linear', got {labels!r}")


def _key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % (1 << 32)), seed >> 32)


def _generator(mesh, rows: int, cols: int, labels: str, block_rows: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    n_dev = mesh.devices.size
    shard_rows = rows // n_dev
    n_blocks = shard_rows // block_rows

    def shard(key):
        # this device's rows: blocks written in place into one buffer
        mine = jax.random.fold_in(key, 1 + jax.lax.axis_index(axis))
        true_w = jax.random.normal(jax.random.fold_in(key, 0), (cols,), jnp.float32)

        def fill(i, carry):
            X, y = carry
            kx, kn = jax.random.split(jax.random.fold_in(mine, i))
            xb = jax.random.normal(kx, (block_rows, cols), jnp.float32)
            score = jnp.matmul(xb, true_w, precision=jax.lax.Precision.HIGHEST)
            if labels == "sign":
                yb = (score > 0).astype(jnp.float32)
            else:
                yb = score + jax.random.normal(kn, (block_rows,), jnp.float32)
            at = i * block_rows
            X = jax.lax.dynamic_update_slice(X, xb, (at, 0))
            y = jax.lax.dynamic_update_slice(y, yb, (at,))
            return X, y

        X0 = jnp.zeros((shard_rows, cols), jnp.float32)
        y0 = jnp.zeros((shard_rows,), jnp.float32)
        X, y = jax.lax.fori_loop(0, n_blocks, fill, (X0, y0))
        return X, y, jnp.ones((shard_rows,), jnp.float32)

    sharded = jax.shard_map(
        shard, mesh=mesh, in_specs=P(),
        out_specs=(P(axis, None), P(axis), P(axis)), check_vma=False,
    )
    return jax.jit(sharded)


def make_rows(mesh, rows: int, cols: int, seed: int, labels: str,
              block_rows: int | None = None):
    """(X (rows, cols) f32, y (rows,) f32, ones (rows,) f32), rows sharded
    over the mesh's one axis, every value a function of `seed` alone."""
    _check_labels(labels)
    n_dev = mesh.devices.size
    if rows % n_dev:
        raise ValueError(f"{rows} rows do not divide over {n_dev} devices")
    b = block_rows_for(rows // n_dev, block_rows or BLOCK_ROWS)
    return _generator(mesh, rows, cols, labels, b)(_key(seed))


def host_rows(rows: int, cols: int, seed: int, labels: str, workers: int = 8):
    """The same data model drawn on the host with numpy, for traffic that
    fits from host memory: (X (rows, cols) f32 C-order, y (rows,) f64), each
    row block from its own child of `seed` on a few threads.  (A device
    array of this shape comes back from a v5e column-major, and the program
    would spend 37 s of every fit making it row-major: my chip run, PR 25.)"""
    from concurrent.futures import ThreadPoolExecutor

    _check_labels(labels)
    seed, block_rows = int(seed), BLOCK_ROWS
    true_w = np.random.default_rng([seed, 0]).standard_normal(cols).astype(np.float32)
    X = np.empty((rows, cols), np.float32)
    y = np.empty((rows,), np.float64)

    def fill(i: int) -> None:
        at = i * block_rows
        rng = np.random.default_rng([seed, 1 + i])
        xb = X[at:at + block_rows]
        rng.standard_normal(dtype=np.float32, out=xb)
        score = xb @ true_w
        if labels == "sign":
            y[at:at + len(xb)] = score > 0
        else:
            y[at:at + len(xb)] = score + rng.standard_normal(len(xb), dtype=np.float32)

    with ThreadPoolExecutor(max(1, min(workers, os.cpu_count() or 1))) as pool:
        list(pool.map(fill, range(-(-rows // block_rows))))
    return X, y


def put_rows(mesh, X: np.ndarray, y: np.ndarray):
    """Host rows onto the mesh, sharded by rows, as the reference reads them
    (f32 features, f32 labels).  Block by block into a buffer on each device:
    one device_put of the 12 GB would keep a second, re-laid-out copy of it on
    the host, and ran the 40 GiB one-chip machine out of memory (my chip
    run, PR 25)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    rows, cols = X.shape
    devices = list(mesh.devices.flat)
    shard_rows = rows // len(devices)
    write = jax.jit(
        lambda buf, blk, at: jax.lax.dynamic_update_slice(buf, blk, (at, 0)),
        donate_argnums=0)
    shards = []
    for k, dev in enumerate(devices):
        buf = jax.jit(lambda: jnp.zeros((shard_rows, cols), jnp.float32),
                      out_shardings=SingleDeviceSharding(dev))()
        for at in range(0, shard_rows, BLOCK_ROWS):
            lo = k * shard_rows + at
            blk = jax.device_put(X[lo:min(lo + BLOCK_ROWS, (k + 1) * shard_rows)], dev)
            buf = write(buf, blk, np.int32(at))
        shards.append(buf)
    Xd = jax.make_array_from_single_device_arrays(
        (rows, cols), NamedSharding(mesh, P(axis, None)), shards)
    return Xd, jax.device_put(y.astype(np.float32), NamedSharding(mesh, P(axis)))
