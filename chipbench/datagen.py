#
# chipbench/datagen.py: the cell's rows, made ON the devices from --seed in
# one jitted call (the benchmark's "weights" are its data).
#
# WHAT is drawn belongs to the configuration: its file's `data` block names a
# data model, a file of its own under chipbench/data_models/ found by that
# name (`hidden_direction`, `blobs`), and holds the model's parameters.  A
# model says only what differs between models: what is drawn once from the
# key, and one block of rows.  HOW rows are drawn is here and shared: one
# key per device and row block (jax's threefry), each device filling its own
# shard in row blocks written in place (one jax.random.normal of a whole
# 6-12 GB shard would need its random bits beside its output).
#
# Traffic that fits from host memory draws the same model with numpy
# (`host_rows`: another stream, the same distribution); the comparison then
# puts those rows on the devices itself (`put_rows`).
#
from __future__ import annotations

import os

import numpy as np

from chipbench import manifest as mf
from chipbench.blocks import block_rows_for

# rows drawn, or sent to a device, at a time: 0.3 GB of f32 at 3,000 columns
BLOCK_ROWS = 25_000


def _model(data: dict):
    """The data model `data` names, once its block is seen to be whole."""
    found = mf.data_problems(data)
    if found:
        raise ValueError("; ".join(found))
    return mf.data_model(data["model"])


def _key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % (1 << 32)), seed >> 32)


def _generator(mesh, rows: int, cols: int, model, data: dict, block_rows: int):
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
        drawn_once = model.shared(jax.random.fold_in(key, 0), cols, data)

        def fill(i, carry):
            X, y = carry
            xb, yb = model.block(jax.random.fold_in(mine, i), drawn_once, block_rows, cols, data)
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


def make_rows(mesh, rows: int, cols: int, seed: int, data: dict,
              block_rows: int | None = None):
    """(X (rows, cols) f32, y (rows,) f32, ones (rows,) f32), rows sharded
    over the mesh's one axis, every value a function of `seed` and of the
    configuration's `data` block alone."""
    model = _model(data)
    n_dev = mesh.devices.size
    if rows % n_dev:
        raise ValueError(f"{rows} rows do not divide over {n_dev} devices")
    b = block_rows_for(rows // n_dev, block_rows or BLOCK_ROWS)
    return _generator(mesh, rows, cols, model, data, b)(_key(seed))


def host_rows(rows: int, cols: int, seed: int, data: dict, workers: int = 8):
    """The same data model drawn on the host with numpy, for traffic that
    fits from host memory: (X (rows, cols) f32 C-order, y (rows,) f64), each
    row block from its own child of `seed` on a few threads.  (A device
    array of this shape comes back from a v5e column-major, and the program
    would spend 37 s of every fit making it row-major: my chip run, PR 25.)"""
    from concurrent.futures import ThreadPoolExecutor

    model = _model(data)
    seed, block_rows = int(seed), BLOCK_ROWS
    drawn_once = model.host_shared(np.random.default_rng([seed, 0]), cols, data)
    X = np.empty((rows, cols), np.float32)
    y = np.empty((rows,), np.float64)

    def fill(i: int) -> None:
        at = i * block_rows
        xb = X[at:at + block_rows]
        y[at:at + len(xb)] = model.host_block(
            np.random.default_rng([seed, 1 + i]), drawn_once, xb, data)

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
