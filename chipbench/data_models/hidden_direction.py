#
# chipbench/data_models/hidden_direction.py: standard-normal f32 features and
# a hidden direction `true_w`, the repo's own seeded stream
# (benchmark/gen_data.py `classification_slab`).  `labels`: [x . true_w > 0]
# ("sign") for a classifier, x . true_w + N(0, 1) ("linear") for a
# regressor.  A configuration file with no `data` block draws this model,
# with its estimator family's LABELS.
#
# What a data model is (chipbench/datagen.py owns everything else: devices,
# row blocks, keys, threads): NEEDS, the keys its `data` block has to hold;
# check(data), a ValueError for a value it cannot draw; what is drawn once,
# `shared` on the devices and `host_shared` with numpy; one block of rows,
# `block` in jax.numpy and `host_block`, its numpy twin.
#
from __future__ import annotations

import numpy as np

NEEDS = ("labels",)


def check(data: dict) -> None:
    if data["labels"] not in ("sign", "linear"):
        raise ValueError(f"labels must be 'sign' or 'linear', got {data['labels']!r}")


def shared(key, cols: int, data: dict):
    import jax
    import jax.numpy as jnp

    return jax.random.normal(key, (cols,), jnp.float32)


def block(key, true_w, rows: int, cols: int, data: dict):
    """(features (rows, cols) f32, labels (rows,) f32) of one block's key."""
    import jax
    import jax.numpy as jnp

    kx, kn = jax.random.split(key)
    xb = jax.random.normal(kx, (rows, cols), jnp.float32)
    score = jnp.matmul(xb, true_w, precision=jax.lax.Precision.HIGHEST)
    if data["labels"] == "sign":
        return xb, (score > 0).astype(jnp.float32)
    return xb, score + jax.random.normal(kn, (rows,), jnp.float32)


def host_shared(rng: np.random.Generator, cols: int, data: dict):
    return rng.standard_normal(cols).astype(np.float32)


def host_block(rng: np.random.Generator, true_w, xb: np.ndarray, data: dict):
    """Fills `xb` (a block of the rows, f32) in place; gives its labels."""
    rng.standard_normal(dtype=np.float32, out=xb)
    score = xb @ true_w
    if data["labels"] == "sign":
        return score > 0
    return score + rng.standard_normal(len(xb), dtype=np.float32)
