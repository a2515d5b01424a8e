#
# chipbench/data_models/softmax_labels.py: standard-normal f32 features and
# labels drawn from a softmax over `classes` classes, the usual stand-in for
# frozen embeddings under a linear probe.  A seeded W_true (classes, cols)
# is drawn once, scaled so that each class's logit x . W_true[c] has
# standard deviation `logit_std` over the rows; each row's label is drawn
# by Gumbel-max from softmax(x W_true^T): argmax_c (logit_c + Gumbel noise).
#
# The labels are NOT separable: a row's label is a draw, so the multinomial
# objective has an optimum at finite weights, and no iteration of a
# tol = 1e-30 fit runs the objective down to float32 noise (a separable
# label would: PERF.md §4).
#
# The logits that draw the labels are data, not a result: on a TPU they are
# one bfloat16 pass (the draw only needs a softmax near x W_true^T); the
# host's twin takes them in float32.
#
from __future__ import annotations

import numpy as np

NEEDS = ("classes", "logit_std")


def _params(data: dict):
    return int(data["classes"]), float(data["logit_std"])


def check(data: dict) -> None:
    try:
        classes, std = _params(data)
    except (TypeError, ValueError) as e:
        raise ValueError(f"softmax_labels: classes or logit_std unreadable ({e})")
    if classes < 2 or classes != data["classes"] or not std > 0:
        raise ValueError(
            f"softmax_labels: classes {data['classes']!r} (a count, at least 2), "
            f"logit_std {std!r} (> 0)")


def shared(key, cols: int, data: dict):
    """W_true (classes, cols) f32: 8 MB at 1,000 x 2,048."""
    import jax
    import jax.numpy as jnp

    classes, std = _params(data)
    return jax.random.normal(key, (classes, cols), jnp.float32) * (std / np.sqrt(cols))


def block(key, w_true, rows: int, cols: int, data: dict):
    """(features (rows, cols) f32, labels (rows,) f32 class ids)."""
    import jax
    import jax.numpy as jnp

    classes, _ = _params(data)
    kx, kg = jax.random.split(key)
    xb = jax.random.normal(kx, (rows, cols), jnp.float32)
    logits = jnp.matmul(xb, w_true.T) + jax.random.gumbel(kg, (rows, classes), jnp.float32)
    return xb, jnp.argmax(logits, axis=1).astype(jnp.float32)


def host_shared(rng: np.random.Generator, cols: int, data: dict):
    classes, std = _params(data)
    return (rng.standard_normal((classes, cols)) * (std / np.sqrt(cols))).astype(np.float32)


def host_block(rng: np.random.Generator, w_true, xb: np.ndarray, data: dict):
    """Fills `xb` (a block of the rows, f32) in place; gives its labels."""
    rng.standard_normal(dtype=np.float32, out=xb)
    logits = xb @ w_true.T + rng.gumbel(size=(len(xb), w_true.shape[0])).astype(np.float32)
    return np.argmax(logits, axis=1)
