#
# chipbench/data_models/low_rank.py: a mostly low-rank matrix with a
# bell-shaped singular profile and a fat tail, what the reference project's
# `gen_data.py low_rank_matrix` makes for its PCA row through sklearn's
# `make_low_rank_matrix`.  The singular profile is sklearn's own:
#   s_i = (1 - tail_strength) exp(-(i / effective_rank)^2)
#         + tail_strength exp(-0.1 i / effective_rank),   i = 0 .. cols - 1
# and the rows are X = G diag(s) V^T: V the Q of a seeded (cols, cols)
# normal matrix, drawn once; G standard-normal row blocks.  The covariance
# the rows are drawn from is V diag(s^2) V^T: with `effective_rank` 10 and
# `tail_strength` 0.5 (sklearn's defaults) its eigenvalues run 1, 0.980,
# 0.942, 0.888, ..., gaps of 2-5 % of the largest, so the top eigenvectors
# are single directions that float32 can be held to.  (With the repo's own
# port's `effective_rank = cols // 10` the top eigenvalues sit 3e-4 apart
# and the sample noise of a million rows mixes them.)
#
# Unlike sklearn, whose U is the Q of an (n, cols) normal matrix and has
# exactly orthonormal columns, a block of rows cannot know the others: G's
# columns are orthogonal only to 1/sqrt(rows), and X is sqrt(rows) times
# sklearn's scale (a row of X has squared norm sum s_i^2, about 18.8 at the
# defaults and 3,000 columns).  The label is 0: PCA reads none.
#
from __future__ import annotations

import numpy as np

NEEDS = ("effective_rank", "tail_strength")


def _params(data: dict):
    return float(data["effective_rank"]), float(data["tail_strength"])


def check(data: dict) -> None:
    try:
        rank, tail = _params(data)
    except (TypeError, ValueError) as e:
        raise ValueError(f"low_rank: effective_rank or tail_strength unreadable ({e})")
    if not rank > 0 or not 0.0 <= tail <= 1.0:
        raise ValueError(
            f"low_rank: effective_rank {data['effective_rank']!r} (> 0), "
            f"tail_strength {data['tail_strength']!r} (in [0, 1])")


def singular_profile(cols: int, data: dict) -> np.ndarray:
    """s_0 .. s_{cols-1}, float64, as sklearn's make_low_rank_matrix."""
    rank, tail = _params(data)
    i = np.arange(cols, dtype=np.float64)
    return (1.0 - tail) * np.exp(-((i / rank) ** 2)) + tail * np.exp(-0.1 * i / rank)


def shared(key, cols: int, data: dict):
    """diag(s) V^T, (cols, cols) f32: 36 MB at 3,000 columns."""
    import jax
    import jax.numpy as jnp

    V, _ = jnp.linalg.qr(jax.random.normal(key, (cols, cols), jnp.float32))
    return jnp.asarray(singular_profile(cols, data), jnp.float32)[:, None] * V.T


def block(key, mix, rows: int, cols: int, data: dict):
    import jax
    import jax.numpy as jnp

    G = jax.random.normal(key, (rows, cols), jnp.float32)
    xb = jnp.matmul(G, mix, precision=jax.lax.Precision.HIGHEST)
    return xb, jnp.zeros((rows,), jnp.float32)


def host_shared(rng: np.random.Generator, cols: int, data: dict):
    V, _ = np.linalg.qr(rng.standard_normal((cols, cols)))
    return (singular_profile(cols, data)[:, None] * V.T).astype(np.float32)


def host_block(rng: np.random.Generator, mix, xb: np.ndarray, data: dict):
    np.matmul(rng.standard_normal((len(xb), mix.shape[0]), dtype=np.float32), mix, out=xb)
    return np.zeros(len(xb))
