#
# chipbench/data_models/blobs.py: isotropic Gaussian blobs, what the
# reference project's `gen_data.py blobs` makes for its clustering rows
# through sklearn's `make_blobs` (the repo's benchmark/gen_data.py
# `gen_blobs`): `centers` centres uniform in `center_box`, each row a centre
# plus `cluster_std` x N(0, I).  The label is the centre's index.
#
# Unlike sklearn, which deals the rows out evenly and shuffles them, each row
# draws its centre uniformly (a block of rows cannot know the others'
# counts): the counts are multinomial, not equal.  `cluster_std` is one
# number.  Defaults are sklearn's own and nothing else's: `cluster_std` 1.0,
# `center_box` [-10, 10].
#
# On noise with no structure a Lloyd trajectory is chaotic and no number
# separates float32 from the precision below it (PERF.md §4); on these rows
# it does, which is why a clustering cell draws them.
#
from __future__ import annotations

import numpy as np

NEEDS = ("centers",)


def _params(data: dict):
    lo, hi = data.get("center_box", (-10.0, 10.0))
    return int(data["centers"]), float(data.get("cluster_std", 1.0)), float(lo), float(hi)


def check(data: dict) -> None:
    try:
        centers, std, lo, hi = _params(data)
    except (TypeError, ValueError) as e:
        raise ValueError(f"blobs: centers, cluster_std or center_box unreadable ({e})")
    if centers < 1 or centers != data["centers"] or not std > 0 or not lo < hi:
        raise ValueError(
            f"blobs: centers {data['centers']!r} (a count), cluster_std {std!r} (> 0), "
            f"center_box {[lo, hi]!r} (low < high)")


def shared(key, cols: int, data: dict):
    """The (centers, cols) centre matrix: 12 MB at 1000 x 3000."""
    import jax
    import jax.numpy as jnp

    centers, _, lo, hi = _params(data)
    return jax.random.uniform(key, (centers, cols), jnp.float32, lo, hi)


def block(key, centres, rows: int, cols: int, data: dict):
    import jax
    import jax.numpy as jnp

    centers, std, _, _ = _params(data)
    kc, kn = jax.random.split(key)
    which = jax.random.randint(kc, (rows,), 0, centers)
    xb = centres[which] + std * jax.random.normal(kn, (rows, cols), jnp.float32)
    return xb, which.astype(jnp.float32)


def host_shared(rng: np.random.Generator, cols: int, data: dict):
    centers, _, lo, hi = _params(data)
    return rng.uniform(lo, hi, (centers, cols)).astype(np.float32)


def host_block(rng: np.random.Generator, centres, xb: np.ndarray, data: dict):
    centers, std, _, _ = _params(data)
    which = rng.integers(0, centers, len(xb))
    rng.standard_normal(dtype=np.float32, out=xb)
    if std != 1.0:
        xb *= np.float32(std)
    xb += centres[which]
    return which
