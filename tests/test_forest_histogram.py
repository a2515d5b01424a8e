#
# One level's histogram (`ops/forest._level_histogram`) against numpy's
# `np.add.at` over the same tiles, exactly: at tiles under a lane's 128 rows
# (the product with features and bins merged into one axis) and over it
# (kept apart), in one panel and in several, for integer class counts and
# for real-valued regression statistics.
#
import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_ml_tpu.ops import forest as forest_ops

ROWS, NODES, FEATURES, BINS = 2048, 5, 6, 16


def _tiles(T, criterion, seed):
    """Bin ids (tiles, K, T), per-row statistics (tiles, S, T) as float64,
    the tile's node (NODES for a tile counted nowhere), rows of weight 0
    among them.  Regression labels are multiples of 1/8 under 4 in size:
    w y^2 has up to 12 significant bits, more than one bfloat16 part holds,
    and every sum of them is exact in float32."""
    rng = np.random.default_rng(seed)
    tiles = ROWS // T
    sel = rng.integers(0, BINS, (tiles, FEATURES, T)).astype(np.uint8)
    w = rng.poisson(1.0, (tiles, T)).astype(np.float64)
    node = np.sort(rng.integers(0, NODES + 1, tiles)).astype(np.int32)
    if criterion == forest_ops.VARIANCE:
        y = rng.integers(-31, 32, (tiles, T)) / 8.0
        stats = np.stack([w, w * y, w * y * y], axis=1)
    else:
        y = rng.integers(0, 3, (tiles, T))
        stats = w[:, None, :] * (y[:, None, :] == np.arange(3)[:, None])
    return sel, stats, node, y, w


@pytest.mark.parametrize("criterion", [forest_ops.GINI, forest_ops.VARIANCE],
                         ids=["gini", "variance"])
@pytest.mark.parametrize("panel", [FEATURES, 2], ids=["one_panel", "three_panels"])
@pytest.mark.parametrize("T", [8, 32, 64, 512])
def test_level_histogram_is_numpys_add_at_of_the_same_tiles(T, panel, criterion):
    sel, stats, node, y, w = _tiles(T, criterion, seed=T + panel + criterion)
    S = stats.shape[1]
    stats3 = forest_ops._three_parts(
        forest_ops._row_stats(jnp.asarray(w, jnp.float32), jnp.asarray(y, jnp.float32),
                              criterion, S),
        jnp.bfloat16)  # the chip's operand: each part exact in bfloat16
    step = min(ROWS // T, max(1, 1024 // T))
    got = forest_ops._level_histogram(jnp.asarray(sel), stats3, jnp.asarray(node),
                                      NODES, panel, BINS, step, jnp.float32)

    want = np.zeros((NODES + 1, FEATURES, BINS, S))
    tile, k, t = np.indices(sel.shape)
    np.add.at(want, (node[tile], k, sel), np.moveaxis(stats, 1, -1)[tile, t])
    assert got.shape == (NODES, FEATURES, BINS, S)
    np.testing.assert_array_equal(np.asarray(got, np.float64), want[:NODES])


def test_the_product_keeps_features_and_bins_apart_from_a_lanes_rows_on():
    assert [forest_ops.split_hist(T) for T in (8, 32, 64, 128, 256, 512)] == [
        False, False, False, True, True, True]
