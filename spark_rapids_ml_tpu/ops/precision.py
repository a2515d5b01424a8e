#
# Matmul precision for distance kernels whose OUTPUT IS A RANKING or a
# threshold decision (kNN / ANN neighbor ids, DBSCAN eps tests, the
# nearest-center assignment of a KMeans Lloyd step).
#
# TPU MXU "default" precision feeds f32 operands through bf16 passes:
# relative product error ~2^-8, i.e. up to ~0.8% of |x||y|.  Squared
# euclidean distances computed via the matmul identity then mis-rank
# neighbors whose true distance gap is below that error — measured on a
# v5e: CAGRA recall@10 fell from 0.996 (CPU, exact f32) to 0.58 (TPU,
# default precision) on 200k x 64 gaussian data.  Reference parity also
# demands exactness: cuML/cuVS brute-force and IVF kernels accumulate in
# true f32 (reference knn.py:688-779, 1516-1657).
#
# `distance_precision()` is read at TRACE time — set the config before
# the first fit/search.  "highest" = true f32 (6-pass); "high" = 3-pass
# bf16 (~2^-14 relative, usually rank-safe at small dims); "default" =
# fastest, rank-unsafe.  KMeans Lloyd is routed here too
# (`lloyd_precision`): it was thought to merely converge through its
# distances, but an assignment IS a ranking, and on clusterable rows one
# bf16 pass ends 14-32x further from the float64 trajectory than f32
# does (PERF.md §4).  The D2-sampling inits, which only draw from their
# distances, keep XLA's default.
#
from __future__ import annotations

import jax

from ..config import get_config

_LEVELS = {
    "highest": jax.lax.Precision.HIGHEST,
    "high": jax.lax.Precision.HIGH,
    "default": jax.lax.Precision.DEFAULT,
}


def distance_precision() -> jax.lax.Precision:
    """Precision for rank/threshold-critical distance matmuls
    (config key `distance_precision`, default "highest")."""
    name = str(get_config("distance_precision")).lower()
    if name not in _LEVELS:
        raise ValueError(
            f"distance_precision must be one of {sorted(_LEVELS)}, got {name!r}"
        )
    return _LEVELS[name]


def lloyd_precision() -> jax.lax.Precision:
    """Precision of a KMeans Lloyd step's two products (x.c of the
    assignment, the one-hot cluster sums of the update) and of the cost
    and predict passes: the `distance_precision` key, so true f32 by
    default, as cuML computes them.  Also what `ops/ivf.py` trains its
    quantizer with."""
    return distance_precision()


# "high_compensated" runs the chunk matmuls at HIGH (3-pass bf16) and
# additionally Kahan-compensates the f32 CHUNK-LEVEL accumulation in the
# streamed/fused statistics paths (ops/stats.py accumulator specs): the
# across-chunk floating-point drift plain "high" leaves uncontrolled —
# a later chunk's small contribution can vanish entirely against a large
# f32 running sum — is carried in a twin compensation array instead.
_STATS_LEVELS = dict(_LEVELS, high_compensated=jax.lax.Precision.HIGH)


def stats_precision() -> jax.lax.Precision:
    """Precision for sufficient-statistics matmuls whose output feeds a
    matrix inversion or eigendecomposition (PCA covariance, the linear-
    regression Gram/cross terms; in-memory AND streaming accumulators).
    cuML computes these in fp32; a default bf16 pass costs eigenvector/
    coefficient fidelity.  What "highest" costs: six bf16 MXU passes as
    XLA makes an f32 product, about 3.1 for the same six terms where the
    linear-regression Gram takes `ops/linear.split_gram_half` (f32 rows
    wider than a panel on TPUs: 0.36 s of device time at the reference's
    1M x 3000 on a v5e, PERF.md §6).  Config key `stats_precision`,
    default "highest"; "high" is three passes and drops terms (~2^-14
    relative error); "high_compensated" adds Kahan-compensated chunk
    accumulation on top of the 3-pass bf16 products (see
    `stats_compensated`)."""
    name = str(get_config("stats_precision")).lower()
    if name not in _STATS_LEVELS:
        raise ValueError(
            f"stats_precision must be one of {sorted(_STATS_LEVELS)}; "
            f"got {name!r}"
        )
    return _STATS_LEVELS[name]


def stats_compensated() -> bool:
    """Whether the chunked statistics accumulators (streaming.py and the
    fused stage-and-solve engine) carry a Kahan compensation term per
    accumulated array, bounding across-chunk f32 summation error
    independently of chunk count (`stats_precision="high_compensated"`)."""
    return str(get_config("stats_precision")).lower() == "high_compensated"
