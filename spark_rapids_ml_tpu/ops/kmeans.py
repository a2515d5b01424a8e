#
# KMeans kernel — the TPU-native replacement for `cuml.cluster.kmeans_mg.
# KMeansMG.fit` (called from reference clustering.py:377-411): scalable
# k-means++ init + Lloyd iterations with in-kernel centroid allreduce.
#
# Design notes (TPU-first):
#   - Assignment is one (N,k) distance matrix built from a single X @ C^T
#     matmul (MXU) instead of per-point loops.
#   - The centroid update is a one-hot matmul (one more MXU pass); XLA
#     psums the per-shard partial sums over ICI — the NCCL allreduce the
#     cuML kernel does internally.
#   - k-means++ seeding runs fully on-device with the Gumbel-max trick:
#     sampling a global row index from the D² distribution is an argmax of
#     log(D²·w)+Gumbel — no host round-trips, no dynamic shapes, and it
#     reduces over the sharded axis like any other collective.
#   - Lloyd runs in a lax.while_loop with a center-shift tolerance, so the
#     whole fit is ONE compiled program regardless of iteration count,
#     while a device holds its rows twice beside the (rows, k) temporaries
#     (`kmeans_fit_auto`); past that the host dispatches one program per
#     row block, sized by the memory left beside the rows.
#   - Both products of a Lloyd step (x.c of the assignment, the cluster
#     sums of the update) run at `ops/precision.lloyd_precision()`: true
#     f32 by default, as cuML computes them.
#
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp

from ..tracing import event, trace

# Sample-weight/fold-mask contract (parallel/device_cache.py): every
# reduction here — init sampling logits, cluster sums/counts, inertia —
# weights rows by `w` (w=0 rows are never sampled and contribute nothing),
# so a w=0 row — zero padding OR a CV fold-mask hole — is mathematically
# absent.  The `random` init draws one variate per row of POSITIVE weight
# (`kmeans_init`), so padding never moves it; the D2-sampling inits
# (k-means++, k-means||) still draw one variate per padded row, so there
# a masked view and a compacted view of the same data converge to
# (possibly) different local optima.  KMeans therefore takes the cache's
# gather/compaction fold view (`_supports_fold_weights` stays False),
# which reproduces the legacy host-sliced trajectory exactly; the
# zero-weight invariance below is what makes bucket padding safe and is
# asserted by tests/test_device_cache.py.
SUPPORTS_ZERO_WEIGHT_ROWS = True


def _pairwise_sqdist(X: jax.Array, C: jax.Array, precision=None) -> jax.Array:
    """(N,k) squared euclidean distances via the matmul identity.  The
    x.c product runs at `precision`; None is XLA's default (one bf16 pass
    on a TPU), which the D2-sampling inits keep: they draw from the
    distances, nothing is held to them."""
    x2 = (X * X).sum(axis=1, keepdims=True)
    c2 = (C * C).sum(axis=1)
    d2 = x2 - 2.0 * jnp.matmul(X, C.T, precision=precision) + c2
    return jnp.maximum(d2, 0.0)


def _assign(X: jax.Array, C: jax.Array):
    """(labels, squared distance to the closest center) per row, the x.c
    product at `lloyd_precision()`."""
    from .precision import lloyd_precision

    # the scope names the kernel in a profile (metadata only)
    with jax.named_scope("kmeans_assign"):
        d2 = _pairwise_sqdist(X, C, lloyd_precision())
        return jnp.argmin(d2, axis=1), jnp.min(d2, axis=1)


def _cluster_sums(labels: jax.Array, X: jax.Array, w: jax.Array, k: int):
    """Weighted (k,d) sums and (k,) counts of the rows by label, as a
    one-hot matmul at `lloyd_precision()`.

    At `highest` over f32 rows the product is made of three one-pass
    bf16 products where XLA's own `highest` makes six: the one-hot operand
    is exact in bf16, so only the weighted rows need splitting into three
    bf16 parts that add up to them, and every partial product is exact in
    the MXU's f32 accumulator.  Measured on a v5e at 1M x 3000, k=1000
    (PERF.md §6, PR 29): a fit 2.43 -> 1.87 s, against the
    float64-accumulated reference as `highest` reads (per-centre median
    8.2e-8 both; `high`, 2 % cheaper still, reads 2.4x worse)."""
    from .precision import lloyd_precision

    precision = lloyd_precision()
    with jax.named_scope("kmeans_update"):
        if precision != jax.lax.Precision.HIGHEST or X.dtype != jnp.float32:
            onehot = jax.nn.one_hot(labels, k, dtype=X.dtype) * w[:, None]
            return jnp.matmul(onehot.T, X, precision=precision), onehot.sum(axis=0)
        onehot = jax.nn.one_hot(labels, k, dtype=jnp.bfloat16)
        rest, sums = X * w[:, None], jnp.zeros((k, X.shape[1]), jnp.float32)
        for _ in range(3):
            # reduce_precision, not a cast there and back: XLA may elide
            # that pair (xla_allow_excess_precision) and leave no remainder
            part = jax.lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
            sums = sums + jnp.matmul(onehot.T, part.astype(jnp.bfloat16),
                                     preferred_element_type=jnp.float32)
            rest = rest - part
        return sums, jax.ops.segment_sum(w, labels, k)


def lloyd_partials(C: jax.Array, X: jax.Array, w: jax.Array, k: int):
    """One block's share of a Lloyd step from the centers `C`: weighted
    cluster sums (k,d), counts (k,) and cost ().  The ONE assignment +
    update math of the fused, the stepwise and the epoch-streaming fit."""
    labels, min_d2 = _assign(X, C)
    sums, counts = _cluster_sums(labels, X, w, k)
    return sums, counts, (min_d2 * w).sum()


def random_init_rows(w: jax.Array, k: int, seed, interleaved_over: int = 1) -> jax.Array:
    """Positions in `w` of the k `random` initial centers, in center order.

    THE RULE (stated in `models/clustering.KMeans`, re-derived by the
    benchmark's plain reference): rank the rows of positive weight
    0..m-1 in dataset order; draw
    `g = jax.random.gumbel(jax.random.PRNGKey(seed), (m,), float32)`;
    center i is the row whose rank holds the i-th largest g, ties to the
    lower rank.  A function of (seed, k, m) alone: zero-weight rows,
    wherever they lie, and the device count do not enter.

    Dataset order is the order of `w`, unless the rows were dealt
    round-robin over `interleaved_over` > 1 devices
    (`parallel/mesh.RowStager`: dataset row r at position
    (r % g) * (n / g) + r // g), which is undone here.

    jax's partitionable threefry (pinned here) computes draw j from
    (key, j) alone, so the first m of a longer draw ARE the (m,) draw:
    one draw per padded row serves, with no m known on the host."""
    n, g_dev = w.shape[0], int(interleaved_over)
    valid = w > 0
    if g_dev > 1:  # to dataset order
        valid = valid.reshape(g_dev, n // g_dev).T.reshape(n)
    with jax.threefry_partitionable(True):
        g = jax.random.gumbel(jax.random.PRNGKey(seed), (n,), jnp.float32)
    rank = jnp.maximum(jnp.cumsum(valid) - 1, 0)
    _, idx = jax.lax.top_k(jnp.where(valid, jnp.take(g, rank), -jnp.inf), k)
    if g_dev > 1:  # dataset row -> staged position
        idx = (idx % g_dev) * (n // g_dev) + idx // g_dev
    return idx


_random_init_rows = jax.jit(random_init_rows, static_argnums=(1, 3))


# rows to one program of `take_rows`: one unrolled dynamic_slice each, so
# the program's compile time grows with it (~15 ms a row)
_TAKE_ROWS_CHUNK = 128


def _slice_rows(X, idx):
    return jnp.concatenate([
        jax.lax.dynamic_slice(X, (idx[i], jnp.zeros((), idx.dtype)), (1, X.shape[1]))
        for i in range(idx.shape[0])
    ])


@functools.lru_cache(maxsize=None)
def _slice_rows_program(mesh):
    """jit of (X, idx (_TAKE_ROWS_CHUNK,)) -> those rows of X, replicated;
    with a mesh, every device reads its own shard's and a psum joins them."""
    if mesh is None:
        return jax.jit(_slice_rows)
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]

    def local(Xl, idx):
        at = idx - jax.lax.axis_index(axis) * Xl.shape[0]
        mine = (at >= 0) & (at < Xl.shape[0])
        rows = _slice_rows(Xl, jnp.clip(at, 0, Xl.shape[0] - 1))
        return jax.lax.psum(jnp.where(mine[:, None], rows, 0.0), axis)

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis, None), P()), out_specs=P(),
        check_vma=False,
    ))


def take_rows(X: jax.Array, idx: jax.Array) -> jax.Array:
    """Rows `idx` of the resident rows `X`, replicated, WITHOUT a second
    copy of `X`.  A (rows, d) f32 array lies column-major on a TPU and
    XLA copies the whole operand of a row gather into row-major: 22.6 GB
    asked of a 15.75 GB chip at 1M x 3000 (a compile for a described
    v5e; a `while` over `dynamic_slice` copies its invariant operand
    too).  One unrolled `dynamic_slice` per row reads in place, each
    device from its own shard, `_TAKE_ROWS_CHUNK` rows to a program."""
    k, chunk = int(idx.shape[0]), _TAKE_ROWS_CHUNK
    program = _slice_rows_program(_row_mesh(X))
    idx = jnp.pad(idx, (0, -k % chunk))  # row 0 again; cut off below
    parts = [program(X, idx[at:at + chunk]) for at in range(0, k, chunk)]
    return jnp.concatenate(parts)[:k]


@partial(jax.jit, static_argnames=("k", "init", "interleaved_over"))
def kmeans_init(X: jax.Array, w: jax.Array, k: int, seed, init: str = "k-means++",
                interleaved_over: int = 1):
    """Seed k centers.  `k-means++`: sequential D²-weighted sampling via
    Gumbel-max (the quality target of cuML's scalable-k-means++ init,
    reference clustering.py:130 `init` default).  `random`: k distinct
    rows of positive weight, uniformly (Spark's `takeSample`; weights do
    not bias the draw), by the rule of `random_init_rows`.  The gather
    here copies `X` on a TPU (`take_rows`): the stepwise fit, the route
    of rows a device cannot hold twice, fetches the same rows in place."""
    n, d = X.shape
    if init == "random":
        return jnp.take(
            X, random_init_rows(w, k, seed, interleaved_over), axis=0
        )

    key = jax.random.PRNGKey(seed)
    # weights act as sampling probabilities (w·D² for k-means++); padded
    # rows (w=0) are never sampled
    log_w = jnp.where(w > 0, jnp.log(jnp.maximum(w, 1e-30)), -jnp.inf)

    def body(i, carry):
        centers, d2 = carry
        g = jax.random.gumbel(jax.random.fold_in(key, i), (n,), X.dtype)
        logits = jnp.where(d2 > 0, jnp.log(jnp.maximum(d2, 1e-30)), -jnp.inf) + log_w + g
        idx = jnp.argmax(logits)
        c = jnp.take(X, idx, axis=0)
        centers = centers.at[i].set(c)
        dist_new = ((X - c) ** 2).sum(axis=1)
        return centers, jnp.minimum(d2, dist_new)

    # first center: uniform over valid rows
    g0 = jax.random.gumbel(key, (n,), X.dtype)
    idx0 = jnp.argmax(g0 + log_w)
    c0 = jnp.take(X, idx0, axis=0)
    centers0 = jnp.zeros((k, d), X.dtype).at[0].set(c0)
    d2_0 = ((X - c0) ** 2).sum(axis=1)
    centers, _ = jax.lax.fori_loop(1, k, body, (centers0, d2_0))
    return centers


# independent k-means++ reductions of the k-means|| candidate pool; the
# best-by-weighted-cost draw wins (see the comment at the use site)
_REDUCE_TRIALS = 8


@partial(jax.jit, static_argnames=("k", "rounds", "m"))
def kmeans_parallel_init(X: jax.Array, w: jax.Array, k: int, seed,
                         rounds: int = 2, m: int = 4):
    """k-means|| scalable init (Bahmani et al.) — the TPU analog of cuML's
    `scalable-k-means++` (the init KMeansMG runs, reference
    clustering.py:377-411) and Spark's `initMode="k-means||"` with
    `initSteps` rounds.

    O(rounds) full D² passes instead of k sequential ones: each round draws
    `m` candidates AT ONCE from the D² distribution (Gumbel top-m is
    sampling without replacement), candidates are weighted by the mass they
    attract, and the small (1+rounds*m, d) weighted candidate set is reduced
    to k centers with the sequential Gumbel k-means++.  At k=100+, init cost
    drops from 100 passes to `rounds`+2 passes over the sharded data.
    """
    n, d = X.shape
    key = jax.random.PRNGKey(seed)
    log_w = jnp.where(w > 0, jnp.log(jnp.maximum(w, 1e-30)), -jnp.inf)

    g0 = jax.random.gumbel(key, (n,), X.dtype)
    idx0 = jnp.argmax(g0 + log_w)
    c0 = jnp.take(X, idx0, axis=0)
    C = 1 + rounds * m
    cands0 = jnp.zeros((C, d), X.dtype).at[0].set(c0)
    d2_0 = ((X - c0) ** 2).sum(axis=1)

    def round_body(r, carry):
        cands, d2 = carry
        g = jax.random.gumbel(jax.random.fold_in(key, r + 1), (n,), X.dtype)
        logits = (
            jnp.where(d2 > 0, jnp.log(jnp.maximum(d2, 1e-30)), -jnp.inf)
            + log_w + g
        )
        _, idx = jax.lax.top_k(logits, m)
        new = jnp.take(X, idx, axis=0)  # (m, d)
        cands = jax.lax.dynamic_update_slice(
            cands,
            new,
            (jnp.asarray(1 + r * m, jnp.int32), jnp.zeros((), jnp.int32)),
        )
        # already-chosen rows have d2=0 -> -inf logits -> never re-chosen
        d2 = jnp.minimum(d2, _pairwise_sqdist(X, new).min(axis=1))
        return cands, d2

    cands, _ = jax.lax.fori_loop(0, rounds, round_body, (cands0, d2_0))
    # weight candidates by the sample mass they attract (zero-weight
    # duplicates drop out of the k-means++ reduction below)
    labels = jnp.argmin(_pairwise_sqdist(X, cands), axis=1)
    counts = (jax.nn.one_hot(labels, C, dtype=X.dtype) * w[:, None]).sum(axis=0)
    # Reduce the pool with SEVERAL independent weighted k-means++ draws
    # and keep the lowest-cost one.  A single sequential draw misses a
    # whole cluster ~7% of the time even when the pool covers every
    # cluster (measured on 6 well-separated blobs: one Gumbel inversion
    # puts two seeds in one blob, Lloyd can never split them apart, and
    # the fit converges 7x off sklearn — the test_f32_kmeans_cost
    # failure).  sklearn buys robustness with n_init full restarts;
    # here the restarts run over the tiny (1+rounds*m, d) candidate set
    # only, so _REDUCE_TRIALS draws cost O(trials * k * C * d) — noise
    # next to the rounds+2 full data passes above.
    trial_seeds = seed + 1 + jnp.arange(_REDUCE_TRIALS)
    trials = jax.vmap(
        lambda s: kmeans_init(cands, counts, k, s, "k-means++")
    )(trial_seeds)
    costs = jax.vmap(
        lambda Cs: (jnp.min(_pairwise_sqdist(cands, Cs), axis=1) * counts).sum()
    )(trials)
    return trials[jnp.argmin(costs)]


def seed_sample_stride(n_total: int, init_rows: int) -> int:
    """Global row stride for the seeding subsample: every `stride`-th
    row of the dataset enters the k-means|| init, keeping the sampled
    pool at <= `init_rows` rows.  ONE owner for the formula shared by
    the epoch-streaming fit (streaming.py `kmeans_streaming_fit`, via
    the registered `kmeans_sample` statistic program) so the sampled
    pool cannot silently diverge between paths."""
    return max(1, -(-int(n_total) // max(int(init_rows), 1)))


def init_candidate_pool(k: int, init_steps: int, oversample: float) -> tuple:
    """(rounds, m) of the k-means|| init: `rounds` D2 passes that draw m
    candidates each, so a pool of 1 + rounds*m.  ONE owner shared by the
    fused init and the stepwise init's subsample sizing."""
    rounds = max(init_steps, 1)
    # per-round draw: l = oversample*k (Spark/cuML's oversampling
    # factor), bumped so the candidate pool can cover k centers
    m = max(int(round(oversample * k)), -(-(k - 1) // rounds), 1)
    return rounds, m


@partial(jax.jit, static_argnames=("k", "max_iter", "init", "init_steps",
                                   "oversample", "interleaved_over"))
def kmeans_fit(
    X: jax.Array,
    w: jax.Array,
    k: int,
    seed,
    max_iter: int = 300,
    tol: float = 1e-4,
    init: str = "scalable-k-means++",
    init_steps: int = 2,
    oversample: float = 2.0,
    interleaved_over: int = 1,
):
    """Distributed Lloyd with center-shift convergence.

    Returns (centers (k,d), cost (weighted inertia), n_iter).
    Convergence matches Spark MLlib semantics: stop when every center moves
    less than `tol` (euclidean).  `interleaved_over`: the row layout the
    `random` init ranks its rows through (`random_init_rows`).
    """
    n = X.shape[0]
    if init in ("scalable-k-means++", "k-means||"):
        rounds, m = init_candidate_pool(k, init_steps, oversample)
        m = min(m, n)
        centers = kmeans_parallel_init(X, w, k, seed, rounds=rounds, m=m)
    else:
        centers = kmeans_init(X, w, k, seed, init, interleaved_over)

    def cond(state):
        _, shift2, it, _ = state
        return (it < max_iter) & (shift2 > tol * tol)

    def body(state):
        C, _, it, _ = state
        sums, counts, cost = lloyd_partials(C, X, w, k)  # psum over shards
        new_C = _new_centers(C, sums, counts)
        shift2 = ((new_C - C) ** 2).sum(axis=1).max()
        return new_C, shift2, it + 1, cost

    init_state = (centers, jnp.array(jnp.inf, X.dtype), jnp.array(0, jnp.int32),
                  jnp.array(0.0, X.dtype))
    centers, _, n_iter, _ = jax.lax.while_loop(cond, body, init_state)
    # final cost under the final centers
    _, min_d2 = _assign(X, centers)
    cost = (min_d2 * w).sum()
    return centers, cost, n_iter


def _new_centers(C, sums, counts):
    """Centers from the weighted sums; an empty cluster keeps its center.
    Guards only against zero weight: fractional total weights (<1) must
    still divide exactly."""
    return jnp.where(
        counts[:, None] > 0,
        sums / jnp.where(counts > 0, counts, 1.0)[:, None],
        C,
    )


def _row_block(X, w, start, rows: int):
    """Rows [start, start + rows) of a device's own X and w: a slice, so
    no second copy of the rows (a reshape into blocks is one, PERF.md)."""
    Xb = jax.lax.dynamic_slice(X, (start, jnp.zeros((), jnp.int32)),
                               (rows, X.shape[1]))
    return Xb, jax.lax.dynamic_slice(w, (start,), (rows,))


def _lloyd_block_step(acc, C, X, w, start, rows: int, k: int):
    """Assignment + weighted partial sums over one row block, added to
    acc = (sums (k,d), counts (k,), cost ()), each with or without a
    leading axis of one (a device's own accumulators, `_block_programs`)."""
    part = lloyd_partials(C, *_row_block(X, w, start, rows), k)
    return jax.tree.map(lambda a, p: a + p.reshape(a.shape), acc, part)


def _lloyd_block_cost(cost, C, X, w, start, rows: int):
    """One row block's share of the weighted cost under `C`, added to
    `cost`: the assignment alone, no sums."""
    Xb, wb = _row_block(X, w, start, rows)
    return cost + (_assign(Xb, C)[1] * wb).sum().reshape(cost.shape)


@functools.lru_cache(maxsize=None)
def _block_programs(mesh, rows: int, k: int):
    """(step, cost): `_lloyd_block_step` / `_lloyd_block_cost` over blocks
    of `rows` rows, jitted under those names (the benchmark finds their
    device time by them), the accumulator donated.  With a mesh the rows
    are sharded over it: every device slices the block out of ITS shard
    (`start` counts from the shard's first row) into its own accumulators,
    stacked on a leading device axis, and nothing crosses chips until
    `_lloyd_center_update` sums that axis, once an iteration.  A
    `dynamic_slice` of the global array would move rows between chips."""
    step = functools.wraps(_lloyd_block_step)(partial(_lloyd_block_step, rows=rows, k=k))
    cost = functools.wraps(_lloyd_block_cost)(partial(_lloyd_block_cost, rows=rows))
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        axis = mesh.axis_names[0]
        specs = (P(axis), P(), P(axis, None), P(axis), P())
        step, cost = (
            jax.shard_map(fn, mesh=mesh, in_specs=specs, out_specs=P(axis),
                          check_vma=False)
            for fn in (step, cost)
        )
    return jax.jit(step, donate_argnums=(0,)), jax.jit(cost, donate_argnums=(0,))


@jax.jit
def _lloyd_center_update(C, sums, counts):
    """New centers and the largest squared shift.  Accumulators with a
    leading device axis (`_block_programs`) are summed over it."""
    if sums.ndim == 3:
        sums, counts = sums.sum(axis=0), counts.sum(axis=0)
    new_C = _new_centers(C, sums, counts)
    shift2 = ((new_C - C) ** 2).sum(axis=1).max()
    return new_C, shift2


def _row_mesh(X):
    """The mesh `X`'s rows are sharded over, None for one device."""
    sharding = getattr(X, "sharding", None)
    mesh = getattr(sharding, "mesh", None)
    if mesh is None or mesh.devices.size == 1:
        return None
    if tuple(sharding.spec)[:1] != (mesh.axis_names[0],):
        raise ValueError(
            f"KMeans takes rows sharded over the mesh's first axis, got {sharding.spec}"
        )
    return mesh


def _shard_rows(X) -> int:
    """Rows of `X` one device holds."""
    mesh = _row_mesh(X)
    return int(X.shape[0]) // (mesh.devices.size if mesh is not None else 1)


def lloyd_row_bytes(d: int, k: int, itemsize: int = 4, sliced: bool = True) -> int:
    """Device bytes one row of a Lloyd program may cost beside the resident
    rows: its slice of the features (`sliced`: a block of the stepwise
    route; the fused program reads the rows themselves), the split of its
    features into bf16 parts that a multi-pass product reads (three at
    `highest`), and its rows of the (rows, k) distance matrix and one-hot.
    An upper count: on a v5e the compiler fuses nearly all of it into the
    two products (13 MB of HLO temp for a 62,500-row block, PERF.md §6)."""
    return (itemsize * d if sliced else 0) + 6 * d + 2 * itemsize * k


# 65,536 x 3,000 x 1,000 is 0.4 TFLOP a block: tens of milliseconds of
# MXU work behind every ~100 us dispatch
_MAX_BLOCK_ROWS = 65_536


def lloyd_block_rows(X: jax.Array, k: int) -> int:
    """Rows to a block of the stepwise Lloyd, equal blocks that tile a
    device's shard: as many as half the memory the device has left beside
    its shard pays for (`lloyd_row_bytes`), and no more than
    `_MAX_BLOCK_ROWS`, past which a block only costs memory."""
    from ..parallel.device_cache import bytes_beside

    shard_rows = _shard_rows(X)
    per_row = lloyd_row_bytes(int(X.shape[1]), k, X.dtype.itemsize)
    limit = max(1, min(bytes_beside(X) // 2 // per_row, _MAX_BLOCK_ROWS, shard_rows))
    return -(-shard_rows // -(-shard_rows // limit))


def kmeans_fit_auto(
    X: jax.Array,
    w: jax.Array,
    k: int,
    seed,
    max_iter: int = 300,
    tol: float = 1e-4,
    init: str = "scalable-k-means++",
    init_steps: int = 2,
    oversample: float = 2.0,
    interleaved_over: int = 1,
    checkpoint_path: str = None,
    checkpoint_tag: str = "",
):
    """The ONE fused-vs-stepwise gate, by MEMORY: the fused
    single-program solver while a device holds its shard of the rows
    twice (XLA copies a `while_loop`'s invariant operands) beside the
    program's (rows, k) distance matrix and one-hot
    (`parallel/device_cache.fused_program_fits`, the test the logistic
    router reads too); else the host-dispatched stepwise Lloyd in row
    blocks sized by what the device has left (`lloyd_block_rows`).  At
    the reference's 1M x 3000, k=1000 on one 15.75 GB chip: stepwise.
    Shared by the KMeans model (models/clustering.py) and the IVF
    quantizer/codebook training (ops/ivf.py).
    `checkpoint_path` forces the stepwise solver regardless of size: the
    fused while_loop is one opaque device program with no iteration
    boundary to checkpoint at, while the stepwise loop persists centers
    per iteration and RESUMES after a crash (resilience/checkpoint.py).
    Which route ran is a fact of the fit: the instant
    `kmeans_route[fused|stepwise]` in its report.
    Returns (centers, cost, n_iter, used_stepwise)."""
    from ..parallel.device_cache import fused_program_fits

    temp = _shard_rows(X) * lloyd_row_bytes(
        int(X.shape[1]), k, X.dtype.itemsize, sliced=False
    )
    fits = fused_program_fits(X, temp)
    kwargs = dict(k=k, seed=seed, max_iter=max_iter, tol=tol, init=init,
                  init_steps=init_steps, oversample=oversample,
                  interleaved_over=interleaved_over)
    if fits and not checkpoint_path:
        event("kmeans_route[fused]",
              detail=f"two copies of the shard and {temp:.3g} B of "
                     "temporaries fit the device")
        # asynchronous: the wait for the one program lands in the
        # caller's fetch
        centers, cost, n_iter = kmeans_fit(X, w, **kwargs)
        return centers, cost, n_iter, False
    block_rows = lloyd_block_rows(X, k)
    event("kmeans_route[stepwise]",
          detail=f"block_rows={block_rows}, checkpointing "
                 f"{'on' if checkpoint_path else 'off'}, the fused program "
                 f"{'fits' if fits else 'does NOT fit'} the device")
    centers, cost, n_iter = kmeans_fit_stepwise(
        X, w, block_rows=block_rows, checkpoint_path=checkpoint_path,
        checkpoint_tag=checkpoint_tag, **kwargs
    )
    return centers, cost, n_iter, True


def kmeans_fit_stepwise(
    X: jax.Array,
    w: jax.Array,
    k: int,
    seed,
    max_iter: int = 300,
    tol: float = 1e-4,
    init: str = "scalable-k-means++",
    init_steps: int = 2,
    oversample: float = 2.0,
    interleaved_over: int = 1,
    block_rows: int = None,
    init_rows: int = 262_144,
    checkpoint_path: str = None,
    checkpoint_tag: str = "",
):
    """Lloyd with HOST-dispatched iterations for device-resident data.

    The fused `kmeans_fit` is one program over all the rows: it needs
    them twice, and a distance matrix and a one-hot of (rows, k).  Where
    a device cannot hold that (the reference benchmark config, 1M x 3000,
    k=1000, reference python/benchmark/databricks/run_benchmark.sh:74-82:
    12 GB of rows on a 15.75 GB chip), this variant dispatches one
    program per block of `block_rows` rows per iteration (default
    `lloyd_block_rows`: what fits beside the rows), each device slicing
    the block out of its own shard, updates centers on device, and
    fetches only the shift scalar.  The D2-sampling inits are ONE program
    over their rows with (rows, candidates) distance matrices, so they
    seed from a strided subsample of at most `init_rows` rows that fits
    the same memory (the `kmeans_streaming_fit` contract); `random`
    draws from every row.  Same update math as `kmeans_fit`
    (`lloyd_partials`); trajectories match up to f32 reduction order
    when seeded identically.

    Spans (docs/observability.md): `kmeans_init`, one `kmeans_lloyd_iter`
    per iteration (block dispatches to the shift on the host),
    `kmeans_cost` (the final pass under the final centers).

    `checkpoint_path`/`checkpoint_tag`: per-iteration center checkpoint
    via the shared contract (resilience/checkpoint.py) — a crashed or
    preempted fit resumes at its last completed Lloyd iteration instead
    of re-seeding and restarting at iteration 0."""
    import numpy as np

    from ..parallel.device_cache import bytes_beside
    from ..resilience import maybe_inject
    from ..resilience.checkpoint import (
        clear_checkpoint,
        load_checkpoint,
        save_checkpoint,
    )

    n, d = X.shape
    start_it = 0
    resumed = (
        load_checkpoint(checkpoint_path, checkpoint_tag)
        if checkpoint_path
        else None
    )
    with trace("kmeans_init"):
        if resumed is not None:
            # centers persist in f64 (host truth); the device consumes X.dtype
            C = jnp.asarray(np.asarray(resumed["centers"]), X.dtype)
            start_it = int(resumed["it"])
            event("kmeans_resume", detail=f"it={start_it}")
        elif init == "random":
            C = take_rows(X, _random_init_rows(w, k, seed, interleaved_over))
        else:
            # the init is ONE compiled program: its subsample's (rows,
            # candidates) distance matrices must fit beside the rows
            rounds, m = init_candidate_pool(k, init_steps, oversample)
            pool = 1 + rounds * m if init != "k-means++" else k
            per_row = X.dtype.itemsize * (d + 2 * pool)
            n_init = max(min(n, init_rows, bytes_beside(X) // 2 // per_row), k)
            stride = max(1, -(-n // n_init))
            Xs, ws = (X[::stride], w[::stride]) if stride > 1 else (X, w)
            if init == "k-means++":
                C = kmeans_init(Xs, ws, k, seed, init)
            else:
                m = min(m, int(Xs.shape[0]))
                C = kmeans_parallel_init(Xs, ws, k, seed, rounds=rounds, m=m)
        # the span is the init's time, not its dispatch
        C = jax.block_until_ready(C)

    # ---- blocked Lloyd ----
    mesh = _row_mesh(X)
    shard_rows = _shard_rows(X)
    if block_rows is None:
        block_rows = lloyd_block_rows(X, k)
    block = max(1, min(int(block_rows), shard_rows))
    n_full, tail = divmod(shard_rows, block)
    blocks = [(i * block, block) for i in range(n_full)]
    if tail:
        blocks.append((n_full * block, tail))

    lead, placed = (), {}
    if mesh is not None:  # one accumulator a device, stacked and so sharded
        from jax.sharding import NamedSharding, PartitionSpec

        lead = (mesh.devices.size,)
        placed = {"device": NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))}

    def zeros(*shape):
        return jnp.zeros(lead + shape, X.dtype, **placed)

    def one_pass(acc, which, C):
        for start, rows in blocks:
            acc = _block_programs(mesh, rows, k)[which](
                acc, C, X, w, jnp.asarray(start, jnp.int32)
            )
        return acc

    from ..telemetry import Heartbeat

    hb = Heartbeat("kmeans_lloyd", total=max_iter)
    n_iter = start_it
    for n_iter in range(start_it + 1, max_iter + 1):
        with trace("kmeans_lloyd_iter"):
            maybe_inject("kmeans_lloyd")
            sums, counts, _ = one_pass((zeros(k, d), zeros(k), zeros()), 0, C)
            C, shift2 = _lloyd_center_update(C, sums, counts)
            shift2 = float(np.asarray(shift2))  # scalar fetch = sync
            hb.beat(n_iter, detail=f"shift2={shift2:.3e}")
            if checkpoint_path:
                save_checkpoint(
                    checkpoint_path, checkpoint_tag,
                    {"centers": np.asarray(C, np.float64), "it": n_iter},
                )
        if shift2 <= tol * tol:
            break
    with trace("kmeans_cost"):
        # the span is the pass, not its dispatch
        cost = jax.block_until_ready(one_pass(zeros(), 1, C).sum())
    # end-mark on NORMAL completion only — AFTER the final cost pass: a
    # fit that dies anywhere before the result exists must leave its
    # last iteration/loss visible for the flight recorder's post-mortem
    # (telemetry/heartbeat.py Heartbeat.close)
    hb.close()
    if checkpoint_path:
        clear_checkpoint(checkpoint_path)
    return C, cost, n_iter


@jax.jit
def kmeans_predict(X: jax.Array, C: jax.Array) -> jax.Array:
    return _assign(X, C)[0].astype(jnp.int32)


@jax.jit
def kmeans_cost(X: jax.Array, w: jax.Array, C: jax.Array) -> jax.Array:
    """Weighted sum of squared distances to the closest center (Spark's
    `summary.trainingCost` / cuML inertia)."""
    return (_assign(X, C)[1] * w).sum()
