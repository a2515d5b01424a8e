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
#     whole fit is ONE compiled program regardless of iteration count.
#
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# Sample-weight/fold-mask contract (parallel/device_cache.py): every
# reduction here — init sampling logits, cluster sums/counts, inertia —
# weights rows by `w` (w=0 rows are never sampled and contribute nothing),
# so a w=0 row — zero padding OR a CV fold-mask hole — is mathematically
# absent.  NOTE the trajectory is still row-COUNT sensitive: the seeded
# Gumbel inits draw one variate per padded row, so a masked view and a
# compacted view of the same data converge to (possibly) different local
# optima.  KMeans therefore takes the cache's gather/compaction fold view
# (`_supports_fold_weights` stays False), which reproduces the legacy
# host-sliced trajectory exactly; the zero-weight invariance below is
# what makes bucket padding safe and is asserted by
# tests/test_device_cache.py.
SUPPORTS_ZERO_WEIGHT_ROWS = True


def _pairwise_sqdist(X: jax.Array, C: jax.Array) -> jax.Array:
    """(N,k) squared euclidean distances via the matmul identity."""
    x2 = (X * X).sum(axis=1, keepdims=True)
    c2 = (C * C).sum(axis=1)
    d2 = x2 - 2.0 * (X @ C.T) + c2
    return jnp.maximum(d2, 0.0)


@partial(jax.jit, static_argnames=("k", "init"))
def kmeans_init(X: jax.Array, w: jax.Array, k: int, seed, init: str = "k-means++"):
    """Seed k centers.  `k-means++`: sequential D²-weighted sampling via
    Gumbel-max (the quality target of cuML's scalable-k-means++ init,
    reference clustering.py:130 `init` default).  `random`: Gumbel top-k
    uniform over valid rows."""
    n, d = X.shape
    key = jax.random.PRNGKey(seed)
    # weights act as sampling probabilities (w·D² for k-means++); padded
    # rows (w=0) are never sampled
    log_w = jnp.where(w > 0, jnp.log(jnp.maximum(w, 1e-30)), -jnp.inf)

    if init == "random":
        g = jax.random.gumbel(key, (n,), X.dtype)
        _, idx = jax.lax.top_k(g + log_w, k)
        return jnp.take(X, idx, axis=0)

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


def init_flops_accounting(
    init: str, k: int, d: int, init_steps: int, oversample: float
) -> tuple:
    """Shared init cost model: (rounds, m, flops_per_row) for a given
    init scheme.  Single source of truth for the fused-vs-stepwise gate
    (models/clustering.py), the stepwise init subsampling below, and the
    fused init's candidate-pool size — these MUST stay in lock-step or
    the gate stops matching the budget it mirrors.
      scalable: `rounds` D2 passes vs m candidates + one labeling pass
                vs the 1 + rounds*m pool
      random:   one Gumbel top-k pass, no matmuls
      k-means++: k sequential D2 passes
    """
    rounds = max(init_steps, 1)
    # per-round draw: l = oversample*k (Spark/cuML's oversampling
    # factor), bumped so the candidate pool can cover k centers
    m = max(int(round(oversample * k)), -(-(k - 1) // rounds), 1)
    if init in ("scalable-k-means++", "k-means||"):
        per_row = 2.0 * d * (rounds * m + (1 + rounds * m))
    elif init == "random":
        per_row = 1.0
    else:  # sequential k-means++
        per_row = 2.0 * d * k
    return rounds, m, per_row


@partial(jax.jit, static_argnames=("k", "max_iter", "init", "init_steps", "oversample"))
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
):
    """Distributed Lloyd with center-shift convergence.

    Returns (centers (k,d), cost (weighted inertia), n_iter).
    Convergence matches Spark MLlib semantics: stop when every center moves
    less than `tol` (euclidean).
    """
    n = X.shape[0]
    if init in ("scalable-k-means++", "k-means||"):
        rounds, m, _ = init_flops_accounting(
            init, k, X.shape[1], init_steps, oversample
        )
        m = min(m, n)
        centers = kmeans_parallel_init(X, w, k, seed, rounds=rounds, m=m)
    else:
        centers = kmeans_init(X, w, k, seed, init)

    def assign(C):
        d2 = _pairwise_sqdist(X, C)
        labels = jnp.argmin(d2, axis=1)
        min_d2 = jnp.min(d2, axis=1)
        return labels, min_d2

    def update(C):
        labels, min_d2 = assign(C)
        onehot = jax.nn.one_hot(labels, k, dtype=X.dtype) * w[:, None]
        counts = onehot.sum(axis=0)  # (k,)  — psum over shards
        sums = onehot.T @ X  # (k,d) — MXU + psum
        # guard only against zero weight — fractional total weights (<1)
        # must still divide exactly
        new_C = jnp.where(
            counts[:, None] > 0, sums / jnp.where(counts > 0, counts, 1.0)[:, None], C
        )
        cost = (min_d2 * w).sum()
        return new_C, cost

    def cond(state):
        _, shift2, it, _ = state
        return (it < max_iter) & (shift2 > tol * tol)

    def body(state):
        C, _, it, _ = state
        new_C, cost = update(C)
        shift2 = ((new_C - C) ** 2).sum(axis=1).max()
        return new_C, shift2, it + 1, cost

    init_state = (centers, jnp.array(jnp.inf, X.dtype), jnp.array(0, jnp.int32),
                  jnp.array(0.0, X.dtype))
    centers, _, n_iter, _ = jax.lax.while_loop(cond, body, init_state)
    # final cost under the final centers
    _, min_d2 = assign(centers)
    cost = (min_d2 * w).sum()
    return centers, cost, n_iter


@partial(jax.jit, static_argnames=("rows", "k"), donate_argnums=(0,))
def _lloyd_block_step(acc, C, X, w, start, rows: int, k: int):
    """Assignment + weighted partial sums over one row block.
    acc = (sums (k,d), counts (k,), cost ()) — donated, in-place."""
    sums, counts, cost = acc
    Xb = jax.lax.dynamic_slice(X, (start, jnp.zeros((), jnp.int32)),
                               (rows, X.shape[1]))
    wb = jax.lax.dynamic_slice(w, (start,), (rows,))
    d2 = _pairwise_sqdist(Xb, C)
    labels = jnp.argmin(d2, axis=1)
    onehot = jax.nn.one_hot(labels, k, dtype=X.dtype) * wb[:, None]
    return (
        sums + onehot.T @ Xb,
        counts + onehot.sum(axis=0),
        cost + (jnp.min(d2, axis=1) * wb).sum(),
    )


@jax.jit
def _lloyd_center_update(C, sums, counts):
    new_C = jnp.where(
        counts[:, None] > 0,
        sums / jnp.where(counts > 0, counts, 1.0)[:, None],
        C,
    )
    shift2 = ((new_C - C) ** 2).sum(axis=1).max()
    return new_C, shift2


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
    budget: float = None,
    checkpoint_path: str = None,
    checkpoint_tag: str = "",
):
    """The ONE fused-vs-stepwise gate (dispatch rule): the fused
    single-program solver while `2·n·d·k·max_iter + n·init_per_row`
    FLOPs fit the per-program budget (`dispatch_flops_limit` when
    `budget` is None), else the host-dispatched stepwise Lloyd.  Shared
    by the KMeans model (models/clustering.py) and the IVF quantizer/
    codebook training (ops/ivf.py) so the cost model cannot diverge.
    `checkpoint_path` forces the stepwise solver regardless of size: the
    fused while_loop is one opaque device program with no iteration
    boundary to checkpoint at, while the stepwise loop persists centers
    per iteration and RESUMES after a crash (resilience/checkpoint.py).
    Returns (centers, cost, n_iter, used_stepwise)."""
    if budget is None:
        from ..config import get_config

        budget = float(get_config("dispatch_flops_limit"))
    n, d = int(X.shape[0]), int(X.shape[1])
    _, _, init_per_row = init_flops_accounting(
        init, k, d, init_steps, oversample
    )
    fused_flops = 2.0 * n * d * k * max(max_iter, 1) + n * init_per_row
    kwargs = dict(k=k, seed=seed, max_iter=max_iter, tol=tol, init=init,
                  init_steps=init_steps, oversample=oversample)
    if fused_flops <= budget and not checkpoint_path:
        centers, cost, n_iter = kmeans_fit(X, w, **kwargs)
        return centers, cost, n_iter, False
    centers, cost, n_iter = kmeans_fit_stepwise(
        X, w, flops_budget=budget, checkpoint_path=checkpoint_path,
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
    flops_budget: float = 2e12,
    init_rows: int = 262_144,
    checkpoint_path: str = None,
    checkpoint_tag: str = "",
):
    """Lloyd with HOST-dispatched iterations for device-resident data.

    The fused `kmeans_fit` compiles the whole solve into one program;
    past the per-program FLOP budget (`dispatch_flops_limit` — sized for
    a development link that no longer exists, kept until re-justified on
    the chip or deleted, ROADMAP Design 3) the solve is split.  At e.g.
    the reference benchmark config (1M x 3000, k=1000, reference
    python/benchmark/databricks/run_benchmark.sh:74-82) one assignment
    pass alone is ~6e12 FLOPs, so this variant dispatches one program per
    row block per iteration (block size from `flops_budget`), updates
    centers on device, and fetches only the 8-byte shift scalar.  When
    the init's D2 passes would themselves exceed the budget, seeding runs
    on a strided subsample (the `kmeans_streaming_fit` contract).  Same
    update math as `kmeans_fit`; trajectories match up to f32 reduction
    order when seeded identically.

    `checkpoint_path`/`checkpoint_tag`: per-iteration center checkpoint
    via the shared contract (resilience/checkpoint.py) — a crashed or
    preempted fit resumes at its last completed Lloyd iteration instead
    of re-seeding and restarting at iteration 0."""
    import numpy as np

    from ..resilience import maybe_inject
    from ..resilience.checkpoint import (
        clear_checkpoint,
        load_checkpoint,
        save_checkpoint,
    )

    n, d = X.shape
    # ---- seeding ----
    # the init is ONE compiled program, so the subsample must bring ITS
    # work under the same per-program budget the Lloyd blocks respect
    # (cost model shared with the fused-vs-stepwise gate:
    # init_flops_accounting above)
    rounds, m, per_row = init_flops_accounting(
        init, k, d, init_steps, oversample
    )
    n_init_max = max(int(flops_budget // per_row), k)
    n_init = min(n, init_rows if per_row > 1.0 else n, n_init_max)
    if n_init < n:
        stride = max(1, -(-n // n_init))
        Xs, ws = X[::stride], w[::stride]
    else:
        Xs, ws = X, w
    start_it = 0
    resumed = (
        load_checkpoint(checkpoint_path, checkpoint_tag)
        if checkpoint_path
        else None
    )
    if resumed is not None:
        # centers persist in f64 (host truth); the device consumes X.dtype
        C = jnp.asarray(np.asarray(resumed["centers"]), X.dtype)
        start_it = int(resumed["it"])
        from ..tracing import event

        event("kmeans_resume", detail=f"it={start_it}")
    elif init in ("scalable-k-means++", "k-means||"):
        m = min(m, int(Xs.shape[0]))
        C = kmeans_parallel_init(Xs, ws, k, seed, rounds=rounds, m=m)
    else:
        C = kmeans_init(Xs, ws, k, seed, init)

    # ---- blocked Lloyd ----
    block = max(1, min(n, int(flops_budget // max(2.0 * d * k, 1.0))))
    n_full, tail = divmod(n, block)
    starts = [i * block for i in range(n_full)]

    def one_pass(C):
        acc = (
            jnp.zeros((k, d), X.dtype),
            jnp.zeros((k,), X.dtype),
            jnp.zeros((), X.dtype),
        )
        for s in starts:
            acc = _lloyd_block_step(
                acc, C, X, w, jnp.asarray(s, jnp.int32), block, k
            )
        if tail:
            acc = _lloyd_block_step(
                acc, C, X, w, jnp.asarray(n_full * block, jnp.int32), tail, k
            )
        return acc

    from ..telemetry import Heartbeat

    hb = Heartbeat("kmeans_lloyd", total=max_iter)
    n_iter = start_it
    for n_iter in range(start_it + 1, max_iter + 1):
        maybe_inject("kmeans_lloyd")
        sums, counts, _ = one_pass(C)
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
    _, _, cost = one_pass(C)
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
    return jnp.argmin(_pairwise_sqdist(X, C), axis=1).astype(jnp.int32)


@jax.jit
def kmeans_cost(X: jax.Array, w: jax.Array, C: jax.Array) -> jax.Array:
    """Weighted sum of squared distances to the closest center (Spark's
    `summary.trainingCost` / cuML inertia)."""
    return (jnp.min(_pairwise_sqdist(X, C), axis=1) * w).sum()
