#
# UMAP kernels — the TPU-native replacement for `cuml.manifold.UMAP`
# fit/transform (called from reference umap.py:1016-1063, 1452-1529).
#
# The reference fits UMAP on ONE worker (optionally on a sample_fraction,
# umap.py:926-948) and distributes only the transform; the same strategy is
# kept here, so the fit kernels are single-device jit programs:
#
#   - Fuzzy simplicial set: rho/sigma per point via a vectorized bisection
#     (umap-learn's smooth_knn_dist), membership strengths, symmetrization
#     with set_op_mix_ratio.
#   - Embedding optimizer: umap-learn's SGD recast for XLA — every epoch
#     processes ALL edges at once.  Edge activity follows the
#     epochs_per_sample schedule (floor-crossing test, identical in
#     expectation to umap-learn's per-edge countdown), attractive and
#     repulsive (negative-sampled) gradients are one gather + segment
#     scatter-add each, and the whole n_epochs loop is a lax.fori_loop in
#     one compiled program.  Gradient clipping (+-4) matches umap-learn.
#
# find_ab_params is the standard least-squares fit of 1/(1+a d^{2b}) to the
# min_dist/spread membership curve (host-side scipy, once per fit) — the
# analog of cuml.manifold.umap.find_ab_params (reference umap.py:1452-1456).
#
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def find_ab_params(spread: float, min_dist: float) -> Tuple[float, float]:
    from scipy.optimize import curve_fit

    def curve(x, a, b):
        return 1.0 / (1.0 + a * x ** (2 * b))

    xv = np.linspace(0, spread * 3, 300)
    yv = np.zeros(xv.shape)
    yv[xv < min_dist] = 1.0
    mask = xv >= min_dist
    yv[mask] = np.exp(-(xv[mask] - min_dist) / spread)
    params, _ = curve_fit(curve, xv, yv)
    return float(params[0]), float(params[1])


@partial(jax.jit, static_argnames=("local_connectivity",))
def smooth_knn_dist(
    knn_dists: jax.Array,  # (n, k) sorted ascending, self excluded
    local_connectivity: int = 1,
    n_iter: int = 64,
):
    """Per-point (rho, sigma): rho = distance to the local_connectivity-th
    neighbor; sigma solves sum_j exp(-(d_j - rho)/sigma) = log2(k)."""
    n, k = knn_dists.shape
    rho = knn_dists[:, local_connectivity - 1]
    target = jnp.log2(k)

    def psum(sigma):
        d = jnp.maximum(knn_dists - rho[:, None], 0.0)
        return jnp.exp(-d / sigma[:, None]).sum(axis=1)

    def body(_, carry):
        lo, hi, mid = carry
        val = psum(mid)
        hi = jnp.where(val > target, mid, hi)
        lo = jnp.where(val > target, lo, mid)
        mid = (lo + hi) / 2.0
        return lo, hi, mid

    lo = jnp.full((n,), 1e-10, knn_dists.dtype)
    hi = jnp.full((n,), 1e4, knn_dists.dtype)
    mid = jnp.ones((n,), knn_dists.dtype)
    _, _, sigma = jax.lax.fori_loop(0, n_iter, body, (lo, hi, mid))
    # umap-learn floors sigma at a fraction of the mean neighbor distance
    mean_d = jnp.maximum(knn_dists.mean(), 1e-10)
    sigma = jnp.maximum(sigma, 1e-3 * mean_d)
    return rho, sigma


@partial(jax.jit, static_argnames=("set_op_mix_ratio",))
def fuzzy_simplicial_set(
    knn_inds: jax.Array,  # (n, k) neighbor row indices
    knn_dists: jax.Array,  # (n, k)
    rho: jax.Array,
    sigma: jax.Array,
    set_op_mix_ratio: float = 1.0,
):
    """Directed membership strengths + symmetrization.  Returns the dense
    edge list of the symmetric graph as (heads (n*k,), tails (n*k,),
    weights (n*k,)) — each directed edge (i -> knn[i,j]) carries the
    symmetrized weight w_ij = mix*(a+b-ab) + (1-mix)*ab where a = w(i->j),
    b = w(j->i)."""
    n, k = knn_inds.shape
    w = jnp.exp(-jnp.maximum(knn_dists - rho[:, None], 0.0) / sigma[:, None])
    # build dense (n, n) would blow memory; instead compute w(j->i) by
    # scatter into a (n, n)-free lookup: for each directed edge (i, j)
    # find the reverse weight by scanning j's neighbor list for i.
    heads = jnp.repeat(jnp.arange(n, dtype=knn_inds.dtype), k)
    tails = knn_inds.reshape(-1)
    w_fwd = w.reshape(-1)
    # reverse lookup: does j list i among its neighbors, with what weight
    j_neighbors = knn_inds[tails]  # (n*k, k)
    j_weights = w[tails]  # (n*k, k)
    match = j_neighbors == heads[:, None]
    w_rev = jnp.where(match, j_weights, 0.0).max(axis=1)
    sym = (
        set_op_mix_ratio * (w_fwd + w_rev - w_fwd * w_rev)
        + (1.0 - set_op_mix_ratio) * (w_fwd * w_rev)
    )
    return heads, tails, sym


@partial(
    jax.jit,
    static_argnames=("n_epochs", "e_count", "negative_sample_rate", "k"),
)
def _optimize_epoch_chunk_structured(
    emb0: jax.Array,  # (n, dim) current embedding
    key: jax.Array,  # PRNG key carried across chunks
    tails2d: jax.Array,  # (n, k) neighbor indices (head-major edge list)
    weights2d: jax.Array,  # (n, k)
    perm: jax.Array,  # (E,) edge permutation sorting tails ascending
    tails_sorted: jax.Array,  # (E,) tails[perm]
    e_start,  # traced scalar: absolute index of this chunk's first epoch
    e_count: int,
    n_epochs: int,
    a,
    b,
    initial_alpha,
    k: int,
    negative_sample_rate: int = 5,
    repulsion_strength: float = 1.0,
):
    """Scatter-free epoch kernel for the head-major edge list that
    `fuzzy_simplicial_set` produces (heads == repeat(arange(n), k)).

    The generic kernel's four unsorted scatter-adds per epoch are the
    TPU bottleneck (XLA serializes random-index scatters).  With the
    structure:
      - head-side updates are a reshape + sum over k — no gather/scatter;
      - negative samples repel only heads — again a plain sum;
      - the one true scatter (tail-side attract) uses indices that are
        STATIC across epochs, so a single upfront argsort turns it into
        a sorted segment_sum every epoch.
    Numerics match the generic kernel up to reduction order."""
    n, dim = emb0.shape
    E = n * k
    a = jnp.asarray(a, emb0.dtype)
    b = jnp.asarray(b, emb0.dtype)
    e_start = jnp.asarray(e_start, jnp.int32)
    wmax = jnp.maximum(weights2d.max(), 1e-12)
    freq = weights2d / wmax
    freq = jnp.where(weights2d >= wmax / n_epochs, freq, 0.0)  # (n, k)
    self_ids = jnp.arange(n, dtype=tails2d.dtype)

    def epoch(e, carry):
        emb, key = carry
        ef = (e_start + e).astype(emb.dtype)
        alpha = initial_alpha * (1.0 - ef / n_epochs)
        active = jnp.floor((ef + 1.0) * freq) > jnp.floor(ef * freq)
        act = active.astype(emb.dtype)  # (n, k)

        t = emb[tails2d]  # (n, k, dim)
        diff = emb[:, None, :] - t
        d2 = (diff * diff).sum(axis=2)
        grad_coeff = (-2.0 * a * b * d2 ** (b - 1.0)) / (1.0 + a * d2**b)
        grad_coeff = jnp.where(d2 > 0.0, grad_coeff, 0.0)
        g = jnp.clip(grad_coeff[:, :, None] * diff, -4.0, 4.0) * act[:, :, None]
        tail_add = jax.ops.segment_sum(
            g.reshape(E, dim)[perm], tails_sorted, num_segments=n,
            indices_are_sorted=True,
        )
        emb = emb + alpha * (g.sum(axis=1) - tail_add)

        # negative samples: for each active edge, nsr random points repel
        # the HEAD only — a dense sum over (k, nsr), no scatter
        key, sub = jax.random.split(key)
        neg = jax.random.randint(sub, (n, k, negative_sample_rate), 0, n)
        nt = emb[neg]  # (n, k, nsr, dim)
        diff_n = emb[:, None, None, :] - nt
        d2n = (diff_n * diff_n).sum(axis=3)
        rep = (2.0 * repulsion_strength * b) / (
            (0.001 + d2n) * (1.0 + a * d2n**b)
        )
        gn = jnp.clip(rep[:, :, :, None] * diff_n, -4.0, 4.0)
        gn = jnp.where(d2n[:, :, :, None] > 0.0, gn, 4.0)
        gn = jnp.where(
            (neg == self_ids[:, None, None])[:, :, :, None], 0.0, gn
        )
        gn = gn * act[:, :, None, None]
        emb = emb + alpha * gn.sum(axis=(1, 2))
        return emb, key

    return jax.lax.fori_loop(0, e_count, epoch, (emb0, key))


@partial(
    jax.jit,
    static_argnames=("n_epochs", "e_count", "negative_sample_rate"),
)
def _optimize_epoch_chunk(
    emb0: jax.Array,  # (n, dim) current embedding
    key: jax.Array,  # PRNG key carried across chunks
    heads: jax.Array,  # (E,) int
    tails: jax.Array,  # (E,) int
    weights: jax.Array,  # (E,)
    e_start,  # traced scalar: absolute index of this chunk's first epoch
    e_count: int,
    n_epochs: int,
    a,
    b,
    initial_alpha,
    negative_sample_rate: int = 5,
    repulsion_strength: float = 1.0,
):
    """`e_count` SGD epochs starting at absolute epoch `e_start`; all edges
    are processed per epoch with the epochs_per_sample activity schedule.
    `e_start` is traced so every full chunk shares one compilation."""
    n, dim = emb0.shape
    E = heads.shape[0]
    a = jnp.asarray(a, emb0.dtype)
    b = jnp.asarray(b, emb0.dtype)
    e_start = jnp.asarray(e_start, jnp.int32)
    # umap-learn: edges with weight < max/n_epochs are never sampled
    wmax = jnp.maximum(weights.max(), 1e-12)
    freq = weights / wmax  # samples-per-epoch fraction in (0, 1]
    freq = jnp.where(weights >= wmax / n_epochs, freq, 0.0)

    def epoch(e, carry):
        emb, key = carry
        ef = (e_start + e).astype(emb.dtype)
        alpha = initial_alpha * (1.0 - ef / n_epochs)
        # floor-crossing schedule == umap-learn's epochs_per_sample countdown
        active = jnp.floor((ef + 1.0) * freq) > jnp.floor(ef * freq)
        act = active.astype(emb.dtype)

        h = emb[heads]  # (E, dim)
        t = emb[tails]
        diff = h - t
        d2 = (diff * diff).sum(axis=1)
        # attractive gradient coefficient: -2ab d^{2(b-1)} / (1 + a d^{2b})
        grad_coeff = (-2.0 * a * b * d2 ** (b - 1.0)) / (1.0 + a * d2**b)
        grad_coeff = jnp.where(d2 > 0.0, grad_coeff, 0.0)
        g = jnp.clip(grad_coeff[:, None] * diff, -4.0, 4.0) * act[:, None]
        emb = emb.at[heads].add(alpha * g)
        emb = emb.at[tails].add(-alpha * g)

        # negative samples: for each active edge, nsr random points repel
        key, sub = jax.random.split(key)
        neg = jax.random.randint(sub, (E, negative_sample_rate), 0, n)
        h2 = emb[heads]  # re-gather after attract update
        nt = emb[neg]  # (E, nsr, dim)
        diff_n = h2[:, None, :] - nt
        d2n = (diff_n * diff_n).sum(axis=2)
        rep = (2.0 * repulsion_strength * b) / (
            (0.001 + d2n) * (1.0 + a * d2n**b)
        )
        gn = jnp.clip(rep[:, :, None] * diff_n, -4.0, 4.0)
        # coincident-but-distinct points get the max push; a self-collision
        # (neg == head) is skipped like umap-learn's `j == k: continue`
        gn = jnp.where(d2n[:, :, None] > 0.0, gn, 4.0)
        gn = jnp.where((neg == heads[:, None])[:, :, None], 0.0, gn)
        gn = gn * act[:, None, None]
        emb = emb.at[heads].add(alpha * gn.sum(axis=1))
        return emb, key

    return jax.lax.fori_loop(0, e_count, epoch, (emb0, key))


# observability for the umap_kernel=auto measured probe: the last
# optimize_embedding call's kernel choice and its per-epoch timings
# (read by the tests; None timings = no probe ran)
LAST_KERNEL_DECISION: dict = {
    "kernel": None,
    "decided_by": None,
    "warm_epoch_sec_generic": None,
    "warm_epoch_sec_structured": None,
}


def optimize_embedding(
    emb0: jax.Array,  # (n, dim) initial embedding
    heads: jax.Array,
    tails: jax.Array,
    weights: jax.Array,
    seed,
    n_epochs: int,
    a,
    b,
    initial_alpha,
    negative_sample_rate: int = 5,
    repulsion_strength: float = 1.0,
    deterministic: bool = False,
):
    """umap-learn SGD over `n_epochs`, dispatched from the host in epoch
    chunks sized adaptively to `_TARGET_DISPATCH_S` of device time each
    (a bound sized for a development link that no longer exists; kept
    until re-justified on the chip or deleted, ROADMAP Design 2).  The
    PRNG key is carried across chunks, so the epoch/RNG sequence — and
    the result — is identical for any chunking."""
    import time as _time

    import numpy as np

    if n_epochs <= 0:
        # op-level contract: no epochs means the initial embedding verbatim
        # (the old fori_loop ran zero iterations; the probe dispatch below
        # would run one epoch and divide by zero in the alpha schedule)
        return jnp.asarray(emb0)

    emb = jnp.asarray(emb0)
    key = jax.random.PRNGKey(seed)

    # head-major structure check (the shape fuzzy_simplicial_set emits):
    # heads == repeat(arange(n), k) enables the scatter-free kernel
    from ..config import get_config

    mode = str(get_config("umap_kernel"))
    n = emb.shape[0]
    E = int(heads.shape[0])
    k = E // n if n else 0
    # head-major structure is a precondition for the structured kernel
    # regardless of mode
    structured_ok = (
        n > 0
        and E == n * k
        and k > 0
        and bool(
            jnp.array_equal(
                heads, jnp.repeat(jnp.arange(n, dtype=heads.dtype), k)
            )
        )
    )
    if mode == "structured":
        structured = structured_ok
        decided_by = "forced" if structured_ok else "structure-missing"
    elif mode == "generic" or not structured_ok:
        structured = False
        decided_by = "forced" if mode == "generic" else "structure-missing"
    elif deterministic:
        # random_state set: reproducibility outranks the measured probe —
        # two same-seed fits must not diverge because host timing noise
        # flipped the kernel choice (cuML documents the same trade:
        # "setting a random_state will [reduce] performance", umap.py
        # random_state docstring).  The platform prior decides, the same
        # way for every fit.
        structured = jax.default_backend() == "tpu"
        decided_by = "random-state-platform-prior"
    elif n_epochs < 10:
        # too few epochs to amortize a second kernel compile: fall back to
        # the platform prior (scatters serialize on TPU, are cheap on CPU)
        structured = jax.default_backend() == "tpu"
        decided_by = "platform-prior"
    else:
        structured = None  # measured probe below decides
        decided_by = "measured"
    if structured_ok and structured is not False:
        tails2d = jnp.asarray(tails).reshape(n, k)
        weights2d = jnp.asarray(weights).reshape(n, k)
        perm = jnp.argsort(tails)  # once per fit: tails are epoch-static
        tails_sorted = jnp.asarray(tails)[perm]

    def run(e_start: int, e_count: int, use_structured: bool):
        nonlocal emb, key
        t0 = _time.perf_counter()
        if use_structured:
            emb, key = _optimize_epoch_chunk_structured(
                emb, key, tails2d, weights2d, perm, tails_sorted,
                e_start, e_count, n_epochs, a, b, initial_alpha, k,
                negative_sample_rate, repulsion_strength,
            )
        else:
            emb, key = _optimize_epoch_chunk(
                emb, key, heads, tails, weights, e_start, e_count,
                n_epochs, a, b, initial_alpha, negative_sample_rate,
                repulsion_strength,
            )
        np.asarray(emb[0, 0])  # true sync (fetch, not block_until_ready)
        return _time.perf_counter() - t0

    # probe with the minimal unit (1 epoch): even a single epoch can be
    # tens of seconds at multi-million-row scale, so no blind multi-epoch
    # dispatch may happen before a timing exists
    done = 0
    if structured is None:
        # measured kernel selection (auto must pick by measurement, not
        # platform).  The kernels agree numerically up to
        # reduction order, so the probe epochs ARE real fit epochs: run
        # cold + 2 warm with each kernel (min-of-2 resists a transient
        # load spike committing the whole fit to the slower kernel), keep
        # all six epochs' work, and commit the tail to the faster kernel.
        # Overhead = one extra 1-epoch compile.
        run(0, 1, False)  # generic cold (compile)
        t_generic = min(run(1, 1, False), run(2, 1, False))
        run(3, 1, True)  # structured cold (compile)
        t_structured = min(run(4, 1, True), run(5, 1, True))
        done = 6
        if abs(t_structured - t_generic) < 0.1 * min(
            t_structured, t_generic
        ):
            # inside noise: defer to the platform prior rather than let a
            # coin flip make same-seed fits nondeterministic run-to-run
            structured = jax.default_backend() == "tpu"
            decided_by = "measured-tie-platform-prior"
        else:
            structured = t_structured < t_generic
            decided_by = "measured"
        elapsed = min(t_structured, t_generic)
        LAST_KERNEL_DECISION.update(
            kernel="structured" if structured else "generic",
            decided_by=decided_by,
            warm_epoch_sec_generic=t_generic,
            warm_epoch_sec_structured=t_structured,
        )
    else:
        LAST_KERNEL_DECISION.update(
            kernel="structured" if structured else "generic",
            decided_by=decided_by,
            warm_epoch_sec_generic=None,
            warm_epoch_sec_structured=None,
        )
        elapsed = run(0, 1, structured)  # cold: includes the compile
        done = 1
        if done < n_epochs:
            elapsed = run(done, 1, structured)  # warm: honest device time
            done += 1
    if done < n_epochs:
        per_epoch = max(elapsed, 1e-4)
        # ~20 s of device work per dispatch, floor 1
        chunk = int(min(max(20.0 / per_epoch, 1), n_epochs - done))
        while n_epochs - done >= chunk:
            run(done, chunk, structured)
            done += chunk
        if n_epochs - done:
            run(done, n_epochs - done, structured)
    return emb


@jax.jit
def categorical_intersection(
    knn_inds: jax.Array,  # (n, k) neighbor row indices (edge-list order)
    heads: jax.Array,  # (n*k,)
    tails: jax.Array,  # (n*k,)
    weights: jax.Array,  # (n*k,) symmetrized membership weights
    labels: jax.Array,  # (n,) int codes; -1 = unknown
    unknown_dist=1.0,
    far_dist=5.0,
):
    """Supervised (categorical) simplicial set intersection — the analog of
    cuML's supervised UMAP fit consuming labelCol (reference
    umap.py:812-813, 901; umap-learn's
    `categorical_simplicial_set_intersection` + `reset_local_connectivity`):

      - edges between differently-labeled points are scaled by
        exp(-far_dist), edges touching unknown (-1) labels by
        exp(-unknown_dist);
      - local connectivity is then reset: per-head max-normalization
        followed by the fuzzy union with the reverse edge (reverse weights
        looked up by scanning the tail's neighbor list, as in
        `fuzzy_simplicial_set`; a reverse edge absent from the kNN lists
        contributes 0 — the same approximation the forward pass makes).
    """
    n, k = knn_inds.shape
    li = jnp.take(labels, heads)
    lj = jnp.take(labels, tails)
    unknown = (li < 0) | (lj < 0)
    differ = li != lj
    scale = jnp.where(
        unknown,
        jnp.exp(-unknown_dist),
        jnp.where(differ, jnp.exp(-far_dist), 1.0),
    )
    w = weights * scale
    wmat = w.reshape(n, k)
    wmax = jnp.maximum(wmat.max(axis=1), 1e-12)
    wn = wmat / wmax[:, None]
    j_neighbors = knn_inds[tails]  # (n*k, k)
    j_weights = wn[tails]  # (n*k, k)
    match = j_neighbors == heads[:, None]
    w_rev = jnp.where(match, j_weights, 0.0).max(axis=1)
    w_fwd = wn.reshape(-1)
    return w_fwd + w_rev - w_fwd * w_rev


@jax.jit
def transform_init(
    knn_inds: jax.Array,  # (q, k) neighbor indices into training rows
    knn_dists: jax.Array,  # (q, k)
    rho: jax.Array,  # (n,) training rho
    sigma: jax.Array,  # (n,) training sigma
    train_emb: jax.Array,  # (n, dim)
):
    """New-point embedding init: membership-weighted average of the
    training neighbors' embeddings (umap-learn transform init)."""
    q, k = knn_inds.shape
    # memberships computed with each NEIGHBOR's smooth-knn parameters
    rho_n = rho[knn_inds]
    sigma_n = sigma[knn_inds]
    w = jnp.exp(-jnp.maximum(knn_dists - rho_n, 0.0) / sigma_n)
    w = w / jnp.maximum(w.sum(axis=1, keepdims=True), 1e-12)
    return jnp.einsum("qk,qkd->qd", w, train_emb[knn_inds])
