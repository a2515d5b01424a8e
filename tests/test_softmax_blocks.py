#
# The multinomial evaluation in row blocks (ops/logistic.py
# `softmax_data_term`): value and gradient against a plain reference, whole
# fits against the reference's L-BFGS on both routes, Spark's intercept
# centring, the route and the record a fit leaves, and the precision of
# every product a multinomial fit takes.
#
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import scipy.sparse as sp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from spark_rapids_ml_tpu.classification import LogisticRegression
from spark_rapids_ml_tpu.config import reset_config, set_config
from spark_rapids_ml_tpu.ops import logistic as L

HIGHEST = jax.lax.Precision.HIGHEST


# -- the plain reference: every row at once, float32 at `highest`, no blocks ----

def ref_objective(theta, X, y, w, C, l2, fit_intercept=True):
    """Spark's multinomial objective: the weighted mean of
    logsumexp(x W^T + b) - (x W^T + b)_y, plus l2/2 |W|^2."""
    d = X.shape[1]
    W = theta[:C * d].reshape(C, d)
    b = theta[C * d:] if fit_intercept else jnp.zeros((C,), theta.dtype)
    logits = jnp.matmul(X, W.T, precision=HIGHEST) + b
    nll = jax.nn.logsumexp(logits, axis=1) - jnp.take_along_axis(
        logits, y[:, None], axis=1)[:, 0]
    return (w * nll).sum() / w.sum() + 0.5 * l2 * (W * W).sum()


def ref_lbfgs(X, y, w, C, l2, max_iter, history=10, ls_max=20):
    """L-BFGS in numpy float64 over the reference's float32 value and
    gradient, as the program runs it: two-loop recursion over the last
    `history` pairs scaled by s.y / y.y, Armijo backtracking by halving from
    step 1 (1/max(|p|, 1) while no pair is kept), a pair kept when
    s.y > 1e-10.  Returns (theta, objective history, evaluations)."""
    vg = jax.jit(jax.value_and_grad(partial(ref_objective, C=C, l2=l2)))
    n_evals = 0

    def full(theta):
        nonlocal n_evals
        n_evals += 1
        f, g = vg(jnp.asarray(theta, jnp.float32), X, y, w)
        return float(f), np.asarray(g, np.float64)

    theta = np.zeros(C * (X.shape[1] + 1))
    f, g = full(theta)
    hist, pairs = [f], []
    for _ in range(max_iter):
        q, alphas = g.copy(), []
        for s, yv, rho in reversed(pairs):
            a = rho * (s @ q)
            q -= a * yv
            alphas.append(a)
        if pairs:
            q *= (pairs[-1][0] @ pairs[-1][1]) / (pairs[-1][1] @ pairs[-1][1])
        for (s, yv, rho), a in zip(pairs, reversed(alphas)):
            q += (a - rho * (yv @ q)) * s
        p = -q
        t = 1.0 if pairs else 1.0 / max(np.linalg.norm(p), 1.0)
        for _ in range(ls_max + 1):
            f_t, g_t = full(theta + t * p)
            if f_t <= f + 1e-4 * t * (g @ p):
                break
            t *= 0.5
        s, yv = t * p, g_t - g
        if s @ yv > 1e-10:
            pairs = (pairs + [(s, yv, 1.0 / (s @ yv))])[-history:]
        theta, f, g = theta + s, f_t, g_t
        hist.append(f)
    return theta, hist, n_evals


def _rows(n, d=12, C=4, holes=False, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    W = rng.normal(size=(C, d))
    y = np.argmax(X @ W.T + rng.gumbel(size=(n, C)), axis=1).astype(np.int32)
    w = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    if holes:  # CV fold-mask holes and padding: rows of weight zero
        w[::5] = 0.0
        w[-17:] = 0.0
    return X, y, w


def _on_mesh(n_dev, X, y, w):
    if n_dev == 1:
        return None, (jnp.asarray(X), jnp.asarray(y), jnp.asarray(w))
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("data",))
    rows = NamedSharding(mesh, P("data"))
    return mesh, (jax.device_put(X, NamedSharding(mesh, P("data", None))),
                  jax.device_put(y, rows), jax.device_put(w, rows))


# -- one evaluation -----------------------------------------------------------

@pytest.mark.parametrize("fit_intercept", [True, False], ids=["intercept", "no_intercept"])
@pytest.mark.parametrize(
    "C,n,holes,n_dev",
    [(3, 512, False, 1), (10, 300, False, 1), (10, 300, True, 1), (3, 1160, True, 4)],
    ids=["C3_whole_blocks", "C10_ragged", "C10_ragged_holes", "C3_four_devices"],
)
def test_blocked_evaluation_matches_the_plain_reference(C, n, holes, n_dev, fit_intercept):
    """Value and gradient of the blocked evaluation (blocks of 128 rows: the
    last one overlaps the one before it unless they divide the rows) against
    autodiff of the plain reference, with sample weights and rows of weight
    zero, on one device and over four.  Its products drop lo.lo, 2^-16 of
    each term at most: 3e-5 of the value and the gradient."""
    tol = 3e-5
    X, y, w = _rows(n, C=C, holes=holes, seed=n + C)
    mesh, (Xs, ys, ws) = _on_mesh(n_dev, X, y, w)
    d, l2 = X.shape[1], 1e-3
    theta = 0.3 * np.random.default_rng(1).normal(size=C * (d + fit_intercept))
    theta = jnp.asarray(theta, jnp.float32)
    plan = L.SoftmaxBlocks(128, mesh)
    loss_fn, _, _, _ = L._multinomial_problem(
        None, C, d, jnp.float32, ws, ys, l2, fit_intercept,
        L.softmax_data_term(plan, Xs, ws, ys))
    f, g = jax.jit(jax.value_and_grad(loss_fn))(theta)
    if not fit_intercept:
        ref = lambda t: ref_objective(t, X, y, w, C, l2, fit_intercept=False)  # noqa: E731
    else:
        ref = partial(ref_objective, X=X, y=y, w=w, C=C, l2=l2)
    f0, g0 = jax.value_and_grad(ref)(theta)
    assert abs(float(f) - float(f0)) <= tol * abs(float(f0))
    assert np.linalg.norm(np.asarray(g) - np.asarray(g0)) <= tol * np.linalg.norm(np.asarray(g0))


@pytest.mark.parametrize("parts", [1, 2, 3])
def test_split_products_take_the_passes_their_parts_make(parts):
    """`_split_product` of n bfloat16 parts an operand takes the part
    products a_i.b_j with i + j < n: 1, 3 or 6 bfloat16 dot_generals, as far
    from float64 as those passes leave (one part: the operands' bfloat16
    roundings, 2^-9 of a term; two: 2^-16; three: float32's own)."""
    rng = np.random.default_rng(parts)
    a = rng.normal(size=(64, 96)).astype(np.float32)
    b = rng.normal(size=(48, 96)).astype(np.float32)
    dims = (((1,), (1,)), ((), ()))
    fn = jax.jit(lambda a, b: L._split_product(
        L._bf16_parts(a, parts), L._bf16_parts(b, parts), dims))
    dots = _matrix_dots(fn.lower(a, b))
    assert len(dots) == parts * (parts + 1) // 2
    assert all("xbf16>" in _operands(line) for line in dots)
    exact = a.astype(np.float64) @ b.astype(np.float64).T
    gap = np.linalg.norm(np.asarray(fn(a, b), np.float64) - exact) / np.linalg.norm(exact)
    low, high = {1: (3e-4, 3e-2), 2: (0.0, 3e-5), 3: (0.0, 1e-6)}[parts]
    assert low <= gap <= high


def test_the_evaluation_takes_the_passes_its_instant_names():
    """Each of the blocked evaluation's two products lowers to `_PASSES`
    bfloat16 dot_generals, the count `lbfgs_eval_kernel[softmax_blocks]`'s
    detail gives."""
    C, d = 10, 12
    X, y, w = _rows(300, d=d, C=C, seed=5)
    Xs, ys, ws = jnp.asarray(X), jnp.asarray(y), jnp.asarray(w)
    loss_fn, _, _, _ = L._multinomial_problem(
        None, C, d, jnp.float32, ws, ys, 0.0, True,
        L.softmax_data_term(L.SoftmaxBlocks(128), Xs, ws, ys))
    theta = jnp.zeros((C * (d + 1),), jnp.float32)
    dots = _matrix_dots(jax.jit(jax.value_and_grad(loss_fn)).lower(theta))
    assert L._PASSES == 3 and len(dots) == 2 * L._PASSES


def test_block_rows_tile_the_shard_and_follow_the_memory_left(monkeypatch):
    """Near-equal blocks in whole tiles of 8 rows, as many rows as half the
    memory left beside the shard pays for, at most 16,384; the ImageNet
    probe's shape on a v5e (15.75 GiB) gets 79 blocks of 16,224."""
    from types import SimpleNamespace as NS

    from spark_rapids_ml_tpu.parallel import device_cache

    def stand_in(rows, d, left):
        monkeypatch.setattr(device_cache, "device_hbm_bytes", lambda dev: rows * d * 4 + left)
        return NS(shape=(rows, d), addressable_shards=[NS(
            device=None, data=NS(nbytes=rows * d * 4, shape=(rows, d)))])

    X = stand_in(1_281_167, 2048, 16_909_336_064 - 1_281_167 * 2048 * 4)
    assert L.softmax_block_rows(X, 1000) == 16_224
    assert 78 * 16_224 < 1_281_167 <= 79 * 16_224
    per_row = L.softmax_row_bytes(2048, 1000)
    X = stand_in(100_000, 2048, 2 * 5_000 * per_row)  # room for 5,000 rows a block
    assert L.softmax_block_rows(X, 1000) == 5_000
    assert L.softmax_block_rows(stand_in(100, 2048, 1 << 40), 1000) == 100


# -- whole fits -----------------------------------------------------------------

@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("route", ["fused", "host_dispatch"])
def test_fit_matches_the_reference_lbfgs(route, n_dev):
    """A whole fit through the blocked evaluation against the plain
    reference's L-BFGS: coefficients, objective history, iterations and
    evaluations (the fused program counts its own)."""
    X, y, w = _rows(1160, d=8, C=4, seed=7)
    w = np.ones_like(w)
    mesh, (Xs, ys, ws) = _on_mesh(n_dev, X, y, w)
    plan = L.SoftmaxBlocks(128, mesh)
    kw = dict(n_classes=4, l2=1e-2, l1=0.0, fit_intercept=True, tol=1e-30, max_iter=8)
    if route == "fused":
        W, b, loss, n_iter, hist, n_evals = L.logreg_fit(Xs, ws, ys, softmax=plan, **kw)
    else:
        W, b, loss, n_iter, hist, n_evals = L.logreg_fit_host_dispatch(
            Xs, ws, ys, softmax=plan, **kw)
    theta, ref_hist, ref_evals = ref_lbfgs(X, y, w, 4, 1e-2, 8)
    assert int(n_iter) == 8 and int(n_evals) == ref_evals
    got = np.append(np.asarray(W, np.float64).ravel(), np.asarray(b, np.float64))
    assert np.linalg.norm(got - theta) <= 1e-4 * np.linalg.norm(theta)
    np.testing.assert_allclose(np.asarray(hist, np.float64)[:9], ref_hist, rtol=1e-6)


def _dense(rng, n=900, d=10, C=3):
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = np.argmax(X @ rng.normal(size=(C, d)).T + rng.gumbel(size=(n, C)), axis=1)
    return X, y.astype(np.float64)


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The backend check stubbed: the input alone decides the plan."""
    monkeypatch.setattr(L, "_on_tpu", lambda X: True)


@pytest.mark.parametrize("on_tpu", [False, True], ids=["autodiff", "softmax_blocks"])
def test_spark_centres_the_multinomial_intercepts(rng, monkeypatch, on_tpu):
    """Spark's intercepts sum to zero (the softmax does not see a shift of
    them all), and the coefficients and centred intercepts are the plain
    reference's, whichever evaluation ran."""
    if on_tpu:
        monkeypatch.setattr(L, "_on_tpu", lambda X: True)
    X, y = _dense(rng)
    model = LogisticRegression(regParam=0.01, maxIter=8, tol=1e-30, standardization=False).fit((X, y))
    assert model.interceptVector.shape == (3,)
    assert abs(float(np.sum(model.interceptVector))) < 1e-5
    theta, _, _ = ref_lbfgs(X, y.astype(np.int32), np.ones(len(y), np.float32), 3, 0.01, 8)
    b = theta[-3:] - theta[-3:].mean()
    np.testing.assert_allclose(model.coefficientMatrix, theta[:-3].reshape(3, -1), atol=2e-4)
    np.testing.assert_allclose(model.interceptVector, b, atol=2e-4)


def _events(model, prefix):
    def find(nodes):
        for n in nodes:
            if n["name"].startswith(prefix):
                yield n["name"], n.get("detail", "")
            yield from find(n.get("children", []))

    return list(find(model.fit_report()["spans"]))


@pytest.fixture
def conf():
    """`set_config` for one test."""
    yield set_config
    reset_config()


@pytest.mark.parametrize("budget", [None, 1.0], ids=["default", "1"])
def test_a_multinomial_fit_on_tpu_takes_the_blocks_and_the_fused_route(
        rng, as_on_tpu, conf, budget):
    """Dense float32 multinomial rows on a TPU: the blocked evaluation, the
    fused program whatever the FLOP budget says (it holds one block's
    temporaries and the solver's state beside the rows), and one
    `fact[lbfgs]` with the evaluations the program counted."""
    if budget is not None:
        conf(dispatch_flops_limit=budget)
    model = LogisticRegression(regParam=0.01, maxIter=6, tol=1e-30).fit(_dense(rng))
    ((kernel, detail),) = _events(model, "lbfgs_eval_kernel[")
    assert kernel == "lbfgs_eval_kernel[softmax_blocks]"
    assert "C=3" in detail and "block_rows=" in detail and "passes=3" in detail
    ((route, why),) = _events(model, "lbfgs_route[")
    assert route == "lbfgs_route[fused]" and "in place" in why and "budget" not in why
    fact = model.fit_report()["lbfgs"]
    assert fact["classes"] == 3 and fact["iterations"] == 6
    assert fact["evaluation"] == "softmax_blocks" and fact["evaluations"] >= 7


def test_a_checkpointed_multinomial_fit_counts_its_evaluations_on_the_host(
        rng, as_on_tpu, conf, tmp_path):
    conf(checkpoint_dir=str(tmp_path))
    model = LogisticRegression(regParam=0.01, maxIter=6, tol=1e-30).fit(_dense(rng))
    ((route, _),) = _events(model, "lbfgs_route[")
    assert route == "lbfgs_route[host_dispatch]"
    evals = [n for n, _ in _events(model, "lbfgs_eval") if n == "lbfgs_eval"]
    assert model.fit_report()["lbfgs"]["evaluations"] == len(evals) >= 7


@pytest.mark.parametrize("kind", ["cpu", "ell", "binomial"])
def test_other_fits_record_their_evaluation(rng, kind):
    """Off a TPU a multinomial fit keeps autodiff (the reason in the
    instant); the sparse rows too; a fused binomial program does not count
    its evaluations."""
    X, y = _dense(rng)
    if kind == "binomial":
        y = (y > 0).astype(np.float64)
    rows = sp.csr_matrix(np.where(np.abs(X) < 1.0, 0.0, X)) if kind == "ell" else X
    model = LogisticRegression(regParam=0.01, maxIter=4).fit((rows, y))
    ((kernel, detail),) = _events(model, "lbfgs_eval_kernel[")
    assert kernel == "lbfgs_eval_kernel[autodiff]"
    fact = model.fit_report()["lbfgs"]
    assert fact["evaluation"] == "autodiff" and fact["classes"] == (2 if kind == "binomial" else 3)
    if kind == "cpu":
        assert "multinomial" in detail and "not a TPU" in detail
    if kind == "binomial":
        assert fact["evaluations"] is None
    else:
        assert fact["evaluations"] >= 5


# -- precision: no multinomial product is one default bfloat16 pass ------------

def _operands(line: str) -> str:
    return line.split(":")[-1].split("->")[0]


def _matrix_dots(lowered) -> list:
    """The lowered program's products of a matrix: with the rows or the
    weights.  The solver's own vector algebra (dots of two vectors, shared
    with the binomial program) is left out."""
    return [line for line in lowered.as_text().splitlines() if "dot_general" in line
            and max(t.count("x") for t in _operands(line).split("tensor<")[1:]) >= 2]


def _default_precision_f32_dots(lowered) -> list:
    """Those at default precision with f32 (or f64) operands: one bfloat16
    pass each on a TPU.  Products of bfloat16 operands are one exact pass
    by construction."""
    return [line for line in _matrix_dots(lowered)
            if "HIGHEST" not in line and "xbf16>" not in _operands(line)]


def _multinomial_programs():
    X, y, w = _rows(300, d=8, C=3)
    Xd, yd, wd = jnp.asarray(X), jnp.asarray(y), jnp.asarray(w)
    kw = dict(n_classes=3, l2=1e-3, l1=0.0, max_iter=2)
    theta = jnp.zeros((3 * 9,), jnp.float32)
    loss_fn, _, _, _ = L._multinomial_problem(
        partial(L._dense_logits, Xd), 3, 8, jnp.float32, wd, yd, 1e-3, True)
    K = 4
    vals = jnp.asarray(X[:, :K])
    cols = jnp.tile(jnp.arange(K, dtype=jnp.int32), (300, 1))
    return {
        "fused_autodiff": L.logreg_fit.lower(Xd, wd, yd, **kw),
        "fused_blocks": L.logreg_fit.lower(
            Xd, wd, yd, softmax=L.SoftmaxBlocks(128), **kw),
        "host_dispatch_autodiff": jax.jit(jax.value_and_grad(loss_fn)).lower(theta),
        "ell": L.logreg_fit_ell.lower(vals, cols, wd, yd, d=8, **kw),
    }


@pytest.mark.parametrize(
    "program", ["fused_autodiff", "fused_blocks", "host_dispatch_autodiff", "ell"])
def test_multinomial_products_are_never_default_precision(program):
    lowered = _multinomial_programs()[program]
    assert _matrix_dots(lowered), "the program has products"
    assert _default_precision_f32_dots(lowered) == []
    if program == "fused_blocks":  # bfloat16 parts, nothing else
        assert all("xbf16>" in _operands(line) for line in _matrix_dots(lowered))
