#
# The multinomial evaluation in row blocks (ops/logistic.py
# `softmax_data_term`): value and gradient against a plain reference, whole
# fits against the reference's L-BFGS on both routes, Spark's intercept
# centring, the route and the record a fit leaves, and the precision of
# every product a multinomial fit takes; and the one-pass kernel that takes
# those evaluations where the rows allow it (ops/pallas_softmax.py), in
# Pallas' interpreter, against the blocks and the same reference.
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
from spark_rapids_ml_tpu.ops import pallas_softmax as ps
from spark_rapids_ml_tpu.ops.linear import _bf16_parts

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
        L.softmax_data_term(plan, Xs, ws, ys, C))
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
        L.softmax_data_term(L.SoftmaxBlocks(128), Xs, ws, ys, C))
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


# -- the labels' part of the gradient: a constant of the fit, exact -----------

@pytest.mark.parametrize("n_dev", [1, 2])
@pytest.mark.parametrize("n", [300, 20_012], ids=["one_block", "blocks_ragged"])
def test_the_labels_part_is_exact(n, n_dev):
    """`_label_sums`: Σ w [y = c] x and Σ w [y = c] against float64, with
    sample weights, rows of weight zero and (past 8,192 rows a shard) an
    overlapping last block, on one device and over two: the one-hot labels
    are exact in one bfloat16
    part and w x in three, so only float32's own sums are left."""
    C, d = 7, 16
    X, y, w = _rows(n, d=d, C=C, holes=True, seed=n)
    mesh, (Xs, ys, ws) = _on_mesh(n_dev, X, y, w)
    M, m = L._label_sums(L.SoftmaxBlocks(128, mesh), Xs, ws, ys, C)
    Y = np.eye(C)[y] * w.astype(np.float64)[:, None]
    want_M, want_m = Y.T @ X.astype(np.float64), Y.sum(axis=0)
    assert np.linalg.norm(np.asarray(M, np.float64) - want_M) <= 1e-6 * np.linalg.norm(want_M)
    np.testing.assert_allclose(np.asarray(m, np.float64), want_m, rtol=1e-6)


def _exact_gradient(X, y, w, Wm, b):
    """The data term's gradient in W in float64."""
    logits = X.astype(np.float64) @ np.asarray(Wm, np.float64).T + np.asarray(b, np.float64)
    P = np.exp(logits - logits.max(axis=1, keepdims=True))
    P /= P.sum(axis=1, keepdims=True)
    R = w.astype(np.float64)[:, None] * (P - np.eye(Wm.shape[0])[y])
    return R.T @ X.astype(np.float64)


@pytest.mark.parametrize("evaluation", ["blocks", "kernel"])
@pytest.mark.parametrize("C,scale,closer", [(1000, 0.0, 10.0), (200, 0.01, 5.0)],
                         ids=["C1000_at_zero", "C200_near_zero"])
def test_the_residual_is_cut_without_the_labels(C, scale, closer, evaluation):
    """Where each row's own class has a small probability, as in a
    1,000-class fit's first iterations, the gradient is many times closer
    to float64 than the same three part products of w (P - Y) cut with the
    labels in it: those keep 16 bits of P_y - 1 ~ -1 and lose P_y."""
    d, n = 128, 1031
    rng = np.random.default_rng(C)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, C, n).astype(np.int32)
    w = rng.uniform(0.5, 2.0, n).astype(np.float32)
    Wm = jnp.asarray(scale * rng.normal(size=(C, d)), jnp.float32)
    b = jnp.zeros((C,), jnp.float32)
    plan = L.SoftmaxBlocks(128) if evaluation == "blocks" else _tiles(128)
    _, gW, _ = L._softmax_evaluate(plan, jnp.asarray(X), jnp.asarray(w), jnp.asarray(y), Wm, b)
    exact = _exact_gradient(X, y, w, Wm, b)
    logits = jnp.matmul(jnp.asarray(X), Wm.T, precision=HIGHEST) + b
    r = jnp.asarray(w)[:, None] * (jax.nn.softmax(logits, axis=1) - jax.nn.one_hot(y, C))
    with_labels = L._split_product(_bf16_parts(r, 2), _bf16_parts(jnp.asarray(X), 2),
                                   (((0,), (0,)), ((), ())))

    def gap(g):
        return np.linalg.norm(np.asarray(g, np.float64) - exact) / np.linalg.norm(exact)

    assert closer * gap(gW) <= gap(with_labels)


def test_a_fit_takes_the_labels_part_once():
    """The fused program calls `_label_sums` once, before its loop, and
    they take three bfloat16 passes: the labels' part is not taken again
    an evaluation."""
    X, y, w = _rows(300, d=8, C=3)
    text = L.logreg_fit.lower(jnp.asarray(X), jnp.asarray(w), jnp.asarray(y), n_classes=3,
                              l2=1e-3, l1=0.0, max_iter=2,
                              softmax=L.SoftmaxBlocks(128)).as_text()
    main = text.split("func.func private ")[0]
    assert text.count("call @_label_sums(") == 1
    assert main.index("call @_label_sums(") < main.index("stablehlo.while")
    # its products: the one-hot labels of a block of all 300 rows with w x's
    # three parts (the evaluation's blocks hold 128 rows)
    dots = [line for line in text.splitlines() if "dot_general" in line
            and "tensor<300x3xbf16>" in _operands(line)]
    assert len(dots) == 3 and all(_operands(line).count("xbf16>") == 2 for line in dots)


# -- the one-pass kernel (ops/pallas_softmax.py), in Pallas' interpreter -------

def _tiles(tile, mesh=None):
    """The kernel's plan, through the interpreter: only a test asks for it."""
    return ps.SoftmaxTiles(tile, mesh, interpret=True)


def _ref_data_term(X, y, w, Wm, b):
    """Σ w nll over every row at once, float32 at `highest`."""
    logits = jnp.matmul(X, Wm.T, precision=HIGHEST) + b
    nll = jax.nn.logsumexp(logits, axis=1) - jnp.take_along_axis(
        logits, y[:, None], axis=1)[:, 0]
    return (w * nll).sum()


@pytest.mark.parametrize(
    "C,n,holes,n_dev",
    [(10, 1031, False, 1), (10, 1031, True, 1), (1000, 521, True, 1),
     (130, 1030, True, 2), (3, 1024, False, 2)],
    ids=["C10_prime_rows", "C10_holes", "C1000_holes", "C130_two_devices",
         "C3_two_devices_whole_tiles"],
)
def test_kernel_matches_the_blocks_and_the_plain_reference(C, n, holes, n_dev):
    """Value and both gradients of the kernel (tiles of 128 rows; a prime
    row count leaves the last tile ragged) against the blocked evaluation,
    the same three part products summed in another order (1e-6), and
    against autodiff of the plain float32 reference (the products drop
    lo.lo: 3e-5), with rows of weight zero, on one device and over two."""
    d = 128
    X, y, w = _rows(n, d=d, C=C, holes=holes, seed=n + C)
    mesh, (Xs, ys, ws) = _on_mesh(n_dev, X, y, w)
    rng = np.random.default_rng(C)
    Wm = jnp.asarray(0.1 * rng.normal(size=(C, d)), jnp.float32)
    b = jnp.asarray(rng.normal(size=C), jnp.float32)
    got = L._softmax_evaluate(_tiles(128, mesh), Xs, ws, ys, Wm, b)
    blocks = L._softmax_evaluate(L.SoftmaxBlocks(128, mesh), Xs, ws, ys, Wm, b)
    f0, (gW0, gb0) = jax.value_and_grad(_ref_data_term, argnums=(3, 4))(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(w), Wm, b)

    def rel(a, ref):
        return float(np.linalg.norm(np.asarray(a, np.float64) - np.asarray(ref, np.float64))
                     / np.linalg.norm(np.asarray(ref, np.float64)))

    for k, (a, ref) in enumerate(zip(got, blocks)):
        assert rel(a, ref) <= 1e-6, k
    for a, ref in zip(got, (f0, gW0, gb0)):
        assert rel(a, ref) <= 3e-5
    assert got[1].shape == (C, d) and got[2].shape == (C,)


def test_the_kernel_masks_what_lies_past_the_rows(monkeypatch):
    """The last tile's rows past the end are read from whatever the buffer
    holds: NaN in the rows, weights or labels there must not reach a sum.
    A kernel told its shard has 200 rows, handed a 256-row buffer with NaN
    and wild labels in its last 56, gives the 200 rows' sums to the bit."""
    C, d = 10, 128
    X, y, w = _rows(256, d=d, C=C, seed=3)
    Wm = jnp.asarray(0.1 * np.random.default_rng(0).normal(size=(C, d)), jnp.float32)
    b = jnp.zeros((C,), jnp.float32)
    want = ps.shard_value_and_grad(
        jnp.asarray(X[:200]), jnp.asarray(w[:200]), jnp.asarray(y[:200]), Wm, b, 128, True)
    X[200:], w[200:], y[200:] = np.nan, np.nan, 7_777
    kernel = ps._kernel
    monkeypatch.setattr(ps, "_kernel", lambda rows, tile: kernel(200, tile))
    got = ps.shard_value_and_grad(
        jnp.asarray(X), jnp.asarray(w), jnp.asarray(y), Wm, b, 128, True)
    for a, ref in zip(got, want):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(ref))


def test_the_gradient_adds_its_partial_sums_every_few_tiles(monkeypatch):
    """The gradient in W gathers `_FLUSH_TILES` tiles in a partial sum, then
    adds it, compensated, to the whole; here every 2 of 9 tiles, the last
    a ragged one: the same sums as the blocks'."""
    C, d = 10, 128
    X, y, w = _rows(1031, d=d, C=C, holes=True, seed=9)
    rng = np.random.default_rng(2)
    Wm = jnp.asarray(0.1 * rng.normal(size=(C, d)), jnp.float32)
    b = jnp.asarray(rng.normal(size=C), jnp.float32)
    monkeypatch.setattr(ps, "_FLUSH_TILES", 2)
    got = ps.shard_value_and_grad(jnp.asarray(X), jnp.asarray(w), jnp.asarray(y), Wm, b, 128, True)
    blocks = L.SoftmaxBlocks(128).shard(jnp.asarray(X), jnp.asarray(w), jnp.asarray(y), Wm, b)
    for a, ref in zip(got, blocks):
        a, ref = np.asarray(a, np.float64), np.asarray(ref, np.float64)
        assert np.linalg.norm(a - ref) <= 1e-6 * np.linalg.norm(ref)


def test_the_kernels_parts_are_the_blocks_parts_bit_for_bit():
    """The kernel cuts its tile and its residual where they lie, by
    conversion: the same two bfloat16 parts, to the bit, as
    `_bf16_parts(x, 2)`'s `reduce_precision`, over float32's whole normal
    range, both signs, zeros and exact halfway cases of both parities."""
    from jax.experimental import pallas as pl

    rng = np.random.default_rng(11)
    x = rng.normal(size=(64, 256)) * 2.0 ** rng.integers(-120, 120, size=(64, 256))
    x = x.astype(np.float32)
    bits = x.view(np.int32)
    halfway = ((bits & -65536) | 0x8000).view(np.float32)  # a tie, hi even or odd
    x = np.concatenate([x, halfway, -halfway, np.zeros((8, 256), np.float32)])

    def cut(x_ref, hi_ref, lo_ref):
        hi_ref[...], lo_ref[...] = ps.bf16_parts(x_ref[...])

    part = jax.ShapeDtypeStruct(x.shape, jnp.bfloat16)
    hi, lo = pl.pallas_call(cut, out_shape=[part, part], interpret=True)(x)
    hi0, lo0 = _bf16_parts(jnp.asarray(x), 2)
    for a, ref in ((hi, hi0), (lo, lo0)):
        np.testing.assert_array_equal(np.asarray(a).view(np.int16), np.asarray(ref).view(np.int16))
    assert np.any(np.asarray(lo0) != 0)


def test_the_kernels_sums_across_tiles_are_compensated():
    """The value and the intercepts' gradient add one fold a tile into one
    float32 sum: 1 and then 255 increments of 3e-8, each under half an
    ulp of 1, are lost to a plain sum (1.0) and kept by `add_compensated`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    steps = 256
    v = np.full((steps, 1, 128), 3e-8, np.float32)
    v[0] = 1.0

    def kernel(v_ref, sum_ref, lost_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            sum_ref[...] = jnp.zeros_like(sum_ref)
            lost_ref[...] = jnp.zeros_like(lost_ref)

        ps.add_compensated(sum_ref, lost_ref, v_ref[0])

    got = pl.pallas_call(
        kernel, grid=(steps,), in_specs=[pl.BlockSpec((1, 1, 128), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, 128), jnp.float32)], interpret=True,
    )(v)
    exact = 1.0 + 255 * np.float64(np.float32(3e-8))
    assert np.float32(1.0) + np.float32(3e-8) == np.float32(1.0)
    np.testing.assert_allclose(np.asarray(got, np.float64), exact, rtol=1e-7)


def _dots(jaxpr):
    """Every dot_general of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _dots(sub)


def test_the_kernel_takes_three_bfloat16_passes_a_product():
    """What lowers to Mosaic hides its products from the lowered program's
    text (the blocks' and autodiff's are counted there, above), so the
    kernel's own jaxpr is read: the logits W X^T and the gradient R^T X
    each take exactly `_PASSES` dot_generals, of bfloat16 operands,
    accumulated in float32."""
    C, d, n = 10, 128, 300
    X, y, w = _rows(n, d=d, C=C, seed=5)
    Wm, b = jnp.zeros((C, d), jnp.float32), jnp.zeros((C,), jnp.float32)
    outer = jax.make_jaxpr(partial(ps.shard_value_and_grad, tile=128, interpret=True))(
        jnp.asarray(X), jnp.asarray(w), jnp.asarray(y), Wm, b)
    (call,) = [e for e in outer.jaxpr.eqns if e.primitive.name == "pallas_call"]
    dots = list(_dots(call.params["jaxpr"]))
    assert len(dots) == 2 * L._PASSES == 6
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [jnp.bfloat16, jnp.bfloat16]
        assert eqn.params["preferred_element_type"] == jnp.float32
    contractions = [eqn.params["dimension_numbers"][0] for eqn in dots]
    assert contractions.count(((1,), (1,))) == L._PASSES  # the logits, W X^T
    assert contractions.count(((1,), (0,))) == L._PASSES  # the gradient, R^T X


@pytest.mark.parametrize("n_dev", [1, 2])
@pytest.mark.parametrize("route", ["fused", "host_dispatch"])
def test_fit_through_the_kernel_matches_the_reference_lbfgs(route, n_dev):
    """A whole fit through the kernel against the plain reference's L-BFGS,
    as the blocks' fit above: coefficients, objective history, iterations
    and evaluations."""
    X, y, w = _rows(1030, d=128, C=4, seed=7)  # 515 rows a shard on two devices
    w = np.ones_like(w)
    mesh, (Xs, ys, ws) = _on_mesh(n_dev, X, y, w)
    kw = dict(n_classes=4, l2=1e-2, l1=0.0, fit_intercept=True, tol=1e-30, max_iter=6,
              softmax=_tiles(128, mesh))
    if route == "fused":
        W, b, loss, n_iter, hist, n_evals = L.logreg_fit(Xs, ws, ys, **kw)
    else:
        W, b, loss, n_iter, hist, n_evals = L.logreg_fit_host_dispatch(Xs, ws, ys, **kw)
    theta, ref_hist, ref_evals = ref_lbfgs(X, y, w, 4, 1e-2, 6)
    assert int(n_iter) == 6 and int(n_evals) == ref_evals
    got = np.append(np.asarray(W, np.float64).ravel(), np.asarray(b, np.float64))
    assert np.linalg.norm(got - theta) <= 1e-4 * np.linalg.norm(theta)
    np.testing.assert_allclose(np.asarray(hist, np.float64)[:7], ref_hist, rtol=1e-6)


# -- which evaluation a fit takes: read from the rows alone --------------------

def _stand_in(monkeypatch, rows, d, order=(0, 1), left=16_909_336_064 - 1_281_167 * 2048 * 4):
    """Rows of this shape on one TPU that lie `order` (major to minor), with
    `left` bytes of the device free beside them: no 10 GB array on the CPU."""
    from types import SimpleNamespace as NS

    from spark_rapids_ml_tpu.parallel import device_cache

    monkeypatch.setattr(L, "_on_tpu", lambda X: True)
    monkeypatch.setattr(device_cache, "device_hbm_bytes", lambda dev: rows * d * 4 + left)
    return NS(shape=(rows, d), dtype=jnp.float32, devices=lambda: [None],
              format=NS(layout=NS(major_to_minor=order)),
              addressable_shards=[NS(device=None, data=NS(nbytes=rows * d * 4, shape=(rows, d)))])


def test_the_cells_shape_on_a_tpu_takes_the_kernel(monkeypatch):
    """The ImageNet probe's rows (1,281,167 x 2,048, row-major on a v5e)
    with 1,000 classes: 256 rows a tile, the classes padded to 1,024, three
    passes a product, all of it in the instant's detail."""
    plan, detail = L.softmax_plan(_stand_in(monkeypatch, 1_281_167, 2048), 1000)
    assert plan == ps.SoftmaxTiles(256) and plan.evaluation == "softmax_tiles"
    for part in ("C=1000", "tile_rows=256", "classes_padded=1024", "passes=3 on 1 TPU(s)"):
        assert part in detail


@pytest.mark.parametrize(
    "rows,d,C,order,why",
    [(1_281_167, 2048, 1000, (1, 0), "major_to_minor=(1, 0), not row-major"),
     (1_000_000, 3000, 1000, (0, 1), "d=3000 is not a multiple of 128"),
     (100_000, 2048, 20_000, (0, 1), "VMEM plan")],
    ids=["column_major", "d_not_by_128", "C_over_vmem"],
)
def test_what_the_kernel_declines_keeps_the_blocks(monkeypatch, rows, d, C, order, why):
    """Rows that lie column-major, features that do not fill lane tiles and
    a gradient accumulator that does not fit VMEM keep XLA's blocks, the
    reason in the detail beside the blocks' own numbers."""
    plan, detail = L.softmax_plan(_stand_in(monkeypatch, rows, d, order, left=1 << 34), C)
    assert isinstance(plan, L.SoftmaxBlocks) and plan.evaluation == "softmax_blocks"
    assert why in detail and "block_rows=" in detail and "passes=3" in detail


@pytest.fixture
def interpreted_tiles(monkeypatch):
    """A backend that passes for a TPU, and the kernel's plan a fit makes
    there turned to Pallas' interpreter."""
    monkeypatch.setattr(L, "_on_tpu", lambda X: True)
    planned = ps.tiles_plan

    def interpreted(X, C, mesh):
        plan, why = planned(X, C, mesh)
        assert plan is None or not plan.interpret  # production never interprets
        return plan and plan._replace(interpret=True), why

    monkeypatch.setattr(ps, "tiles_plan", interpreted)


def test_a_multinomial_fit_on_tpu_takes_the_kernel_and_the_fused_route(
        rng, monkeypatch, interpreted_tiles):
    """Dense float32 rows whose features fill lane tiles (row-major, as the
    CPU lays out every array): `lbfgs_eval_kernel[softmax_tiles]`, the
    fused program, and the same model as the blocks give."""
    X, y = _dense(rng, n=700, d=128, C=5)
    kw = dict(regParam=0.01, maxIter=6, tol=1e-30, standardization=False)
    model = LogisticRegression(**kw).fit((X, y))
    ((kernel, detail),) = _events(model, "lbfgs_eval_kernel[")
    assert kernel == "lbfgs_eval_kernel[softmax_tiles]"
    assert "C=5" in detail and "tile_rows=256" in detail and "passes=3" in detail
    ((route, why),) = _events(model, "lbfgs_route[")
    assert route == "lbfgs_route[fused]" and "softmax_tiles evaluation" in why
    fact = model.fit_report()["lbfgs"]
    assert fact["evaluation"] == "softmax_tiles" and fact["classes"] == 5
    monkeypatch.setattr(ps, "tiles_plan", lambda X, C, mesh: (None, "the blocks, to compare"))
    blocks = LogisticRegression(**kw).fit((X, y))
    assert _events(blocks, "lbfgs_eval_kernel[")[0][0] == "lbfgs_eval_kernel[softmax_blocks]"
    np.testing.assert_allclose(model.coefficientMatrix, blocks.coefficientMatrix, atol=1e-5)
    np.testing.assert_allclose(model.interceptVector, blocks.interceptVector, atol=1e-5)
