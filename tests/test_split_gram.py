#
# The f32-exact Gram of one matrix in four bfloat16 products, symmetric
# halves skipped (ops/linear.py `split_gram_half`, `linreg_stats_split`):
# the same six terms XLA's `highest` adds, in row blocks read in place.
# The CPU backend routes a fit to the single matmul (`gram_kernel_plan`),
# so the split path is called directly here, or the backend check stubbed.
#
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu.config import reset_config, set_config
from spark_rapids_ml_tpu.ops import linear
from spark_rapids_ml_tpu.regression import LinearRegression

ROWS, COLS = 2003, 96
# panels of one (no triangle), three equal and three unequal widths
PANELS = [96, 32, 40]


def _rows(rows=ROWS, cols=COLS, seed=0):
    rng = np.random.default_rng(seed)
    X = (rng.normal(size=(rows, cols)) * rng.uniform(0.5, 3.0, cols)).astype(np.float32)
    y = (X @ rng.normal(size=cols) + rng.normal(size=rows)).astype(np.float32)
    return X, y


def _stats64(X, w, y):
    X, w, y = (np.asarray(a, np.float64) for a in (X, w, y))
    Xw = X * w[:, None]
    return Xw.T @ X, Xw.T @ y, Xw.sum(axis=0), w.sum(), (y * w).sum(), (y * y * w).sum()


def _close(got, want, rel):
    for g, v in zip(got, want):
        v = np.asarray(v, np.float64)
        assert np.abs(np.asarray(g, np.float64) - v).max() <= rel * max(np.abs(v).max(), 1e-30)


def _gram(half):
    half = np.asarray(half)
    return half + half.T


@pytest.mark.parametrize("panel_cols", PANELS)
def test_four_products_hold_the_six_terms(panel_cols):
    """h^T h + m^T m + P + P^T is XLA's six-term `highest` sum: against the
    six products of the same parts added in float64 it differs by float32
    accumulation alone, against a float64 Gram it errs no more than the
    six products added in float32 do, and it is symmetric bit for bit."""
    X, _ = _rows()
    Z = jnp.asarray(X)
    gram = _gram(linear.split_gram_half(Z, panel_cols))
    h, m, l = (np.asarray(p.astype(jnp.float32), np.float64) for p in linear._bf16_parts(Z))
    six64 = h.T @ h + h.T @ m + m.T @ h + m.T @ m + h.T @ l + l.T @ h
    top = np.abs(six64).max()
    assert np.abs(gram - six64).max() <= 1e-6 * top

    hb, mb, lb = linear._bf16_parts(Z)
    six32 = np.asarray(sum(
        linear._rows_dot(a, b)
        for a, b in ((hb, hb), (hb, mb), (mb, hb), (mb, mb), (hb, lb), (lb, hb))))
    true = np.asarray(X, np.float64).T @ np.asarray(X, np.float64)
    assert np.abs(gram - true).max() <= 1.5 * np.abs(six32 - true).max() + 1e-7 * top
    assert np.abs(gram - true).max() <= 1e-6 * top
    assert (gram == gram.T).all()


def test_the_triangle_on_and_off_give_the_same_array():
    X, _ = _rows()
    whole, *panelled = (_gram(linear.split_gram_half(jnp.asarray(X), p)) for p in PANELS)
    for gram in panelled:
        np.testing.assert_allclose(gram, whole, rtol=0, atol=2e-7 * np.abs(whole).max())


def test_no_term_is_dropped():
    """Rows whose low parts carry the signal: without m^T m, h^T l and l^T h
    the Gram of (1 + 2^-9 u) rows loses what separates them."""
    rng = np.random.default_rng(3)
    X = (1.0 + rng.uniform(-1, 1, (ROWS, COLS)) * 2.0 ** -9).astype(np.float32)
    gram = _gram(linear.split_gram_half(jnp.asarray(X), 32))
    true = X.astype(np.float64).T @ X.astype(np.float64)
    h = np.asarray(linear._bf16_parts(jnp.asarray(X))[0].astype(jnp.float32), np.float64)
    assert np.abs(gram - true).max() <= 1e-6 * np.abs(true).max()
    assert np.abs(h.T @ h - true).max() > 1e-4 * np.abs(true).max()


@pytest.mark.parametrize("block_rows", [None, 500, 2003, 667, 1], ids=str)
def test_row_blocks_cover_every_row_once(block_rows):
    """2,003 rows in one block, in blocks that do not tile them (the last
    starts early and its overlap counts for nothing) and a row at a time."""
    X, y = _rows(rows=2003 if block_rows != 1 else 37)
    w = np.ones(len(X), np.float32)
    got = linear.linreg_stats_split(
        jnp.asarray(X), jnp.asarray(w), jnp.asarray(y), block_rows=block_rows, panel_cols=32)
    _close(got, _stats64(X, w, y), 2e-6)
    assert (np.asarray(got[0]) == np.asarray(got[0]).T).all()


@pytest.mark.parametrize("n_dev", [2, 4])
def test_rows_on_a_mesh_equal_one_devices(n_dev):
    X, y = _rows(rows=2000)
    w = np.random.default_rng(1).uniform(0.5, 2.0, len(X)).astype(np.float32)
    one = linear.linreg_stats_split(
        jnp.asarray(X), jnp.asarray(w), jnp.asarray(y), block_rows=300, panel_cols=32)
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("data",))
    Xs = jax.device_put(X, NamedSharding(mesh, P("data", None)))
    ws, ys = (jax.device_put(a, NamedSharding(mesh, P("data"))) for a in (w, y))
    got = linear.linreg_stats_split(Xs, ws, ys, mesh, block_rows=300, panel_cols=32)
    _close(got, one, 2e-6)
    _close(got, _stats64(X, w, y), 2e-6)
    assert (np.asarray(got[0]) == np.asarray(got[0]).T).all()


def test_a_zero_weight_row_changes_nothing():
    """The SUPPORTS_ZERO_WEIGHT_ROWS contract on the split path: sqrt(0)
    makes the row zeros in Z, whatever it holds."""
    X, y = _rows(rows=400)
    w = np.random.default_rng(2).uniform(0.5, 2.0, len(X)).astype(np.float32)
    Xz = np.concatenate([X, np.full((9, COLS), 1e3, np.float32)])
    yz = np.concatenate([y, np.full(9, 1e3, np.float32)])
    wz = np.concatenate([w, np.zeros(9, np.float32)])
    order = np.random.default_rng(4).permutation(len(Xz))
    plain = linear.linreg_stats_split(
        jnp.asarray(X), jnp.asarray(w), jnp.asarray(y), panel_cols=32)
    holes = linear.linreg_stats_split(
        jnp.asarray(Xz[order]), jnp.asarray(wz[order]), jnp.asarray(yz[order]),
        block_rows=128, panel_cols=32)
    _close(holes, plain, 2e-6)


def test_fractional_weights_are_the_weighted_gram():
    """sqrt(w) folded into both sides is (X w)^T X."""
    X, y = _rows(rows=800)
    w = np.random.default_rng(5).uniform(0.01, 7.0, len(X)).astype(np.float32)
    got = linear.linreg_stats_split(
        jnp.asarray(X), jnp.asarray(w), jnp.asarray(y), block_rows=256, panel_cols=40)
    _close(got, _stats64(X, w, y), 2e-6)


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The backend check stubbed: the rows and the precision level decide."""
    monkeypatch.setattr(linear, "_on_tpu", lambda X: True)


WIDE = linear._GRAM_PANEL_COLS + 8


@pytest.mark.parametrize(
    "level,dtype,cols,kernel,why",
    [("highest", jnp.float32, WIDE, "symmetric_split", "one TPU"),
     ("highest", jnp.float32, linear._GRAM_PANEL_COLS, "xla", "one panel"),
     ("high", jnp.float32, WIDE, "xla", "fewer passes"),
     ("default", jnp.float32, WIDE, "xla", "fewer passes"),
     ("high_compensated", jnp.float32, WIDE, "xla", "fewer passes"),
     ("highest", jnp.float64, WIDE, "xla", "float32 into bfloat16")],
    ids=["f32_highest", "narrow", "high", "default", "high_compensated", "f64"],
)
def test_who_takes_the_split_is_read_from_the_input(as_on_tpu, level, dtype, cols, kernel, why):
    with jax.enable_x64(dtype == jnp.float64):
        X = jnp.zeros((16, cols), dtype)
        set_config(stats_precision=level)
        try:
            got, mesh, detail = linear.gram_kernel_plan(X)
        finally:
            reset_config()
    assert (got, mesh) == (kernel, None)
    assert why in detail and str(np.dtype(dtype)) in detail and level.split("_")[0] in detail


def test_a_cpu_keeps_the_single_matmul():
    kernel, _, detail = linear.gram_kernel_plan(jnp.zeros((16, WIDE), jnp.float32))
    assert kernel == "xla" and "backend cpu" in detail


@pytest.mark.parametrize("n_dev,spec,kernel", [
    (2, P("data", None), "symmetric_split"), (4, P("data", None), "symmetric_split"),
    (2, P(None, "data"), "xla"), (2, P(), "xla"),
], ids=["rows_over_2", "rows_over_4", "columns_sharded", "replicated"])
def test_sharded_rows_take_the_split_under_their_mesh(as_on_tpu, n_dev, spec, kernel):
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("data",))
    X = jax.device_put(np.zeros((16, 2 * WIDE), np.float32), NamedSharding(mesh, spec))
    got, got_mesh, detail = linear.gram_kernel_plan(X)
    assert got == kernel and (got_mesh is mesh) == (kernel == "symmetric_split")
    assert ("not sharded over" in detail) == (kernel == "xla")


def _gram_kernel_events(model):
    def find(nodes):
        for n in nodes:
            if n["name"].startswith("linreg_gram_kernel["):
                yield n["name"], n.get("detail", "")
            yield from find(n.get("children", []))

    return list(find(model.fit_report()["spans"]))


def _wide_fit(num_workers=1):
    X, y = _rows(rows=1200, cols=WIDE, seed=7)
    model = LinearRegression(
        regParam=1e-3, elasticNetParam=0.0, standardization=False, num_workers=num_workers,
    ).fit((X, y))
    return model


@pytest.mark.parametrize("num_workers", [1, 2])
def test_a_fit_records_its_gram_kernel_once(monkeypatch, num_workers):
    """One `linreg_gram_kernel[...]` instant a fit, the reason in its
    detail; a fit that passes for one on TPUs takes the split and ends at
    the single matmul's model."""
    plain = _wide_fit(num_workers)
    ((name, detail),) = _gram_kernel_events(plain)
    assert name == "linreg_gram_kernel[xla]" and "backend cpu" in detail

    monkeypatch.setattr(linear, "_on_tpu", lambda X: True)
    split = _wide_fit(num_workers)
    ((name, detail),) = _gram_kernel_events(split)
    assert name == "linreg_gram_kernel[symmetric_split]"
    assert "float32" in detail and "highest" in detail and "Z^T Z" in detail
    np.testing.assert_allclose(split.coef_, plain.coef_, rtol=2e-4, atol=2e-5)
    assert split.intercept_ == pytest.approx(plain.intercept_, abs=1e-4)
