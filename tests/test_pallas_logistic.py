#
# One-pass logistic value+gradient kernel (ops/pallas_logistic.py): the
# kernel in Pallas interpret mode on the CPU against `jax.value_and_grad`
# of `_binary_problem`'s autodiff loss, the masking of a partial last
# tile, the `shard_map` over a mesh, whole fits with the kernel forced on,
# and the selection, which is by the input alone.
#
from functools import partial

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from spark_rapids_ml_tpu.classification import LogisticRegression
from spark_rapids_ml_tpu.config import _DEFAULTS, reset_config, set_config
from spark_rapids_ml_tpu.ops import pallas_logistic as pk
from spark_rapids_ml_tpu.ops.logistic import (
    _binary_problem,
    logreg_fit_binary,
    logreg_fit_host_dispatch,
    one_pass_program_bytes,
)

TILE = 128
# the kernel through Pallas' interpreter: only a test asks for it
ONE = pk.OnePass(None, interpret=True)


def _rows(n, d=24, holes=False, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X @ rng.normal(size=d) + rng.normal(size=n) > 0).astype(np.int32)
    w = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    if holes:  # CV fold-mask holes and padding: rows of weight zero
        w[::5] = 0.0
        w[-17:] = 0.0
    theta = (0.3 * rng.normal(size=d + 1)).astype(np.float32)
    return X, w, y, theta


def _value_and_grad(X, w, y, theta, fit_intercept, plan):
    """`jax.value_and_grad` of `_binary_problem`'s loss, by autodiff
    (`plan` None) or through the kernel."""
    d = X.shape[1]
    if not fit_intercept:
        theta = theta[:d]
    one_pass = None
    if plan is not None:
        one_pass = partial(pk.one_pass_data_term, plan, jnp.asarray(X))
    loss_fn, _, _, _ = _binary_problem(
        lambda beta: jnp.asarray(X) @ beta, d, jnp.float32, jnp.asarray(w),
        jnp.asarray(y), 1e-3, fit_intercept, one_pass,
    )
    f, g = jax.value_and_grad(loss_fn)(jnp.asarray(theta))
    return float(f), np.asarray(g)


def _assert_close(got, want):
    """Value, gradient and intercept gradient to 1e-6 relative; the
    intercept's on the gradient's scale (it is a sum that cancels)."""
    f, g = got
    f0, g0 = want
    scale = np.linalg.norm(g0)
    assert abs(f - f0) <= 1e-6 * abs(f0)
    assert np.linalg.norm(g - g0) <= 1e-6 * scale
    assert abs(g[-1] - g0[-1]) <= 1e-6 * scale


@pytest.fixture
def tile128(monkeypatch):
    """Tiles of 128 rows, so that a few hundred rows are several tiles."""
    monkeypatch.setattr(pk, "_tile_rows", lambda d: TILE)


@pytest.mark.parametrize("fit_intercept", [True, False])
@pytest.mark.parametrize(
    "n,holes",
    [(384, False), (300, False), (300, True), (100, True), (129, False)],
    ids=["whole_tiles", "partial_tile", "partial_tile_holes", "one_short_tile",
         "one_row_over"],
)
def test_kernel_matches_autodiff(tile128, n, holes, fit_intercept):
    X, w, y, theta = _rows(n, holes=holes, seed=n)
    _assert_close(
        _value_and_grad(X, w, y, theta, fit_intercept, ONE),
        _value_and_grad(X, w, y, theta, fit_intercept, None),
    )


def test_mask_is_what_keeps_the_tail_out(tile128, monkeypatch):
    """The interpreter plants NaN past the end of the array in a partial
    last block, as a chip leaves whatever the buffer held: with the mask
    switched off they reach every output, with it none does, though the
    rows before them carry weight zero (0 x NaN is NaN: the mask is by
    index)."""
    X, w, y, theta = _rows(300, holes=True)
    args = (jnp.asarray(X).T, jnp.asarray(w), jnp.asarray(2.0 * y - 1.0, jnp.float32),
            jnp.asarray(theta[:-1]), jnp.asarray(theta[-1]))
    masked = pk.shard_value_and_grad(*args, interpret=True)
    assert all(np.isfinite(np.asarray(v)).all() for v in masked)
    kernel = pk._kernel
    monkeypatch.setattr(  # as if the rows filled the last tile: no mask
        pk, "_kernel", lambda rows, d, tile: kernel(-(-rows // tile) * tile, d, tile)
    )
    unmasked = pk.shard_value_and_grad(*args, interpret=True)
    assert all(np.isnan(np.asarray(v)).all() for v in unmasked)


def _on_mesh(n_dev, X, w, y):
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("data",))
    rows = NamedSharding(mesh, P("data"))
    return mesh, (
        jax.device_put(X, NamedSharding(mesh, P("data", None))),
        jax.device_put(w, rows), jax.device_put(y, rows),
    )


@pytest.mark.parametrize("n", [512, 1160], ids=["whole_tiles", "partial_tiles"])
def test_shard_map_matches_single_device(tile128, n):
    """Four devices, each its shard under `shard_map` and one psum, against
    the kernel on one device and against autodiff."""
    X, w, y, theta = _rows(n, holes=True, seed=n)
    mesh, (Xs, ws, ys) = _on_mesh(4, X, w, y)
    loss_fn, _, _, _ = _binary_problem(
        None, X.shape[1], jnp.float32, ws, ys, 1e-3, True,
        partial(pk.one_pass_data_term, pk.OnePass(mesh, interpret=True), Xs),
    )
    f, g = jax.jit(jax.value_and_grad(loss_fn))(jnp.asarray(theta))
    sharded = float(f), np.asarray(g)
    _assert_close(sharded, _value_and_grad(X, w, y, theta, True, ONE))
    _assert_close(sharded, _value_and_grad(X, w, y, theta, True, None))


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("route", ["fused", "host_dispatch"])
def test_fit_with_kernel_matches_autodiff_fit(tile128, route, n_dev):
    """A whole L-BFGS fit with the kernel forced on: the iteration count
    and the coefficients of the autodiff fit, on both routes."""
    X, w, y, _ = _rows(1160, d=16, holes=True, seed=7)
    mesh, (Xs, ws, ys) = _on_mesh(n_dev, X, w, y)
    plan = pk.OnePass(mesh if n_dev > 1 else None, interpret=True)
    kw = dict(l2=1e-2, l1=0.0, fit_intercept=True, tol=1e-4, max_iter=60)

    def fit(one_pass):
        if route == "fused":
            return logreg_fit_binary(Xs, ws, ys, one_pass=one_pass, **kw)
        return logreg_fit_host_dispatch(
            Xs, ws, ys, n_classes=2, binomial=True, one_pass=one_pass, **kw
        )

    coef, b, loss, n_iter, _ = fit(plan)
    coef0, b0, loss0, n_iter0, _ = fit(None)
    assert int(n_iter) == int(n_iter0) and 3 < int(n_iter) < 60
    np.testing.assert_allclose(np.asarray(coef), np.asarray(coef0), atol=1e-5)
    np.testing.assert_allclose(float(b), float(b0), atol=1e-5)
    assert abs(float(loss) - float(loss0)) <= 1e-6 * abs(float(loss0))


# -- the selection: by the input alone ---------------------------------------


def _pass_for_tpu(monkeypatch):
    """The backend check stubbed and the rows taken for column-major (on
    the CPU they lie row-major): what is left decides."""
    monkeypatch.setattr(pk, "_on_tpu", lambda X: True)
    monkeypatch.setattr(pk, "_rows_minor", lambda layout: True)


@pytest.fixture
def as_on_tpu(monkeypatch):
    _pass_for_tpu(monkeypatch)


def _events(model, prefix):
    def find(nodes):
        for n in nodes:
            if n["name"].startswith(prefix):
                yield n["name"], n.get("detail", "")
            yield from find(n.get("children", []))

    return list(find(model.fit_report()["spans"]))


def _eval_kernel_events(model):
    return _events(model, "lbfgs_eval_kernel[")


def _dense(rng, n=640, d=16):
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X @ rng.normal(size=d) + rng.normal(size=n) > 0).astype(np.float64)
    return X, y


def _fit_ell(rng):
    X, y = _dense(rng)
    X[np.abs(X) < 1.0] = 0.0
    return LogisticRegression(regParam=0.01, maxIter=10).fit((sp.csr_matrix(X), y))


def _fit_multinomial(rng):
    X, _ = _dense(rng)
    y = np.digitize(X[:, 0], [-0.5, 0.5]).astype(np.float64)
    return LogisticRegression(regParam=0.01, maxIter=10).fit((X, y))


def _fit_bf16(rng):
    set_config(bf16_features=True)
    try:
        return LogisticRegression(regParam=0.01, maxIter=10).fit(_dense(rng))
    finally:
        reset_config()


def _fit_dense(rng):
    return LogisticRegression(regParam=0.01, maxIter=10).fit(_dense(rng))


@pytest.mark.parametrize(
    "fit,why",
    [(_fit_ell, "ELL sparse"), (_fit_multinomial, "multinomial"),
     (_fit_bf16, "bfloat16"), (_fit_dense, "backend cpu")],
    ids=["ell", "multinomial", "bf16_features", "cpu_backend"],
)
def test_every_other_input_keeps_autodiff(rng, monkeypatch, fit, why):
    """One instant a fit, `lbfgs_eval_kernel[autodiff]`, with the reason in
    its detail.  All but the last on a backend that passes for a TPU, so
    that the input is what decides."""
    if fit is not _fit_dense:
        _pass_for_tpu(monkeypatch)
    ((name, detail),) = _eval_kernel_events(fit(rng))
    assert name == "lbfgs_eval_kernel[autodiff]"
    assert why in detail


@pytest.fixture
def interpreted_plan(monkeypatch):
    """A backend that passes for a TPU, and the plan a fit makes there
    turned to Pallas' interpreter."""
    _pass_for_tpu(monkeypatch)
    planned = pk.one_pass_plan

    def interpreted(X, binomial):
        plan, why = planned(X, binomial)
        assert plan is None or not plan.interpret  # production never interprets
        return plan and plan._replace(interpret=True), why

    monkeypatch.setattr(pk, "one_pass_plan", interpreted)


def test_dense_f32_binomial_on_tpu_takes_the_kernel(rng, request):
    """The backend and layout checks stubbed, a dense float32 binomial fit
    records `lbfgs_eval_kernel[one_pass]` and runs the kernel (the plan it
    made, turned to the interpreter here): the same model as the autodiff
    fit."""
    X, y = _dense(rng)
    kw = dict(regParam=0.01, maxIter=25, tol=1e-9)
    plain = LogisticRegression(**kw).fit((X, y))
    request.getfixturevalue("interpreted_plan")
    model = LogisticRegression(**kw).fit((X, y))
    ((name, detail),) = _eval_kernel_events(model)
    assert name == "lbfgs_eval_kernel[one_pass]"
    assert "float32" in detail and "binomial" in detail
    np.testing.assert_allclose(model.coef_, plain.coef_, atol=1e-5)
    np.testing.assert_allclose(model.intercept_, plain.intercept_, atol=1e-5)


@pytest.fixture
def conf():
    """`set_config` for one test."""
    yield set_config
    reset_config()


@pytest.mark.parametrize("budget", [None, 1.0, 1e6], ids=["default", "1", "1e6"])
def test_a_kernel_fit_is_routed_by_memory_alone(rng, interpreted_plan, conf, budget):
    """A fit with a one-pass plan takes the fused program whatever the
    per-program FLOP budget says: its program holds a few rows-length
    vectors beside the rows, and the instant says so in bytes."""
    if budget is not None:
        conf(dispatch_flops_limit=budget)
    model = LogisticRegression(regParam=0.01, maxIter=10).fit(_dense(rng))
    ((route, detail),) = _events(model, "lbfgs_route[")
    assert route == "lbfgs_route[fused]"
    assert "in place" in detail and "fits the device" in detail
    assert "budget" not in detail and "checkpointing off" in detail
    assert [n for n, _ in _eval_kernel_events(model)] == ["lbfgs_eval_kernel[one_pass]"]


def test_a_checkpointed_kernel_fit_stays_host_dispatched(
        rng, interpreted_plan, conf, tmp_path):
    """Its state must persist per iteration: one program a fit has no
    iteration boundary to write it at."""
    conf(checkpoint_dir=str(tmp_path))
    model = LogisticRegression(regParam=0.01, maxIter=10).fit(_dense(rng))
    ((route, detail),) = _events(model, "lbfgs_route[")
    assert route == "lbfgs_route[host_dispatch]" and "checkpointing on" in detail
    assert [n for n, _ in _eval_kernel_events(model)] == ["lbfgs_eval_kernel[one_pass]"]


def test_a_kernel_fit_whose_program_does_not_fit_stays_host_dispatched(
        rng, interpreted_plan, conf):
    """By arithmetic, not by a compile-time OOM: the rows, padded to 768,
    are 49,152 bytes on their one device, the program's bound beside them
    42,864, and the device has 31,808 left."""
    conf(hbm_bytes=49_152 + 31_808)
    est = LogisticRegression(regParam=0.01, maxIter=10, num_workers=1)
    ((route, detail),) = _events(est.fit(_dense(rng)), "lbfgs_route[")
    assert route == "lbfgs_route[host_dispatch]" and "does NOT fit" in detail
    assert "at most 4.29e+04 B beside them, 3.18e+04 B are free" in detail


@pytest.mark.parametrize("fit", [_fit_multinomial, _fit_dense, _fit_bf16],
                         ids=["multinomial", "cpu_backend", "bf16_features"])
def test_a_fit_without_a_plan_keeps_the_budget(rng, monkeypatch, conf, fit):
    """Autodiff's program holds the rows twice and keeps the per-program
    budget: the old rule, in the old words."""
    if fit is not _fit_dense:
        _pass_for_tpu(monkeypatch)
    assert _events(fit(rng), "lbfgs_route[")[0][0] == "lbfgs_route[fused]"
    conf(dispatch_flops_limit=1e4)
    ((route, detail),) = _events(fit(rng), "lbfgs_route[")
    assert route == "lbfgs_route[host_dispatch]"
    assert "fused FLOPs vs budget 1e+04" in detail and "second copy" in detail


@pytest.mark.parametrize(
    "shape,dtype,binomial,why",
    [((640, 12), np.float32, True, "tiles features by 8"),
     ((64, 16384), np.float32, True, "tiles features by 8"),
     ((640, 16), np.float64, True, "reads float32")],
    ids=["features_not_by_8", "features_too_wide", "float64"],
)
def test_plan_declines_what_the_kernel_cannot_tile(as_on_tpu, shape, dtype, binomial, why):
    with jax.enable_x64(dtype == np.float64):
        plan, detail = pk.one_pass_plan(jnp.zeros(shape, dtype), binomial)
    assert plan is None and why in detail


def test_plan_declines_rows_that_do_not_lie_column_major(monkeypatch):
    """The layout is read from the array: on the CPU rows lie row-major,
    and a backend that passes for a TPU does not make `X.T` their bytes."""
    monkeypatch.setattr(pk, "_on_tpu", lambda X: True)
    X = jnp.zeros((640, 16), jnp.float32)
    assert tuple(X.format.layout.major_to_minor) == (0, 1)
    plan, detail = pk.one_pass_plan(X, True)
    assert plan is None and "not column-major" in detail
    assert "major_to_minor=(0, 1)" in detail


def test_plan_reads_the_mesh_from_the_rows(as_on_tpu):
    X, w, y, _ = _rows(512)
    mesh, (Xs, _, _) = _on_mesh(4, X, w, y)
    plan, detail = pk.one_pass_plan(Xs, True)
    assert plan == pk.OnePass(mesh) and "4 TPUs" in detail
    by_features = jax.device_put(X, NamedSharding(mesh, P(None, "data")))
    plan, detail = pk.one_pass_plan(by_features, True)
    assert plan is None and "first axis" in detail
    assert pk.one_pass_plan(jnp.asarray(X), True)[0] == pk.OnePass(None)


def test_no_conf_key_selects_the_kernel():
    """Who takes the kernel is read from the input: no key was added (101
    keys, PERF.md §6, PR 31) and none names it."""
    assert len(_DEFAULTS) == 101
    assert not [k for k in _DEFAULTS if "logistic" in k or "one_pass" in k]


# -- compiled for a described v5e, no chip: the rows are read where they lie ---
#
# The one trap of this kernel (PERF.md §6, PR 32): ask for the rows in another
# layout than the one they lie in and XLA copies all 12 GB of them.  The TPU's
# compiler is installed without the chip; these are the only tests of the
# repo that load it, all in this file (one process at a time may).


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no compiler here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _no_copy_of_the_rows(compiled, shard_rows, d=3000):
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    (rows_format,) = [
        f for f in jax.tree.leaves(compiled.input_formats)
        if len(f.layout.major_to_minor) == 2
    ]
    assert pk._rows_minor(rows_format.layout)  # what the plan would read
    # resident layout in, a bitcast to the kernel's (d, rows), no operation
    # that writes an array of the rows' size
    assert f"f32[{shard_rows},{d}]{{0,1:T(8,128)}}" in text.split("\n", 1)[0]
    assert f"f32[{d},{shard_rows}]{{1,0:T(8,128)}} bitcast(" in text
    written = [
        line for line in text.splitlines()
        if f"= f32[{shard_rows},{d}]" in line or f"= f32[{d},{shard_rows}]" in line
    ]
    assert all(
        any(op in line for op in (" parameter(", " bitcast(", " get-tuple-element("))
        for line in written
    ), written
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def _lower_one_chip_evaluation(topo, rows, d):
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    @jax.jit
    def vg_fn(theta, dat, w, y):  # as `logreg_fit_host_dispatch` builds it
        loss_fn, _, _, _ = _binary_problem(
            None, d, jnp.float32, w, y, 1e-5, True,
            partial(pk.one_pass_data_term, pk.OnePass(None), dat),
        )
        return jax.value_and_grad(loss_fn)(theta)

    return vg_fn.lower(
        spec((d + 1,)), spec((rows, d)), spec((rows,)), spec((rows,), jnp.int32)
    )


def test_one_chip_evaluation_reads_1m_x_3000_in_place(topo):
    rows = 1_000_000
    compiled = _lower_one_chip_evaluation(topo, rows, 3000).compile()
    _no_copy_of_the_rows(compiled, rows)
    assert compiled.as_text().count("tpu_custom_call") == 1  # forward only


@pytest.mark.parametrize(
    "rows,d", [(100_000, 256), (1000, 3000)], ids=["d_by_128", "few_rows"]
)
def test_row_major_rows_are_what_the_plan_declines(topo, rows, d):
    """Which way a shape's rows lie is the runtime's choice: row-major
    where d is a multiple of 128, or where the rows are few.  Forced on
    such rows the kernel's program COPIES them (at 1M x 3072 it asks 22.9
    GB of the chip's 15.75 and does not compile), and the layout the
    program is handed is the one `one_pass_plan` declines."""
    compiled = _lower_one_chip_evaluation(topo, rows, d).compile()
    text = compiled.as_text()
    assert f"f32[{rows},{d}]{{1,0:T(8,128)}}" in text.split("\n", 1)[0]
    assert [
        line for line in text.splitlines()
        if " copy(" in line and f"= f32[{rows},{d}]{{0,1:T(8,128)" in line
    ]
    rows_format = compiled.input_formats[0][1]
    assert not pk._rows_minor(rows_format.layout)


def test_kernel_lowers_in_a_process_with_x64_on(topo):
    """A float64 fit leaves x64 on in its process; the float32 fit after it
    still takes the kernel, whose Python indices would then be 64-bit:
    Mosaic refuses those ('arith.muli' of i32 and i64), so the kernel is
    traced with x64 off."""
    with jax.enable_x64(True):
        compiled = _lower_one_chip_evaluation(topo, 200_000, 1000).compile()
    _no_copy_of_the_rows(compiled, 200_000, 1000)


def test_four_chip_fused_fit_reads_its_shards_in_place(topo):
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    rows, d = 2_000_000, 3000

    def spec(shape, pspec, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, pspec))

    compiled = logreg_fit_binary.lower(
        spec((rows, d), P("data", None)), spec((rows,), P("data")),
        spec((rows,), P("data"), jnp.int32), l2=1e-5, l1=0.0, fit_intercept=True,
        tol=1e-30, max_iter=20, one_pass=pk.OnePass(mesh),
    ).compile()
    _no_copy_of_the_rows(compiled, rows // 4)
    # one all-reduce of [gradient, sum r, loss] per evaluation site
    reduces = [l for l in compiled.as_text().splitlines() if " all-reduce(" in l]
    assert len([l for l in reduces if f"f32[{d + 2}]" in l]) == 3, reduces


@pytest.mark.parametrize(
    "rows,max_iter", [(1_000_000, 20), (1_048_576, 20), (1_000_000, 200)],
    ids=["1m", "ingest_bucket", "published_depth"],
)
def test_one_chip_fused_fit_reads_its_rows_in_place(topo, rows, max_iter):
    """The whole fit as ONE program on one chip, at the cached cell's rows,
    at the padded bucket a staged fit of them gets, and at the published
    depth: no second copy of the rows in the `while_loop`'s state, and what
    the program holds beside them within the bound its router counts."""
    from jax.sharding import SingleDeviceSharding

    one, d = SingleDeviceSharding(topo.devices[0]), 3000

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = logreg_fit_binary.lower(
        spec((rows, d)), spec((rows,)), spec((rows,), jnp.int32), l2=1e-5, l1=0.0,
        fit_intercept=True, tol=1e-30, max_iter=max_iter, one_pass=pk.OnePass(None),
    ).compile()
    _no_copy_of_the_rows(compiled, rows)
    # the first evaluation, the line search's first trial and its retries
    assert compiled.as_text().count("tpu_custom_call") == 3
    memory = compiled.memory_analysis()
    beside = memory.temp_size_in_bytes + memory.argument_size_in_bytes - rows * d * 4
    assert beside <= one_pass_program_bytes(rows, d, 10) < 64 << 20


@pytest.mark.parametrize(
    "hbm_bytes,in_place,fits",
    [(16_909_336_064, True, True), (12_010_000_000, True, False),
     (16_909_336_064, False, False), (24_100_000_000, False, True)],
    ids=["kernel_15.75GiB", "kernel_12.01GB", "autodiff_15.75GiB", "autodiff_24.1GB"],
)
def test_the_memory_test_counts_what_the_program_holds(conf, hbm_bytes, in_place, fits):
    """12 GB of rows on one chip, more than the CPU holds, so a stand-in for
    them: a kernel fit's program holds 35 MB beside them, an autodiff
    fit's a second 12 GB."""
    from types import SimpleNamespace as NS

    from spark_rapids_ml_tpu.parallel.device_cache import fused_program_fits

    conf(hbm_bytes=hbm_bytes)
    rows, d = 1_000_000, 3000
    X = NS(addressable_shards=[NS(
        device=jax.devices()[0], data=NS(nbytes=rows * d * 4, shape=(rows, d)))])
    held = one_pass_program_bytes(rows, d, 10)
    assert 30e6 < held < 40e6
    assert fused_program_fits(X, held if in_place else 0, rows_in_place=in_place) is fits


# -- PCA's covariance (ops/pca.py `pca_scatter`), compiled for the same chip: the
# programs a resident fit of 1M x 3000 runs hold no second copy of the rows
# among their temporaries (a centred copy would be 12 GB of them)

def _covariance_programs(mesh, spec, rows, d):
    """(the mean pass, the shifted block program without labels, the finish),
    lowered for `rows` x `d` resident rows."""
    from spark_rapids_ml_tpu.ops import linear
    from spark_rapids_ml_tpu.ops import pca as pca_ops

    lead = () if mesh is None else (mesh.devices.size,)
    acc = tuple(spec(lead + shape, lead_axis=bool(lead)) for shape in ((d, d), (d,), ()))
    at = spec((), dtype=jnp.int32)
    X, w = spec((rows, d), rows_axis=True), spec((rows,), rows_axis=True)
    block = linear._split_block_program(mesh, 62_500, 512, False, True)
    return (
        pca_ops._pca_covariance_shift.lower(X, w),
        block.lower(acc, X, w, None, at, at, spec((d,))),
        linear._linreg_sufficient_stats_finish.lower(acc),
    )


def test_pca_covariance_reads_1m_x_3000_in_place_on_one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype=jnp.float32, **_):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    shift, block, finish = (
        low.compile() for low in _covariance_programs(None, spec, 1_000_000, 3000))
    assert shift.memory_analysis().temp_size_in_bytes < 1 << 20
    # the block's bfloat16 parts and panels: 0.79 GB beside 12 GB of rows
    assert block.memory_analysis().temp_size_in_bytes < 1 << 30
    assert finish.memory_analysis().temp_size_in_bytes < 128 << 20


def test_pca_covariance_crosses_four_chips_once(topo):
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))

    def spec(shape, dtype=jnp.float32, rows_axis=False, lead_axis=False):
        pspec = P("data") if rows_axis or lead_axis else P()
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, pspec))

    shift, block, finish = (
        low.compile() for low in _covariance_programs(mesh, spec, 1_000_000, 3000))
    assert shift.memory_analysis().temp_size_in_bytes < 1 << 20
    assert block.memory_analysis().temp_size_in_bytes < 1 << 30
    # the blocks add into each chip's own accumulators; the mean pass and
    # the finish are the sums that cross chips
    assert " all-reduce(" not in block.as_text()
    assert " all-reduce(" in shift.as_text() and " all-reduce(" in finish.as_text()


@pytest.mark.parametrize("chips", [1, 4])
def test_pca_subspace_iteration_compiles_for_the_resident_covariance(topo, chips):
    """The eigensolve's device half at the cell's shape (ops/pca.py
    `_pca_subspace_iterate`, 3,000 columns, the block and steps
    `subspace_plan` gives k = 3): it compiles for the chip, beside the
    (d,d) matrix it holds megabytes, its loop stays a loop, and over four
    chips, where every chip holds the matrix, nothing crosses them."""
    from jax.sharding import SingleDeviceSharding

    from spark_rapids_ml_tpu.ops import pca as pca_ops

    block, steps, why = pca_ops.subspace_plan(3000, 3)
    assert block and not why
    if chips == 1:
        held = SingleDeviceSharding(topo.devices[0])
    else:
        held = NamedSharding(Mesh(np.array(topo.devices).reshape(4), ("data",)), P())
    compiled = pca_ops._pca_subspace_iterate.lower(
        jax.ShapeDtypeStruct((3000, 3000), jnp.float32, sharding=held),
        block=block, steps=steps).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 8 << 20
    assert 3000 * block * 4 <= memory.output_size_in_bytes < 1 << 20  # rows padded to tiles
    text = compiled.as_text()
    assert " while(" in text
    assert " all-reduce(" not in text and " all-gather(" not in text
