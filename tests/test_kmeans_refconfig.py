#
# What the reference's KMeans benchmark row (1M x 3000, k=1000, random
# init; chipbench's `kmeans_fit_cached`) asks of the program, at sizes the
# CPU mesh runs: the estimator against the benchmark's plain reference on
# both routes and on one device and eight, the `random` init as a rule of
# (seed, k, rows of positive weight), the memory gate and its block size,
# the stated precision and the named scopes in the step's program.
#
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import blocks, datagen
from chipbench import manifest as mf
from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.config import reset_config, set_config
from spark_rapids_ml_tpu.data import DeviceDataset
from spark_rapids_ml_tpu.ops import kmeans as km
from spark_rapids_ml_tpu.parallel import get_mesh

ROWS, COLS, K = 8192, 48, 16
# six iterations, as the cell cuts its depth.  Squared distances by the
# matmul identity carry float32 rounding of |x|^2 (~3e-4 here), so a row
# whose two nearest centres tie within that may go either way and the
# trajectories part by ~1e-3: one data seed in three meets such a row at this
# size (2**31+29 and +31 do; +30, drawn below, does not), every fit does at
# 1M rows (PERF.md §4)
PARAMS = {"k": K, "maxIter": 6, "tol": 1e-20, "initMode": "random", "seed": 4}
BLOBS = {"model": "blobs", "centers": K}


@pytest.fixture(autouse=True)
def _clean_config():
    reset_config()
    yield
    reset_config()


def _needs(n_dev):
    if len(jax.devices()) < n_dev:
        pytest.skip(f"needs {n_dev} devices")


def _span_names(model):
    def walk(nodes):
        for n in nodes:
            yield n["name"]
            yield from walk(n.get("children", []))

    return list(walk(model.fit_report()["spans"]))


# -- the estimator against the plain reference ---------------------------------

@pytest.mark.parametrize("route", ["fused", "stepwise"])
@pytest.mark.parametrize("n_dev", [1, 8])
def test_estimator_agrees_with_the_plain_reference(n_dev, route, monkeypatch):
    _needs(n_dev)
    monkeypatch.setattr(blocks, "BLOCK_ROWS", 256)
    adapter = mf.adapter("kmeans")
    mesh = get_mesh(n_dev)
    X, y, w = datagen.make_rows(mesh, ROWS, COLS, 2**31 + 30, BLOBS, 256)
    if route == "stepwise":
        # a device that cannot hold its rows twice: 128 KB of them on eight
        # devices, 1 MB on one
        set_config(hbm_bytes=int(1.5 * ROWS * COLS * 4 / n_dev))
    model = adapter.build(PARAMS, n_dev).fit(DeviceDataset(mesh, X, ROWS, y=y, weight=w))
    reset_config()
    names = _span_names(model)
    assert [n for n in names if n.startswith("kmeans_route[")] == [f"kmeans_route[{route}]"]
    ref = adapter.reference(X, y, PARAMS)
    ans = adapter.answer(model)
    got = adapter.compare(ans, ref)
    assert ref["n_iter"] == PARAMS["maxIter"]
    assert got["iterations_off"] == 0 and got["centre_gap"] < 1e-6 and got["cost_gap"] < 1e-6, got
    assert model.summary.numIter == ref["n_iter"] and model.summary.k == K
    if route == "stepwise":
        # one span an iteration, and the rest of the vocabulary once
        assert names.count("kmeans_lloyd_iter") == ref["n_iter"]
        for once in ("kmeans_init", "kmeans_cost", "kmeans_fetch"):
            assert names.count(once) == 1, once
    # the control in the precision below is further off by orders of magnitude
    low = adapter.compare(adapter.reference(X, y, PARAMS, lowered=True), ref)
    assert low["centre_gap"] > 1e3 * max(got["centre_gap"], 1e-9), (low, got)


def test_an_empty_cluster_keeps_its_centre():
    X = jnp.asarray(np.random.default_rng(0).normal(size=(64, 4)).astype(np.float32))
    C = jnp.concatenate([X[:3], jnp.full((1, 4), 1e3, jnp.float32)])  # no row is near the last
    sums, counts, _ = km.lloyd_partials(C, X, jnp.ones(64, jnp.float32), 4)
    new = np.asarray(km._new_centers(C, sums, counts))
    assert float(counts[3]) == 0.0 and np.array_equal(new[3], np.full(4, 1e3, np.float32))
    assert not np.array_equal(new[:3], np.asarray(C[:3]))


# -- initMode="random" as a stated rule ----------------------------------------

def _rule(seed, m, k):
    """KMeans' docstring, with jax.random and numpy and nothing of the package."""
    g = np.asarray(jax.random.gumbel(jax.random.PRNGKey(seed), (m,), jnp.float32))
    return np.argsort(-g, kind="stable")[:k]


@pytest.mark.parametrize("layout", ["one_device", "four_devices", "padded_tail",
                                    "holes", "interleaved_over_4"])
def test_random_init_is_a_rule_of_seed_k_and_the_rows_of_positive_weight(layout):
    m, k, seed = 4000, 100, 2**31 - 5
    want = _rule(seed, m, k)
    assert len(set(want.tolist())) == k
    if layout in ("one_device", "four_devices"):
        n_dev = 4 if layout == "four_devices" else 1
        _needs(n_dev)
        w = jax.device_put(np.ones(m, np.float32), NamedSharding(get_mesh(n_dev), P("data")))
        got = np.asarray(km._random_init_rows(w, k, seed, 1))
    elif layout == "padded_tail":
        w = np.concatenate([np.ones(m), np.zeros(1120)]).astype(np.float32)
        got = np.asarray(km._random_init_rows(jnp.asarray(w), k, seed, 1))
    elif layout == "holes":
        # zero-weight rows anywhere: a row's rank counts the positive ones before it
        w = np.ones(m + 500, np.float32)
        holes = np.random.default_rng(1).choice(m + 500, 500, replace=False)
        w[holes] = 0.0
        got = np.asarray(km._random_init_rows(jnp.asarray(w), k, seed, 1))
        assert not np.isin(got, holes).any()
        got = np.searchsorted(np.flatnonzero(w), got)  # position -> rank
    else:
        # RowStager's round-robin deal of bucket-padded rows: dataset row r
        # lies at (r % 4) * (n / 4) + r // 4
        n = 4096
        w = (np.arange(n) < m).astype(np.float32).reshape(n // 4, 4).T.reshape(n)
        got = np.asarray(km._random_init_rows(jnp.asarray(w), k, seed, 4))
        got = (got % (n // 4)) * 4 + got // (n // 4)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("n_dev", [1, 4])
def test_random_initial_centres_are_those_rows_on_either_route(n_dev):
    """The gather of the fused route and the in-place row fetch of the
    stepwise one give the rule's rows, whatever the device count; host rows
    staged with bucket padding (dealt round-robin over the devices) too."""
    _needs(n_dev)
    rows, k, seed = 3000, 150, 11  # 150 rows: two programs of `take_rows`
    X = np.random.default_rng(5).normal(size=(rows, 6)).astype(np.float32)
    want = X[_rule(seed, rows, k)]
    pad = (-rows) % n_dev
    sharding = NamedSharding(get_mesh(n_dev), P("data", None))
    Xd = jax.device_put(np.concatenate([X, np.zeros((pad, 6), np.float32)]), sharding)
    wd = jax.device_put((np.arange(rows + pad) < rows).astype(np.float32),
                        NamedSharding(get_mesh(n_dev), P("data")))
    assert np.array_equal(np.asarray(km.kmeans_init(Xd, wd, k, seed, "random")), want)
    assert np.array_equal(
        np.asarray(km.take_rows(Xd, km._random_init_rows(wd, k, seed, 1))), want)
    # through the estimator, from host rows (3000 rows stage as 3072): after
    # one iteration every centre is the mean of the rows nearest the rule's rows
    model = KMeans(k=k, maxIter=1, tol=0.0, initMode="random", seed=seed,
                   num_workers=n_dev).fit(X)
    d2 = ((X[:, None, :].astype(np.float64) - want[None]) ** 2).sum(axis=2)
    label = d2.argmin(axis=1)
    means = np.stack([X[label == j].mean(axis=0) for j in range(k)])
    np.testing.assert_allclose(model.cluster_centers_, means, rtol=1e-5, atol=1e-5)


# -- the route and the block size read memory ----------------------------------

class _Resident:
    """Stands for device-resident rows of a shape the CPU cannot hold."""

    def __init__(self, rows, cols, device):
        self.shape, self.dtype, self.sharding = (rows, cols), np.dtype(np.float32), None
        shard = type("Shard", (), {})()
        shard.device = device
        shard.data = type("Data", (), {"nbytes": rows * cols * 4, "shape": (rows, cols)})()
        self.addressable_shards = [shard]


def test_the_gate_reads_memory_at_the_reference_row():
    from spark_rapids_ml_tpu.parallel.device_cache import bytes_beside, fused_program_fits

    v5e = 16_909_336_064  # what a v5e's allocator reports (PERF.md)
    set_config(hbm_bytes=v5e)
    X = _Resident(1_000_000, 3_000, jax.devices()[0])
    assert bytes_beside(X) == v5e - 12_000_000_000
    assert not fused_program_fits(X)  # 12 GB twice
    rows = km.lloyd_block_rows(X, 1000)
    assert rows == 62_500 and 1_000_000 % rows == 0
    # the block's temporaries, as counted, stay under half of what is left
    assert rows * km.lloyd_row_bytes(3000, 1000) <= bytes_beside(X) // 2
    # twice the memory: the rows fit twice, the (rows, k) temporaries beside them do not
    set_config(hbm_bytes=2 * v5e)
    assert fused_program_fits(X) and not fused_program_fits(X, 1_000_000 * 26_000)
    # a chip with little left beside its rows gets small blocks, never none
    set_config(hbm_bytes=12_000_000_000 + 40_000_000)
    assert 1 <= km.lloyd_block_rows(X, 1000) <= 40_000_000 // 2 // km.lloyd_row_bytes(3000, 1000) + 1
    set_config(hbm_bytes=11_000_000_000)
    assert km.lloyd_block_rows(X, 1000) == 1


@pytest.mark.parametrize("budget,route", [(None, "fused"), (400_000, "stepwise")])
def test_the_gate_picks_the_route_by_the_device_budget(budget, route):
    X = np.random.default_rng(2).normal(size=(4096, 16)).astype(np.float32)  # 256 KB
    if budget:
        set_config(hbm_bytes=budget)
    Xd, wd = jnp.asarray(X), jnp.ones(4096, jnp.float32)
    from spark_rapids_ml_tpu import tracing

    tracing.reset_trace()
    *_, stepwise = km.kmeans_fit_auto(Xd, wd, k=8, seed=0, max_iter=3, init="random")
    routes = [e for e in tracing.get_trace_events() if e.name.startswith("kmeans_route[")]
    assert [e.name for e in routes] == [f"kmeans_route[{route}]"] and stepwise == (route == "stepwise")
    if route == "stepwise":
        block = int(re.search(r"block_rows=(\d+)", routes[0].detail).group(1))
        assert block == km.lloyd_block_rows(Xd, 8) and 1 <= block < 4096
        assert block * km.lloyd_row_bytes(16, 8) <= (budget - X.nbytes) // 2


# -- the step's program: the stated precision, the scopes, the names -----------

def _lowered_programs():
    X, w = jnp.zeros((512, 8), jnp.float32), jnp.ones(512, jnp.float32)
    C, at = jnp.zeros((5, 8), jnp.float32), jnp.asarray(0, jnp.int32)
    acc = (jnp.zeros((5, 8)), jnp.zeros(5), jnp.zeros(()))
    mesh = get_mesh(2)
    step1, cost1 = km._block_programs(None, 128, 5)
    step2, cost2 = km._block_programs(mesh, 128, 5)
    Xs = jax.device_put(X, NamedSharding(mesh, P("data", None)))
    ws = jax.device_put(w, NamedSharding(mesh, P("data")))
    acc2 = jax.tree.map(lambda a: jnp.zeros((2,) + a.shape), acc)
    return {
        "block_step": step1.lower(acc, C, X, w, at),
        "block_cost": cost1.lower(acc[2], C, X, w, at),
        "sharded_step": step2.lower(acc2, C, Xs, ws, at),
        "sharded_cost": cost2.lower(acc2[2], C, Xs, ws, at),
        "center_update": km._lloyd_center_update.lower(C, acc[0], acc[1]),
        "fused": km.kmeans_fit.lower(X, w, k=5, seed=0, max_iter=3, init="random"),
        "predict": km.kmeans_predict.lower(X, C),
    }


def test_the_step_computes_at_the_stated_precision_under_its_scopes():
    _needs(2)
    lowered = _lowered_programs()
    # (f32 products at `highest`, exact one-pass bf16 products, scopes) of each
    # program: the assignment is one `highest` product, the update three
    # products of the exact bf16 one-hot with the rows' three bf16 parts
    expect = {
        "block_step": (1, 3, {"kmeans_assign", "kmeans_update"}),
        "sharded_step": (1, 3, {"kmeans_assign", "kmeans_update"}),
        "block_cost": (1, 0, {"kmeans_assign"}),
        "sharded_cost": (1, 0, {"kmeans_assign"}),
        "fused": (2, 3, {"kmeans_assign", "kmeans_update"}),  # the loop's, and the cost's
        "predict": (1, 0, {"kmeans_assign"}),
    }
    for name, (highest, split, scopes) in expect.items():
        text = lowered[name].as_text(debug_info=True)
        dots = re.findall(r"stablehlo\.dot_general.*", text)
        f32 = [d for d in dots if "precision = [HIGHEST, HIGHEST]" in d and "bf16" not in d]
        bf16 = [d for d in dots if re.search(r"\(tensor<\S+xbf16>, tensor<\S+xbf16>\) -> tensor<\S+xf32>", d)]
        assert (len(f32), len(bf16), len(dots)) == (highest, split, highest + split), (name, dots)
        located = set(re.findall(r'loc\("([^"]+)"', text))
        for scope in scopes:
            assert any(re.search(rf"(^|[/(]){scope}([/)]|$)", loc) for loc in located), (name, scope)
    # the conf key that states the precision is read at trace time
    set_config(distance_precision="default")
    X, w = jnp.zeros((64, 8), jnp.float32), jnp.ones(64, jnp.float32)
    C, at = jnp.zeros((5, 8), jnp.float32), jnp.asarray(0, jnp.int32)
    acc = (jnp.zeros((5, 8)), jnp.zeros(5), jnp.zeros(()))
    text = km._block_programs(None, 32, 5)[0].lower(acc, C, X, w, at).as_text()
    assert text.count("stablehlo.dot_general") == 2 and "HIGHEST" not in text and "bf16" not in text


def test_the_three_pass_update_is_exact_to_float32():
    """The update's one-hot product against float64, weights and all: as
    close as float32 sums get, where one bf16 pass is 4,000 times further."""
    rng = np.random.default_rng(0)
    X = (10.0 * rng.normal(size=(5000, 40)) + rng.uniform(-10, 10, size=(1, 40))).astype(np.float32)
    w = rng.uniform(0.1, 2.0, 5000).astype(np.float32)
    w[::7] = 0.0
    label = rng.integers(0, 13, 5000)
    onehot = np.eye(13)[label] * w[:, None].astype(np.float64)
    want, counts = onehot.T @ X.astype(np.float64), onehot.sum(axis=0)
    sums, got_counts = km._cluster_sums(jnp.asarray(label), jnp.asarray(X), jnp.asarray(w), 13)
    assert np.abs(np.asarray(sums) - want).max() / np.abs(want).max() < 3e-7
    np.testing.assert_allclose(np.asarray(got_counts), counts, rtol=3e-6)
    one_pass = jnp.matmul(jnp.asarray(onehot.T, jnp.bfloat16), jnp.asarray(X, jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    assert np.abs(np.asarray(one_pass) - want).max() / np.abs(want).max() > 1e-4


def test_the_benchmark_finds_the_steps_programs_by_name():
    _needs(2)
    modules = {name: re.search(r"module @(\S+)", low.as_text()).group(1)
               for name, low in _lowered_programs().items()}
    assert modules["block_step"] == modules["sharded_step"] == "jit__lloyd_block_step"
    assert modules["block_cost"] == modules["sharded_cost"] == "jit__lloyd_block_cost"
    programs = mf.adapter("kmeans").PROGRAMS
    for pattern in programs["lloyd_step"]:
        assert any(pattern in m for m in modules.values()), pattern
    # an iteration waits for the block steps and the update, not for the cost pass
    assert not any(p in modules["block_cost"] for p in programs["lloyd_iter"])
    assert not any(p in modules["predict"] for p in programs["lloyd_step"])


def test_kmeans_work_by_hand():
    from chipbench import roofline

    w = mf.adapter("kmeans").work(1_000_000, 3_000, 1, {"k": 1000, "maxIter": 5})
    step, assign = w["kernels"]["lloyd_step"], w["kernels"]["lloyd_assign"]
    assert assign == {"flops": 6e12, "bytes": 1.2e10}
    assert step == {"flops": 6e12 + 3e9, "bytes": 1.2e10}
    v5e = roofline.peaks_for("TPU v5 lite")
    seconds, bound = roofline.least_seconds(step, v5e)
    assert bound == "flops" and seconds == pytest.approx(6.003e12 / 197e12)  # 30.5 ms
    assert roofline.fit_least_seconds(w, v5e) == pytest.approx((5 * 6.003e12 + 6e12) / 197e12)
