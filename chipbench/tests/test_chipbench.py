#
# The benchmark's own tests: CPU, toy shapes.
#   python -m pytest chipbench/tests -q -p no:cacheprovider
# Parametrised over what BENCHMARK.json and chipbench/ hold, so a cell, a
# configuration or a metric that a later PR adds is covered without an edit.
#
import copy
import dataclasses
import json
import os

import numpy as np
import pytest

from chipbench import manifest as mf
from chipbench import blocks, datagen, roofline, run, trace_reduce

MANIFEST = mf.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
CONFIGS = [c["name"] for c in MANIFEST["configs"]]
TOY = dict(rows=4096, cols=48)
TOY_BLOCK_ROWS = 256  # several blocks to a shard at the toy size too
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
HIDDEN = {k: {"model": "hidden_direction", "labels": k} for k in ("sign", "linear")}


# -- (a) the manifest ----------------------------------------------------------

def test_manifest_meets_the_contract():
    assert mf.problems(MANIFEST) == []


def test_manifest_check_catches_a_layer_metric_without_cells():
    broken = copy.deepcopy(MANIFEST)
    del broken["per_layer"][0]["workloads"]
    assert any("lists no cells" in p for p in mf.problems(broken))


def test_manifest_check_catches_what_refused_pr22():
    # a per-layer metric reported on a cell where the metric it moves is not
    broken = copy.deepcopy(MANIFEST)
    broken["end_to_end"].append({
        "name": "transform_rows_per_s", "unit": "rows/s", "better": "higher",
        "bound": 0.03, "source": "host_clock", "workloads": []})
    broken["per_layer"][0]["moves"] = "transform_rows_per_s"
    found = mf.problems(broken)
    assert any("which it should move, is not" in p for p in found), found


@pytest.mark.parametrize("bad", [
    {"unit": "tokens per second"}, {"unit": "x" * 17}, {"name": "has space"},
    {"name": "a/b"}, {"better": "more"}, {"source": "guess"},
    {"workloads": ["no_such_cell"]},
])
def test_manifest_check_catches_names_and_units(bad):
    broken = copy.deepcopy(MANIFEST)
    broken["per_layer"][-1].update(bad)
    assert mf.problems(broken)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files_by_name(cell):
    c = mf.cell(MANIFEST, cell)
    adapter = mf.adapter(c["config_file"]["adapter"])
    for need in ("build", "answer", "work", "reference", "compare", "PROGRAMS"):
        assert hasattr(adapter, need), need
    # the rows it fits: the file's own `data` block, or its family's labels
    assert mf.data_problems(mf.data_of(c["config_file"], adapter)) == []
    assert c["traffic_file"]["input"] in ("device_dataset", "host_arrays")
    assert set(c["config_file"]["limits"]), "a cell compares something"
    for m in mf.metrics_of(MANIFEST, "per_layer", cell):
        assert callable(mf.reader(m["name"]))


# -- (b) each cell's whole run at a toy size -----------------------------------

@pytest.fixture
def toy(monkeypatch):
    """The harness as the driver calls it, with the TPU requirement lifted
    and the configurations shrunk, by this test and by nothing else."""
    real = mf.cell

    def small(manifest, workload):
        c = real(manifest, workload)
        c["config_file"].update(TOY)
        return c

    monkeypatch.setattr(mf, "cell", small)
    monkeypatch.setattr(blocks, "BLOCK_ROWS", TOY_BLOCK_ROWS)
    monkeypatch.setattr(datagen, "BLOCK_ROWS", TOY_BLOCK_ROWS)
    monkeypatch.setattr(run, "require_chips", lambda devices, chips: list(devices[:chips]))


def _last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _needs(chips):
    import jax

    if len(jax.devices()) < chips:
        pytest.skip(f"needs {chips} devices")


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_on_the_cpu(cell, trace, toy, capsys):
    _needs(mf.cell(MANIFEST, cell)["chips"])
    seed = 2**31 + 12345  # the driver's seeds are large
    assert run.main(["--workload", cell, "--seed", str(seed),
                     "--seconds", "0.3", "--trace", str(trace)]) == 0
    out = _last_line(capsys)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert out["device"]["platform"] == "cpu"
    # a CPU run's numbers are marked, and none goes under a device metric's name
    known = {m["name"] for g in ("end_to_end", "per_layer") for m in MANIFEST[g]}
    assert out["metrics"], "the run reports something"
    for name, m in out["metrics"].items():
        assert name.startswith("cpu_rehearsal.") and name not in known
        assert name.split(".", 1)[1] in known and m["unit"]
    assert "busy_s" not in out["device"], "no device trace on the CPU"
    for value, limit in out["checks"].values():
        assert value <= limit


def test_no_tpu_means_no_result(monkeypatch, capsys):
    import jax

    with pytest.raises(SystemExit) as e:
        run.require_chips(jax.devices(), 1)
    assert e.value.code not in (0, None)
    with pytest.raises(SystemExit):
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "0.1"])
    assert capsys.readouterr().out == ""


def test_same_seed_same_rows():
    import jax

    from spark_rapids_ml_tpu.parallel import get_mesh

    mesh = get_mesh(1)
    a = datagen.make_rows(mesh, 1024, 16, 2**31 + 7, HIDDEN["sign"], 100)
    b = datagen.make_rows(mesh, 1024, 16, 2**31 + 7, HIDDEN["sign"], 100)
    c = datagen.make_rows(mesh, 1024, 16, 2**31 + 8, HIDDEN["sign"], 100)
    assert all(np.array_equal(x, y) for x, y in zip(jax.device_get(a), jax.device_get(b)))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))
    assert len(np.unique(np.asarray(a[0])[:, 0])) > 1000  # no block drawn twice


# -- the data models: chipbench/data_models/<name>.py ---------------------------

BLOBS = {"model": "blobs", "centers": 10, "cluster_std": 0.7, "center_box": [-6.0, 6.0]}
GOLDEN_SEED = 2**31 + 7
# sha256 (first 16 hex digits) of the bytes of X and of y, as
# `datagen.make_rows(get_mesh(n), 2048, 24, GOLDEN_SEED, labels, 128)` and
# `datagen.host_rows(2000, 24, GOLDEN_SEED, labels, workers=3)` at BLOCK_ROWS
# 256 gave them at commit fd5469e, before the hidden direction moved to a
# file of its own (jax 0.9.0, the CPU backend): the same seed still draws
# the same bits, so no cell's rows, readings or limits moved with the code.
GOLDEN = {
    ("device", 1, "sign"): ("789bc5f290e9a4bb", "cdfb4997d7a603ad"),
    ("device", 1, "linear"): ("789bc5f290e9a4bb", "11ecbbda1dd22b1c"),
    ("device", 4, "sign"): ("424da47d016e0559", "210568a403403e48"),
    ("device", 4, "linear"): ("424da47d016e0559", "48f4926e3fee16f0"),
    ("device", 8, "sign"): ("0f0e9825048449b9", "4104d7729ef906f1"),
    ("device", 8, "linear"): ("0f0e9825048449b9", "7eab148f787992c5"),
    ("host", 0, "sign"): ("f40b2e8676004a3c", "1271386d56eda7cf"),
    ("host", 0, "linear"): ("f40b2e8676004a3c", "cb0c102b7b56dbd2"),
}


def _digest(a) -> str:
    import hashlib

    return hashlib.sha256(np.ascontiguousarray(np.asarray(a)).tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("path,n_dev,labels", sorted(GOLDEN))
def test_hidden_direction_rows_are_the_parents_bits(path, n_dev, labels, monkeypatch):
    from spark_rapids_ml_tpu.parallel import get_mesh

    if path == "device":
        _needs(n_dev)
        X, y, w = datagen.make_rows(get_mesh(n_dev), 2048, 24, GOLDEN_SEED, HIDDEN[labels], 128)
        assert y.dtype == np.float32 and np.array_equal(np.asarray(w), np.ones(2048, np.float32))
    else:
        monkeypatch.setattr(datagen, "BLOCK_ROWS", 256)  # a last block of 208 rows
        X, y = datagen.host_rows(2000, 24, GOLDEN_SEED, HIDDEN[labels], workers=3)
        assert X.flags.c_contiguous and X.dtype == np.float32 and y.dtype == np.float64
    assert (_digest(X), _digest(y)) == GOLDEN[path, n_dev, labels]


def test_a_file_without_a_data_block_draws_the_hidden_direction():
    for name in CONFIGS:
        c = mf.cell(MANIFEST, next(w["name"] for w in MANIFEST["workloads"] if w["config"] == name))
        cfg = c["config_file"]
        adapter = mf.adapter(cfg["adapter"])
        if "data" not in cfg:
            assert mf.data_of(cfg, adapter) == HIDDEN[adapter.LABELS]
    assert mf.data_of({"data": BLOBS}, None) is BLOBS  # its own block, and no LABELS asked for


def _draw(path: str, rows: int, cols: int, seed: int, data: dict):
    """(X, y, the centres or the direction drawn once) on 'device-<n>' or 'host'."""
    import jax

    from spark_rapids_ml_tpu.parallel import get_mesh

    model = mf.data_model(data["model"])
    if path == "host":
        X, y = datagen.host_rows(rows, cols, seed, data, workers=3)
        return X, y, model.host_shared(np.random.default_rng([seed, 0]), cols, data)
    n_dev = int(path.split("-")[1])
    _needs(n_dev)
    X, y, w = datagen.make_rows(get_mesh(n_dev), rows, cols, seed, data, 500)
    assert len(X.sharding.device_set) == n_dev and X.shape == (rows, cols)
    assert X.dtype == y.dtype == w.dtype == np.float32 and float(w.min()) == float(w.max()) == 1.0
    once = model.shared(jax.random.fold_in(datagen._key(seed), 0), cols, data)
    return np.asarray(X), np.asarray(y), np.asarray(once)


@pytest.mark.parametrize("path", ["device-1", "device-4", "host"])
def test_blobs_sit_on_their_centres(path, monkeypatch):
    """sklearn's make_blobs as a distribution: centres uniform in the box,
    a row's centre drawn uniformly, cluster_std x N(0, I) around it; the
    numpy twin is another stream of the same distribution."""
    monkeypatch.setattr(datagen, "BLOCK_ROWS", 500)
    rows, cols = 20_000, 16
    k, std, (lo, hi) = BLOBS["centers"], BLOBS["cluster_std"], BLOBS["center_box"]
    X, y, centres = _draw(path, rows, cols, 2**31 + 21, BLOBS)
    which = y.astype(np.int64)
    assert np.array_equal(which, y) and which.min() == 0 and which.max() == k - 1
    assert centres.shape == (k, cols) and lo <= centres.min() and centres.max() <= hi
    assert centres.max() - centres.min() > 0.9 * (hi - lo)
    counts = np.bincount(which, minlength=k)
    # multinomial: 2000 a centre, standard deviation 42
    assert np.abs(counts - rows / k).max() < 4 * np.sqrt(rows / k)
    resid = X.astype(np.float64) - centres[which]
    for c in range(k):
        mine = resid[which == c]
        assert np.abs(mine.mean(axis=0)).max() < 4 * std / np.sqrt(len(mine)), c
        assert abs(mine.std() / std - 1.0) < 0.02, c
    # normal, not merely of that mean and spread: the share beyond two sigmas
    assert abs((np.abs(resid) > 2 * std).mean() - 0.0455) < 0.002
    assert len(np.unique(X[:, 0])) > 0.99 * rows  # no block drawn twice


def test_blobs_same_seed_same_rows_and_defaults_are_sklearns():
    a = _draw("device-1", 2000, 8, 2**31 + 5, {"model": "blobs", "centers": 4})
    b = _draw("device-1", 2000, 8, 2**31 + 5, {"model": "blobs", "centers": 4})
    c = _draw("device-1", 2000, 8, 2**31 + 6, {"model": "blobs", "centers": 4})
    explicit = _draw("device-1", 2000, 8, 2**31 + 5, {
        "model": "blobs", "centers": 4, "cluster_std": 1.0, "center_box": [-10.0, 10.0]})
    assert all(np.array_equal(p, q) for p, q in zip(a, b))
    assert all(np.array_equal(p, q) for p, q in zip(a, explicit))
    assert not np.array_equal(a[0], c[0]) and not np.array_equal(a[2], c[2])
    h1 = datagen.host_rows(2000, 8, 2**31 + 5, {"model": "blobs", "centers": 4})
    h2 = datagen.host_rows(2000, 8, 2**31 + 5, {"model": "blobs", "centers": 4}, workers=1)
    assert all(np.array_equal(p, q) for p, q in zip(h1, h2))  # whatever the threads
    with pytest.raises(ValueError, match="do not divide"):
        _draw("device-4", 2001, 8, 1, {"model": "blobs", "centers": 4})


BAD_DATA = [
    ({"model": "no_such_model"}, "has no file"),
    ({"labels": "sign"}, "has no file"),
    ({"model": "blobs"}, "lacks ['centers']"),
    ({"model": "blobs", "centers": 0}, "centers 0"),
    ({"model": "blobs", "centers": 2.5}, "centers 2.5"),
    ({"model": "blobs", "centers": 8, "cluster_std": -1.0}, "cluster_std -1.0"),
    ({"model": "blobs", "centers": 8, "center_box": [3.0, 3.0]}, "center_box"),
    ({"model": "blobs", "centers": 8, "center_box": "wide"}, "unreadable"),
    ({"model": "hidden_direction"}, "lacks ['labels']"),
    ({"model": "hidden_direction", "labels": "maybe"}, "'sign' or 'linear'"),
]


@pytest.mark.parametrize("data,says", BAD_DATA)
def test_a_data_block_that_cannot_be_drawn_is_a_problem_not_a_traceback(data, says, monkeypatch):
    assert any(says in p for p in mf.data_problems(data)), mf.data_problems(data)
    # a run stops on the same line, before it touches a device
    with pytest.raises(ValueError, match="data model"):
        datagen.make_rows(None, 1024, 8, 1, data)
    with pytest.raises(ValueError, match="data model"):
        datagen.host_rows(1024, 8, 1, data)
    # and the manifest's check names the configuration whose file holds it
    real = mf._load_json
    monkeypatch.setattr(mf, "_load_json", lambda path: {
        **real(path), **({"data": data} if path.endswith(f"{CONFIGS[0]}.json") else {})})
    found = mf.problems(MANIFEST)
    assert len(found) == 1 and found[0].startswith(f"configuration {CONFIGS[0]}: data model")
    assert says in found[0]


def test_a_new_data_model_is_one_new_file(tmp_path, monkeypatch):
    """No edit to datagen.py, run.py, control.py or manifest.py: the model's
    file, found by the name in a configuration's `data` block, is enough."""
    (tmp_path / "data_models").mkdir()
    (tmp_path / "data_models" / "ramp.py").write_text(
        "import numpy as np\n"
        "NEEDS = ('step',)\n"
        "def check(data):\n"
        "    if data['step'] <= 0: raise ValueError('step must be positive')\n"
        "def shared(key, cols, data):\n"
        "    import jax.numpy as jnp\n"
        "    return data['step'] * jnp.arange(cols, dtype=jnp.float32)\n"
        "def block(key, ramp, rows, cols, data):\n"
        "    import jax, jax.numpy as jnp\n"
        "    u = jax.random.uniform(key, (rows,), jnp.float32)\n"
        "    return u[:, None] + ramp[None, :], u\n"
        "def host_shared(rng, cols, data):\n"
        "    return data['step'] * np.arange(cols, dtype=np.float32)\n"
        "def host_block(rng, ramp, xb, data):\n"
        "    u = rng.random(len(xb), dtype=np.float32)\n"
        "    xb[...] = u[:, None] + ramp[None, :]\n"
        "    return u\n")
    monkeypatch.setattr(mf, "BENCH", str(tmp_path))
    monkeypatch.setattr(datagen, "BLOCK_ROWS", 256)
    data = {"model": "ramp", "step": 2.0}
    assert mf.data_problems(data) == []
    assert any("step must be positive" in p for p in mf.data_problems({"model": "ramp", "step": 0}))
    for path in ("device-4", "host"):
        X, y, ramp = _draw(path, 2000, 6, 17, data)
        assert np.array_equal(ramp, [0, 2, 4, 6, 8, 10])
        assert np.allclose(X - y[:, None], ramp[None, :], atol=1e-6)
        assert len(np.unique(y)) > 1900


# -- why `blobs`: a Lloyd trajectory that a precision can be held to -------------

def _lloyd(X, C0, iters: int, products: str):
    """Plain Lloyd from the centres C0, assignments after `iters` updates:
    'float64', 'float32', or 'bfloat16' (float32 with the x . c products of
    operands rounded to bfloat16, one pass of the MXU)."""
    import ml_dtypes

    dt = np.float64 if products == "float64" else np.float32

    def rounded(a):
        return a.astype(ml_dtypes.bfloat16).astype(np.float32) if products == "bfloat16" else a

    X, C = X.astype(dt), C0.astype(dt)
    x2, Xq = (X * X).sum(axis=1), rounded(X)
    for _ in range(iters):
        d = x2[:, None] - 2 * (Xq @ rounded(C).T) + (C * C).sum(axis=1)[None, :]
        a = d.argmin(axis=1)
        for j in np.unique(a):
            C[j] = X[a == j].mean(axis=0)
    return a


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_on_blobs_a_lloyd_trajectory_separates_float32_from_bfloat16(seed):
    """The model exists so that a clustering cell can decide `correct`: on
    blobs ten Lloyd iterations in float32 end on float64's assignments and
    with bfloat16 products they do not, so a limit on the rows assigned
    otherwise stands between the stated precision and the one below it.
    (On iid-normal rows, the first cells' model, the trajectory is chaotic
    and nothing separates them: PERF.md §4.)"""
    # share of the 20,000 rows assigned otherwise than float64 (seeds 1-10,
    # read when the model was added): float32 0 to 1.7e-3, bfloat16 1.67e-2
    # to 7.9e-2, a factor of ten between the two sides' nearest readings and
    # of 27 or more within a seed.  The limit stands between them with a
    # factor of two of room on either side.
    limit, room = 5e-3, 2.0
    k = 50
    X, _ = datagen.host_rows(20_000, 100, seed, {"model": "blobs", "centers": k})
    C0 = X[np.random.default_rng([seed, 99]).choice(len(X), k, replace=False)]
    ref = _lloyd(X, C0, 10, "float64")
    f32 = float((_lloyd(X, C0, 10, "float32") != ref).mean())
    bf16 = float((_lloyd(X, C0, 10, "bfloat16") != ref).mean())
    assert f32 <= limit / room < limit * room <= bf16, (f32, bf16)


# -- the control and the faults: `correct` has been shown to fail --------------

def _readings(cell, monkeypatch, seed=11):
    import jax

    from chipbench import control

    c = mf.cell(MANIFEST, cell)
    c["config_file"].update(TOY)
    monkeypatch.setattr(blocks, "BLOCK_ROWS", TOY_BLOCK_ROWS)
    monkeypatch.setattr(datagen, "BLOCK_ROWS", TOY_BLOCK_ROWS)
    _needs(c["chips"])
    return control.readings(c, seed, jax.devices()[:c["chips"]], program=True)


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_the_precision_below_comes_out_not_correct(cell, monkeypatch):
    r = _readings(cell, monkeypatch)
    assert all(r["program"][k] <= r["limits"][k] for k in r["limits"]), r
    assert any(r["control"][k] > r["limits"][k] for k in r["limits"]), r


def _broken(fault: str):
    """An estimator whose fit kernel is broken underneath the timed path."""
    real = mf.adapter

    def adapter(name):
        a = real(name)
        build = a.build

        def build_broken(params, chips):
            est = build(params, chips)
            kernel = est._fit_array

            def fit_array(fit_input):
                import jax.numpy as jnp

                n = fit_input.w.shape[0]
                keep = {"half_the_batch": n // 2, "no_exchange": n // max(chips, 2)}.get(fault)
                if keep:  # the rest of the rows never reach the sums
                    fit_input = dataclasses.replace(
                        fit_input, w=fit_input.w * (jnp.arange(n) < keep))
                attrs = kernel(fit_input)
                coef = np.array(attrs["coef_"], copy=True)
                if fault == "state_unchanged":
                    coef[...] = 0.0
                elif fault == "answer_altered":
                    coef.reshape(-1)[0] *= 1.01
                attrs["coef_"] = coef
                return attrs

            est._fit_array = fit_array
            return est

        a.build = build_broken
        return a

    return adapter


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch",
                                   "no_exchange", "answer_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_comes_out_not_correct(cell, fault, toy, monkeypatch, capsys):
    chips = mf.cell(MANIFEST, cell)["chips"]
    _needs(chips)
    if fault == "no_exchange" and chips == 1:
        pytest.skip("one chip exchanges nothing")
    monkeypatch.setattr(mf, "adapter", _broken(fault))
    assert run.main(["--workload", cell, "--seed", "5", "--seconds", "0.1"]) == 0
    out = _last_line(capsys)
    assert out["correct"] is False, out["checks"]


# -- (c) the trace reduction on a recorded trace --------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_ridge_v5e.json")) as f:
        return json.load(f)["events"]


def test_trace_reduce_on_the_recorded_trace(recorded):
    mark = trace_reduce.sync_start(recorded)
    assert mark == pytest.approx(0.063294, abs=1e-6)
    window = (mark, mark + 9.892567)
    s = trace_reduce.reduce(recorded, window)
    assert s["chips"] == 1
    # five fits: a 0.5959 s Gram fusion, two 0.0159 s passes and crumbs each
    assert s["busy_s"] == pytest.approx(5 * (0.595916 + 0.015886 + 0.015857), abs=2e-3)
    seconds, runs = trace_reduce.program_seconds(s, ("linreg_sufficient_stats",))
    assert runs == 5 and seconds == pytest.approx(5 * 0.61182, abs=1e-4)
    assert trace_reduce.program_seconds(s, ("no_such_program",)) == (0.0, 0)
    top = trace_reduce.top_ops(s)
    assert top[0][0] == "fusion f32[3000,3000]" and top[0][1] == pytest.approx(2.97958, abs=1e-4)
    assert s["collective_s"] == 0.0
    # the gaps are what the busy intervals leave of the window
    assert trace_reduce.total(s["gaps"]) == pytest.approx(s["window_s"] - s["busy_s"], abs=1e-9)
    longest = max(hi - lo for lo, hi in s["gaps"])
    assert longest == pytest.approx(2.041905 - (0.080489 + 0.595916), abs=1e-4)
    spans = [("fit_kernel", 0.06, 2.04), ("fit[LinearRegression]", 0.05, 2.05)]
    named = dict(trace_reduce.attribute_gaps(s["gaps"], spans, [1, -1]))
    assert named["fit_kernel"] == pytest.approx(longest, abs=2e-3)
    assert "between_fits" in named
    assert sum(named.values()) == pytest.approx(trace_reduce.total(s["gaps"]), abs=1e-9)
    # nothing ran on a device outside the events' span: nothing to read
    assert trace_reduce.reduce(recorded, (100.0, 101.0)) is None


def test_interval_arithmetic():
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert trace_reduce.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert trace_reduce.clip([(0, 3), (5, 8)], (2, 6)) == [(2, 3), (5, 6)]
    events = [
        {"plane": "/device:TPU:0", "line": "XLA Ops", "name": "%all-reduce.1 = f32[8]{0} all-reduce(x)", "start": 0.0, "dur": 2.0},
        {"plane": "/device:TPU:0", "line": "XLA Ops", "name": "%fusion.2 = f32[8]{0} fusion(x)", "start": 1.0, "dur": 2.0},
    ]
    s = trace_reduce.reduce(events, (0.0, 4.0))
    assert (s["busy_s"], s["collective_s"], s["collective_exposed_s"]) == (3.0, 2.0, 1.0)
    # a `while` that holds both is no work of its own: the same reading, not
    # a window that is busy from end to end with no collective exposed
    loop = {"plane": "/device:TPU:0", "line": "XLA Ops", "start": 0.0, "dur": 4.0,
            "name": "%while.3 = (f32[8]{0}, s32[]) while(x), condition=%c, body=%b"}
    nested = trace_reduce.reduce(events + [loop], (0.0, 4.0))
    assert (nested["busy_s"], nested["collective_exposed_s"]) == (3.0, 1.0)
    assert trace_reduce.total(nested["gaps"]) == 1.0


def test_trace_reduce_sees_inside_the_fused_while():
    """The four-chip cell's solve is one `while` program: the all-reduce of
    every evaluation and the gaps between leaf operations lie inside it."""
    with open(os.path.join(DATA, "trace_logreg_4chip_v5e.json")) as f:
        held = json.load(f)
    events, t0 = held["events"], held["t0"]
    loops = [e for e in events if e["name"].startswith("%while.123 ")]
    assert len(loops) == 2 and all(e["dur"] > 0.3 for e in loops)  # one a chip
    s = trace_reduce.reduce(events, (t0, t0 + 0.0525))
    assert s["chips"] == 2
    # chip 0: all-reduces of 4.742 and 4.623 us before the loop and 3.386 us
    # inside it; chip 1: 4.125, 3.824 and 10.765 us.  None overlaps a leaf
    # operation (each waits for the reduction it sums), so all of it is exposed
    assert s["collective_s"] == pytest.approx(31.465e-6 / 2, abs=1e-9)
    assert s["collective_exposed_s"] == pytest.approx(s["collective_s"], abs=1e-12)
    # leaf operations: two 7.93 ms passes, the 18.8 ms copy, two 8.13 ms
    # passes in the loop, 1.3 ms of the next; the loop leaves 0.12 ms of gaps
    assert s["busy_s"] == pytest.approx(0.0523771, abs=2e-6)
    assert 250 < len(s["gaps"]) < 320
    assert trace_reduce.total(s["gaps"]) == pytest.approx(1.2195e-4, abs=2e-6)
    top = trace_reduce.top_ops(s)
    assert top[0][0] == "copy f32[500000,3000]" and not any(n.startswith("while") for n, _ in top)
    # some 290 gaps split over the spans they overlap: `fit_kernel` has what
    # lies inside it (the gap it starts in, cut there), its parent the rest,
    # and the sum is the idle time, as it was when whole gaps went by their middle
    named = dict(trace_reduce.attribute_gaps(
        s["gaps"], [("fit_kernel", t0 + 0.03, t0 + 0.06), ("fit[x]", t0 - 1.0, t0 + 1.0)], [1, -1]))
    inside = trace_reduce.total(trace_reduce.clip(s["gaps"], (t0 + 0.03, t0 + 0.06)))
    assert 0 < inside < trace_reduce.total(s["gaps"])
    assert named["fit_kernel"] == pytest.approx(inside, abs=1e-12)
    assert set(named) == {"fit_kernel", "fit[x]"}
    assert sum(named.values()) == pytest.approx(trace_reduce.total(s["gaps"]), abs=1e-12)
    assert trace_reduce.attribute_gaps([], [], []) == []


def test_a_gap_longer_than_its_spans_is_split_over_them():
    """The `ingest` cell's staging (PERF.md §5): the device idles through a
    whole `stage` while a prefetch thread prepares pieces and the caller puts
    them.  The gap's middle lies in a short `stage_put`, which got all 10 s."""
    spans = [("stage", 0, 10), ("stage_prep", 0, 4), ("stage_prep", 4, 8),
             ("stage_put", 1, 3), ("stage_put", 4.5, 5.5), ("event", 2, 2)]
    parents = [-1, 0, 0, 0, 0, 3]
    named = dict(trace_reduce.attribute_gaps([(0, 10), (12, 13)], spans, parents))
    # prep alone 0-1, 3-4.5, 5.5-8 (5 s) and half of what it shares with put
    # (1-3, 4.5-5.5); 8-10 is `stage` with no child; 12-13 lies in no span
    assert named == pytest.approx(
        {"stage_prep": 6.5, "stage_put": 1.5, "stage": 2.0, "between_fits": 1.0})
    # busy time between the gaps takes nothing, a span outside them gets nothing
    named = dict(trace_reduce.attribute_gaps([(0, 1), (9, 10)], spans, parents))
    assert named == pytest.approx({"stage_prep": 1.0, "stage": 1.0})
    assert trace_reduce.attribute_gaps([(0, 10)], [], []) == [["between_fits", 10.0]]
    top = trace_reduce.attribute_gaps([(0, 10), (12, 13)], spans, parents, k=2)
    assert [n for n, _ in top] == ["stage_prep", "stage"]


# -- (d) operation and byte counts, by hand, at the published shapes -----------

V5E = roofline.peaks_for("TPU v5 lite")


def test_peaks_table():
    assert (V5E["flops_per_s"], V5E["bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v9")


def test_logreg_work_by_hand():
    w = mf.adapter("logreg").work(1_000_000, 3_000, 1, {"maxIter": 20})
    ev = w["kernels"]["lbfgs_eval"]
    assert ev == {"flops": 1.2e10, "bytes": 1.2e10}  # 4 n d FLOP; n d 4 bytes
    seconds, bound = roofline.least_seconds(ev, V5E)
    assert bound == "bytes" and seconds == pytest.approx(1.2e10 / 819e9)
    assert roofline.fit_least_seconds(w, V5E) == pytest.approx(21 * 1.2e10 / 819e9)
    four = mf.adapter("logreg").work(2_000_000, 3_000, 4, {"maxIter": 20})
    assert four["kernels"]["lbfgs_eval"]["bytes"] == 6e9


def test_ridge_work_by_hand():
    w = mf.adapter("ridge").work(1_000_000, 3_000, 1, {})
    gram = w["kernels"]["gram"]
    assert gram["flops"] == 2 * 1e6 * 3000 * 3000 + 2 * 1e6 * 3000 and gram["bytes"] == 1.2e10
    seconds, bound = roofline.least_seconds(gram, V5E)
    assert bound == "flops" and seconds == pytest.approx(1.8006e13 / 197e12)
    assert roofline.fit_least_seconds(w, V5E) == pytest.approx(
        1.8006e13 / 197e12 + 1.2e10 / 819e9)


@pytest.mark.parametrize("config", CONFIGS)
def test_config_file_states_its_cut(config):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == config)
    with open(os.path.join(mf.ROOT, entry["file"])) as f:
        held = json.load(f)
    assert held["name"] == config and held["source"]
    # every key that differs from the source is in `reduced`, and no other
    as_run = {"rows": held["rows"], "cols": held["cols"], **held["params"]}
    assert set(as_run) == set(held["published"])
    differs = {k for k in as_run if as_run[k] != held["published"][k]}
    assert differs == set(held["reduced"]) == set(entry["reduced"])
    assert "cols" not in differs, "a width is never cut"
    assert held["rows"] * held["cols"] * 4 / mf.cell(
        MANIFEST, next(w["name"] for w in MANIFEST["workloads"] if w["config"] == config)
    )["chips"] >= 0.25 * 16e9, "a cell fills a quarter of a chip at the least"
