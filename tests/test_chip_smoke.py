#
# chip_smoke.py at a toy size on the 8-device CPU mesh, and the repairs it
# stands on: the compile cache that can be placed from outside, the
# OOM-to-streaming refit that now says it happened, the device budget
# read from the device, and the native library that rebuilds itself.
#
import gc
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from spark_rapids_ml_tpu import _jax_env  # noqa: E402
from spark_rapids_ml_tpu.config import reset_config, set_config  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_config():
    reset_config()
    yield
    reset_config()


# ---------------------------------------------------------------------------
# (a) the smoke's body, and its refusal to pass without a TPU
# ---------------------------------------------------------------------------


def test_smoke_body_passes_on_cpu_mesh(tmp_path):
    """Same phases, same result checks, same sharding assertion as on the
    chip: 4096 x 64 through parquet (8 row groups, so the parallel range
    readers run) on all 8 devices."""
    # the CPU mesh's memory provider is a census of the process's live
    # arrays: what an earlier test file of this worker left for the
    # collector must not be freed between the smoke's two readings
    gc.collect()
    facts = chip_smoke.run_smoke(
        4096, 64, str(tmp_path / "out"), data_home=str(tmp_path),
        slab_rows=512,
    )
    assert facts["failures"] == []
    assert facts["device_count"] == 8 and facts["platform"] == "cpu"
    assert facts["reference_size"] is False  # and the output said so
    assert facts["lbfgs_route"] == ["lbfgs_route[fused]"]
    assert facts["staging"]["engine"].startswith("per-device")
    assert facts["prob_gap_vs_float64"] < 1e-5  # an exact f32 matvec here
    hist = facts["objective_history"]
    assert abs(hist[0] - np.log(2)) < 1e-5 and hist == sorted(hist, reverse=True)
    # the data was made for this run and went with it
    assert [p.name for p in tmp_path.iterdir()] == ["out"]
    with open(tmp_path / "out" / "result_8dev_4096x64.json") as f:
        assert json.load(f)["failures"] == []


def test_smoke_falls_back_when_a_place_cannot_keep_the_file(tmp_path, monkeypatch):
    """Where the parquet lives is the smoke's own business: a place that
    cannot keep it is named and passed over, not counted against the
    system; with no place left the same array is fitted from memory (rows
    on the shape-bucket grid, so the staged bytes are the dataset's)."""
    not_a_dir = tmp_path / "file"
    not_a_dir.write_text("")
    good = tmp_path / "good"
    good.mkdir()
    monkeypatch.setattr(
        chip_smoke, "_data_homes", lambda need: [str(not_a_dir), str(good)]
    )
    facts = chip_smoke.run_smoke(4096, 64, str(tmp_path / "out"), slab_rows=512)
    assert facts["failures"] == []
    assert facts["data"] == f"parquet under {good}" and not list(good.iterdir())

    facts = chip_smoke.run_smoke(
        24576, 64, str(tmp_path / "out"), slab_rows=4096, from_memory=True
    )
    assert facts["failures"] == [] and facts["data"] == "memory"
    assert facts["lbfgs_route"] == ["lbfgs_route[fused]"]
    assert facts["resilience"]["oom_streaming_refits"] == 0


def test_data_homes_pass_over_ram_and_full_disks(tmp_path, monkeypatch):
    assert chip_smoke._fs_type("/proc/self") == "proc"
    monkeypatch.setattr(chip_smoke, "DATA_DIR", str(tmp_path / "data"))
    homes = chip_smoke._data_homes(1.0)
    if chip_smoke._fs_type(str(tmp_path)) not in ("tmpfs", "ramfs"):
        assert homes[0] == str(tmp_path / "data")
    assert all(chip_smoke._fs_type(h) not in ("tmpfs", "ramfs") for h in homes)
    assert chip_smoke._data_homes(1e18) == []  # nowhere: fit from memory


def test_smoke_refuses_the_streaming_refit(tmp_path, capsys):
    """A phase that went wrong fails the run: an injected staging OOM still
    yields a model (the streaming refit) — the fit report now says so, with
    an event and a count, and the smoke says no."""
    from spark_rapids_ml_tpu.resilience import fault_inject
    from spark_rapids_ml_tpu.tracing import get_trace_events, reset_trace

    reset_trace()
    with fault_inject("stage_parquet", "oom", times=1):
        facts = chip_smoke.run_smoke(
            4096, 64, str(tmp_path / "out"), data_home=str(tmp_path),
            slab_rows=512,
        )
    assert any("resident fit" in f for f in facts["failures"])
    # a caller that keeps only stderr still reads why
    assert "FAILED resident fit" in capsys.readouterr().err
    assert facts["resilience"]["oom_streaming_refits"] == 1
    marker = [e for e in get_trace_events() if e.name == "oom_streaming_refit"]
    assert len(marker) == 1 and "RESOURCE_EXHAUSTED" in marker[0].detail
    assert marker[0].run_id  # stamped with the fit that it interrupted


def test_main_has_no_way_past_the_tpu_check(monkeypatch, capsys):
    """Non-zero, one line of reason, no result line: on a CPU device list
    `main` ends before anything runs."""
    import jax

    with pytest.raises(SystemExit) as ei:
        chip_smoke.require_tpu(jax.devices())
    assert "no TPU" in str(ei.value) and "'cpu'" in str(ei.value)
    # main itself (the cache helper stubbed out: it would re-point this
    # test process's jax config)
    monkeypatch.setattr(_jax_env, "configure_compile_cache", lambda: None)
    monkeypatch.setattr(
        chip_smoke, "run_smoke",
        lambda *a, **k: pytest.fail("ran without a TPU"),
    )
    with pytest.raises(SystemExit) as ei:
        chip_smoke.main([])
    assert ei.value.code not in (0, None)
    assert str(ei.value).startswith("chip_smoke: no TPU")
    assert capsys.readouterr().out == ""


# ---------------------------------------------------------------------------
# (b) the compile cache: placed from outside, or at one fixed path
# ---------------------------------------------------------------------------

# the helper loaded from its file: importing the package costs two seconds
# a process and is not what is under test
_REPORT_CACHE = (
    "import importlib.util as u, sys;"
    "spec = u.spec_from_file_location('_jax_env', sys.argv[1]);"
    "m = u.module_from_spec(spec); spec.loader.exec_module(m);"
    "print(m.configure_compile_cache());"
    "print(m.configure_compile_cache());"
    "print(m.compile_cache_dir())"
)


def _cache_lines(env):
    proc = subprocess.run(
        [sys.executable, "-c", _REPORT_CACHE, _jax_env.__file__],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.split()


def test_compile_cache_placed_by_env_is_left_alone(tmp_path):
    placed = str(tmp_path / "placed_from_outside")
    env = dict(os.environ, JAX_PLATFORMS="cpu", **{_jax_env.CACHE_ENV: placed})
    # the helper returns it, and jax's own config (read from the
    # environment at import) names it
    assert _cache_lines(env) == [placed, placed, placed]
    assert not os.path.exists(placed)  # no code touched the directory


def test_compile_cache_default_is_one_fixed_path(monkeypatch):
    import jax

    want = os.path.join(REPO, ".jax_cache")
    env = {k: v for k, v in os.environ.items() if k != _jax_env.CACHE_ENV}
    env["JAX_PLATFORMS"] = "cpu"
    # two calls in another process ...
    assert _cache_lines(env) == [want] * 3
    # ... and two in this one agree (this process's jax config restored)
    monkeypatch.delenv(_jax_env.CACHE_ENV, raising=False)
    before = _jax_env.compile_cache_dir()
    try:
        assert _jax_env.configure_compile_cache() == want
        assert _jax_env.configure_compile_cache() == want
        assert _jax_env.compile_cache_dir() == want
    finally:
        jax.config.update(_jax_env.CACHE_CONF, before)


# ---------------------------------------------------------------------------
# (d) the device budget comes from the device
# ---------------------------------------------------------------------------


class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform, self._stats = platform, stats

    def memory_stats(self):
        return self._stats

    def __repr__(self):
        return f"FakeDevice({self.platform})"


def test_device_budget_is_read_from_the_device():
    from spark_rapids_ml_tpu.parallel.device_cache import device_hbm_bytes

    v5e = _FakeDevice("tpu", {"bytes_limit": 16909336064, "bytes_in_use": 0})
    assert device_hbm_bytes(v5e) == 16909336064  # not the data sheet's 16 GiB
    # a backend that reports nothing (the CPU test mesh) gets the conf
    assert device_hbm_bytes(_FakeDevice("cpu", None)) == 16 * 1024**3
    # an explicit conf wins over the device
    set_config(hbm_bytes=1 << 30)
    assert device_hbm_bytes(v5e) == 1 << 30
    assert device_hbm_bytes(_FakeDevice("cpu", None)) == 1 << 30


def test_tpu_without_a_limit_is_an_error():
    from spark_rapids_ml_tpu.parallel.device_cache import device_hbm_bytes

    for stats in (None, {}, {"bytes_in_use": 5}):
        with pytest.raises(RuntimeError, match="bytes_limit"):
            device_hbm_bytes(_FakeDevice("tpu", stats))
    set_config(hbm_bytes=1 << 30)  # ... unless the caller says what it is
    assert device_hbm_bytes(_FakeDevice("tpu", None)) == 1 << 30


def test_auto_memory_provider_on_a_tpu_is_real_or_raises(monkeypatch):
    from spark_rapids_ml_tpu.parallel import mesh
    from spark_rapids_ml_tpu.telemetry import memory

    monkeypatch.setattr(
        mesh, "active_devices", lambda: [_FakeDevice("tpu", None)]
    )
    memory.reset_memory_telemetry()
    try:
        with pytest.raises(RuntimeError, match="memory_stats"):
            memory.get_provider()
        monkeypatch.setattr(
            mesh, "active_devices",
            lambda: [_FakeDevice("tpu", {"bytes_limit": 1, "bytes_in_use": 0})],
        )
        assert memory.get_provider().name == "real"
    finally:
        memory.reset_memory_telemetry()


def test_logreg_leaves_the_fused_solver_when_its_copy_cannot_fit(rng):
    """The single-program L-BFGS holds the features twice (XLA copies a
    while_loop's invariant operands); where one device cannot, the
    resident fit runs host-dispatched instead of dying at compile time."""
    from spark_rapids_ml_tpu.classification import LogisticRegression

    X = rng.normal(size=(4096, 16)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)

    def route(model):
        return [
            e["name"] for e in
            chip_smoke._find_events(model.fit_report()["spans"], "lbfgs_route[")
        ]

    est = LogisticRegression(maxIter=3, num_workers=2)
    assert route(est.fit((X, y))) == ["lbfgs_route[fused]"]
    # shard = 2048 x 16 x 4 = 128 KiB per device; a 200 KiB device holds
    # it once but not twice (and the 8-device budget still keeps the fit
    # resident)
    set_config(hbm_bytes=200 * 1024)
    m = LogisticRegression(maxIter=3, num_workers=2).fit((X, y))
    assert route(m) == ["lbfgs_route[host_dispatch]"]
    assert m.fit_report()["resilience"]["retries"] == 0


# ---------------------------------------------------------------------------
# the native staging library is built here, from this source
# ---------------------------------------------------------------------------


def test_native_library_from_another_machine_is_rebuilt(monkeypatch, tmp_path):
    import shutil

    from spark_rapids_ml_tpu import native

    if shutil.which("g++") is None:
        pytest.skip("no compiler: the numpy path is the only path")
    build = tmp_path / "build"
    build.mkdir()
    lib = build / "libstaging.so"
    # what a copied directory carries in: a library, and the key of the
    # source, flags, CPU and boot it was built under
    lib.write_bytes(b"not built here")
    (build / "libstaging.so.key").write_text("someone else's")
    monkeypatch.setattr(native, "_BUILD_DIR", str(build))
    monkeypatch.setattr(native, "_LIB_PATH", str(lib))
    monkeypatch.setattr(native, "_KEY_PATH", str(lib) + ".key")
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_failed", False)
    assert native.status().startswith("native (")  # rebuilt, then loaded
    assert (build / "libstaging.so.key").read_text() == native._build_key()
    assert lib.read_bytes() != b"not built here"


# ---------------------------------------------------------------------------
# a measuring script without a TPU stops; one process for each chip
# ---------------------------------------------------------------------------


def test_unpinned_measurement_without_a_tpu_exits(monkeypatch, capsys):
    from benchmark.base import NO_TPU_RC, require_tpu_unless_cpu_pinned

    # the CI path: the caller pinned the CPU and gets its label
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert require_tpu_unless_cpu_pinned("t") == "cpu x8"
    # unpinned, jax's silent CPU fallback is refused
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit) as ei:
        require_tpu_unless_cpu_pinned("t")
    assert ei.value.code == NO_TPU_RC
    assert "t: no TPU" in capsys.readouterr().err


def test_pod_launcher_refuses_local_emulation_on_tpus(capsys):
    from benchmark.pod import launch

    with pytest.raises(SystemExit) as ei:
        launch.main(["--platform", "tpu", "--", "kmeans"])
    assert ei.value.code == 2
    assert "local emulation is a CPU mode" in capsys.readouterr().err


def test_dryrun_takes_its_devices_from_the_caller():
    """No platform is pinned behind the caller's back: too few devices is
    an error that says how to get them."""
    import __graft_entry__ as entry

    with pytest.raises(RuntimeError, match="xla_force_host_platform_device_count=16"):
        entry.dryrun_multichip(16)
    assert [name for name, _ in entry.FAMILIES] == [
        "logreg", "kmeans", "knn", "dbscan", "rf", "pca", "linreg", "ann", "umap",
    ]


def test_config_knows_what_the_caller_set(monkeypatch):
    from spark_rapids_ml_tpu.config import is_explicit

    assert not is_explicit("hbm_bytes")
    set_config(hbm_bytes=123)
    assert is_explicit("hbm_bytes")
    reset_config()
    monkeypatch.setenv("SPARK_RAPIDS_ML_TPU_HBM_BYTES", "456")
    assert is_explicit("hbm_bytes")
    with pytest.raises(KeyError):
        is_explicit("no_such_key")
