#
# A fit is recorded once: what a subsystem did for a run is a `fact[...]`
# instant on that run (tracing.fact), and the fit report's `staging`,
# `fused`, `stats`, `solver_decision` and `pass_report` sections are built
# from the run's own facts.  So overlapping fits each report their own, a
# run of the same subsystem elsewhere in the process reaches neither, and
# telemetry/ imports none of the layers above it to find out.
#
import ast
import os
import threading

import numpy as np
import pandas as pd
import pytest

import spark_rapids_ml_tpu.parallel.mesh as mesh_mod
from spark_rapids_ml_tpu import tracing
from spark_rapids_ml_tpu.config import reset_config, set_config
from spark_rapids_ml_tpu.telemetry.report import FitTelemetry

PACKAGE = os.path.dirname(os.path.abspath(tracing.__file__))


@pytest.fixture(autouse=True)
def _clean_config():
    reset_config()
    yield
    reset_config()


@pytest.fixture
def force_pipelined(monkeypatch):
    """Route even tiny arrays through the staging engine (production
    gates on _PIPELINED_MIN_BYTES)."""
    monkeypatch.setattr(mesh_mod, "_FORCE_PIPELINED", True)


# ---------------------------------------------------------------------------
# overlapping fits each report their own
# ---------------------------------------------------------------------------


def test_overlapping_fits_each_report_their_own_staging(force_pipelined):
    """Two fits on two threads, both staged before either solves (a
    barrier between staging and solve, no sleep): each report's `staging`
    holds its OWN bytes, and `concurrent_fits` marks the registry deltas
    beside them.  With process-wide last-run state both reported none."""
    from spark_rapids_ml_tpu.regression import LinearRegression

    rng = np.random.default_rng(3)
    barrier = threading.Barrier(2)
    reports, errors = {}, []

    def fit(key, n):
        try:
            X = rng.standard_normal((n, 8)).astype(np.float32)
            y = X @ np.arange(8, dtype=np.float32)
            est = LinearRegression(num_workers=1)
            solve = est._fit_array

            def staged_then_solve(fit_input):
                barrier.wait(timeout=120)  # both fits are staged and open
                return solve(fit_input)

            est._fit_array = staged_then_solve
            reports[key] = (est.fit((X, y)).fit_report(), X.nbytes)
        except BaseException as e:  # pragma: no cover - diagnostic
            errors.append(e)
            barrier.abort()

    set_config(fused_stage_solve="off")
    threads = [
        threading.Thread(target=fit, args=("a", 4096)),
        threading.Thread(target=fit, args=("b", 6144)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)
    (rep_a, bytes_a), (rep_b, bytes_b) = reports["a"], reports["b"]
    assert rep_a["concurrent_fits"] and rep_b["concurrent_fits"]
    assert rep_a["run_id"] != rep_b["run_id"]
    # the last engine run of a fit is one of its own X, y or w (n x 8 or
    # n float32), never the other fit's: the two fits share no size
    for rep, own in ((rep_a, bytes_a), (rep_b, bytes_b)):
        assert rep["staging"]["mb_per_s"] > 0
        assert rep["staging"]["bytes"] in (own, own // 8), rep["staging"]


# ---------------------------------------------------------------------------
# the eight writers: a run elsewhere in the process does not reach a fit
# ---------------------------------------------------------------------------


def _small_parquet(tmp_path) -> str:
    X = np.random.default_rng(5).standard_normal((600, 4)).astype(np.float32)
    path = str(tmp_path / "rows.parquet")
    pd.DataFrame({"features": list(X), "label": X[:, 0]}).to_parquet(
        path, row_group_size=200
    )
    return path


def _write_staging(tmp_path):
    X = np.random.default_rng(1).standard_normal((2048, 8)).astype(np.float32)
    mesh_mod.RowStager(2048, mesh_mod.get_mesh(1)).stage(X, np.float32)


def _write_parquet_staging(tmp_path):
    from spark_rapids_ml_tpu.streaming import stage_parquet

    stage_parquet(_small_parquet(tmp_path), label_col="label",
                  chunk_rows=256, num_workers=1)


def _write_fused(tmp_path):
    from spark_rapids_ml_tpu.fused import fused_linreg_stats, iter_host_chunks

    X = np.random.default_rng(2).standard_normal((1024, 4)).astype(np.float32)
    fused_linreg_stats(
        lambda n_dev: iter_host_chunks(X, X[:, 0], None, 256, np.float32),
        4, np.float32,
    )


def _write_stats(tmp_path):
    from spark_rapids_ml_tpu.stats import summarize

    X = np.random.default_rng(4).standard_normal((2000, 3)).astype(np.float32)
    summarize(X, metrics=["mean"])


def _write_pca_solver(tmp_path):
    from spark_rapids_ml_tpu.ops.pca import resolve_pca_solver

    resolve_pca_solver(256, 3)


def _write_parquet_readers(tmp_path):
    from spark_rapids_ml_tpu.fused import resolve_parquet_readers

    resolve_parquet_readers()


def _write_serving_bucket(tmp_path):
    from spark_rapids_ml_tpu.serving import ServingController

    ServingController().note_bucket("m", 100)


def _write_pass_report(tmp_path):
    from spark_rapids_ml_tpu.telemetry import fleet

    fleet.begin_pod_pass()
    fleet.complete_pod_pass()


# writer -> (the report section it fills, a key it puts there)
WRITERS = {
    "staging": (_write_staging, "staging", "mb_per_s"),
    "parquet_staging": (_write_parquet_staging, "staging", "engine"),
    "fused": (_write_fused, "fused", "kind"),
    "stats": (_write_stats, "stats", "programs"),
    "pca_solver": (_write_pca_solver, "solver_decision", "solver"),
    "parquet_readers": (
        _write_parquet_readers, "solver_decision", "parquet_readers"
    ),
    "serving_bucket": (
        _write_serving_bucket, "solver_decision", "serving_bucket"
    ),
    "pass_report": (_write_pass_report, "pass_report", "pass_id"),
}


@pytest.mark.parametrize("writer", sorted(WRITERS))
def test_a_run_outside_the_fit_does_not_reach_its_report(
    writer, tmp_path, force_pipelined
):
    """The subsystem runs on another thread, outside any fit, while a fit
    is open: the fit's report does not hold it (a wall-clock stamp let it
    in).  The same call made by the fit's own thread does land."""
    write, section, key = WRITERS[writer]

    def report_of(run_inside) -> dict:
        ft = FitTelemetry("FactProbe")
        with ft.span():
            run_inside()
        return ft.build()

    def on_another_thread():
        errors = []

        def run():
            try:
                write(tmp_path)
            except BaseException as e:  # pragma: no cover - diagnostic
                errors.append(e)

        t = threading.Thread(target=run)
        t.start()
        t.join(timeout=300)
        assert not errors and not t.is_alive(), errors

    outside = report_of(on_another_thread)
    assert "concurrent_fits" not in outside  # no other fit: a bare run
    assert key not in outside.get(section, {}), outside.get(section)
    own = report_of(lambda: write(tmp_path))
    assert key in own[section], own.get(section)


# ---------------------------------------------------------------------------
# layering: telemetry/ does not reach up
# ---------------------------------------------------------------------------


def _imports_of(path: str, package_parts: tuple) -> set:
    """Top-level names under `spark_rapids_ml_tpu` that the file imports,
    anywhere in it (function-local imports included)."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            targets = [a.name.split(".") for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            # level 1 is the file's own package, each further one a parent
            keep = len(package_parts) - node.level + 1
            base = list(package_parts[:keep]) if node.level else []
            mod = base + (node.module.split(".") if node.module else [])
            # `from .. import fused` names the module in `names`
            targets = [mod] + [mod + [a.name] for a in node.names]
        else:
            continue
        for parts in targets:
            if len(parts) > 1 and parts[0] == "spark_rapids_ml_tpu":
                found.add(parts[1])
    return found


@pytest.mark.parametrize(
    "layer", ["fused", "stats", "ops", "serving", "streaming", "models", "core"]
)
def test_telemetry_imports_no_layer_above_it(layer):
    """A subsystem hands its facts down (`tracing.fact`); no file under
    telemetry/ imports it to fetch them."""
    tdir = os.path.join(PACKAGE, "telemetry")
    offenders = sorted(
        name for name in os.listdir(tdir)
        if name.endswith(".py") and layer in _imports_of(
            os.path.join(tdir, name), ("spark_rapids_ml_tpu", "telemetry")
        )
    )
    assert not offenders, f"telemetry/{offenders} import {layer}"


# ---------------------------------------------------------------------------
# the mechanism: facts outside a run, and facts that outlive the trim
# ---------------------------------------------------------------------------


def test_a_fact_outside_any_run_is_in_the_threads_buffer():
    tracing.fact("probe_outside", rows=7, engine="x")
    assert tracing.last_fact("probe_outside") == {"rows": 7, "engine": "x"}
    ev = [e for e in tracing.get_trace_events()
          if e.name == "fact[probe_outside]"][-1]
    assert ev.run_id == "" and ev.kind == "instant" and ev.seconds == 0.0
    # another thread's buffer does not hold it; the process-wide view does
    seen = {}
    t = threading.Thread(
        target=lambda: seen.update(
            own=tracing.last_fact("probe_outside"),
            all=tracing.last_fact("probe_outside", all_threads=True),
        )
    )
    t.start()
    t.join(timeout=60)
    assert seen == {"own": {}, "all": {"rows": 7, "engine": "x"}}


def test_a_runs_facts_outlive_the_buffers_trim():
    """`_append` drops the oldest half of a full buffer.  The running
    run's facts are exempt (the last of each section), an ended run's are
    not, and the buffer stays bounded."""
    with tracing.run_context(prefix="ended") as ended:
        tracing.fact("probe_trim", n=0)
    with tracing.run_context(prefix="trim") as run_id:
        tracing.fact("probe_trim", n=1)
        tracing.fact("probe_trim", n=2)
        tracing.fact("probe_other", n=3)
        for _ in range(2 * tracing.MAX_EVENTS + 10):  # several trims
            tracing.record_span("filler", 0.0, 0.0)
        assert tracing.last_fact("probe_trim", run_id=run_id) == {"n": 2}
        assert tracing.last_fact("probe_other", run_id=run_id) == {"n": 3}
        assert tracing.last_fact("probe_trim", run_id=ended) == {}
        events = tracing.get_trace_events()
        assert len(events) <= tracing.MAX_EVENTS
        kept = [e for e in events if e.fields is not None]
        assert [e.fields for e in kept if e.name == "fact[probe_trim]"] == [
            {"n": 2}
        ]


def test_a_fit_that_records_many_spans_still_reports_its_facts():
    """The staging fact is recorded early in a fit; a solve that records
    more than MAX_EVENTS / 2 spans after it must not lose it."""
    ft = FitTelemetry("ManySpans")
    with ft.span():
        tracing.fact("staging", bytes=123, mb_per_s=4.5)
        for _ in range(tracing.MAX_EVENTS):
            tracing.record_span("lbfgs_eval", 0.0, 0.0)
    rep = ft.build()
    assert rep["staging"]["bytes"] == 123
    assert rep["staging"]["mb_per_s"] == 4.5


# ---------------------------------------------------------------------------
# the decision input has an owner
# ---------------------------------------------------------------------------


def test_auto_readers_are_sink_bounded_by_the_engines_put_rate(monkeypatch):
    """`fused_parquet_readers=auto`: with a decode rate and the staging
    engine's last put rate on record, readers beyond put/decode + 1 only
    contend for memory bandwidth.  The put rate is `mesh`'s own measured
    value, read through its accessor."""
    import spark_rapids_ml_tpu.fused as fused

    monkeypatch.setattr(os, "cpu_count", lambda: 16)
    monkeypatch.setattr(fused, "_DECODE_RATE", {"mb_per_s": 100.0})
    monkeypatch.setattr(mesh_mod, "_PUT_RATE", {})
    assert mesh_mod.last_put_rate_mb_per_s() is None
    assert fused.resolve_parquet_readers() == 16  # no put rate: the cores
    monkeypatch.setattr(mesh_mod, "_PUT_RATE", {"mb_per_s": 250.0})
    assert mesh_mod.last_put_rate_mb_per_s() == 250.0
    assert fused.resolve_parquet_readers() == 4  # ceil(250 / 100) + 1
    decision = tracing.last_fact("parquet_readers")
    assert decision["parquet_readers"] == 4
    assert decision["parquet_readers_mode"] == "auto"
    assert "sink-bounded at 250MB/s put" in decision["parquet_readers_reason"]
    # a fast decode needs no more than two
    monkeypatch.setattr(fused, "_DECODE_RATE", {"mb_per_s": 5000.0})
    assert fused.resolve_parquet_readers() == 2


def test_the_staging_engine_keeps_its_put_rate(force_pipelined):
    X = np.random.default_rng(6).standard_normal((4096, 8)).astype(np.float32)
    mesh_mod.RowStager(4096, mesh_mod.get_mesh(1)).stage(X, np.float32)
    assert mesh_mod.last_put_rate_mb_per_s() == (
        tracing.last_fact("staging")["mb_per_s"]
    )
