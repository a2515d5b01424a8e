#
# Unified telemetry tests — the metrics registry (Counter/Gauge/
# Histogram + the legacy dict views), correlated spans (run ids, t0/t1,
# cross-thread adoption), the Chrome-trace and Prometheus exporters, the
# per-fit report, and the solver heartbeat.  The end-to-end acceptance
# scenario (a fault-injected KMeans fit whose retry/recovery markers
# share the fit's run_id, fall inside the fit span, and reconcile with
# RECOVERY_METRICS and the fit report) runs ONE small fit on the 8-dev
# CPU mesh and asserts everything off it.
#
import json
import threading
import time
import urllib.request

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu.config import reset_config, set_config
from spark_rapids_ml_tpu.telemetry import (
    Heartbeat,
    MetricsRegistry,
    chrome_trace,
    delta,
    dump_prometheus,
    parse_prometheus,
    snapshot,
)
from spark_rapids_ml_tpu.tracing import (
    current_run_id,
    get_trace_events,
    reset_trace,
    run_context,
    summarize,
    trace,
)


@pytest.fixture(autouse=True)
def _clean():
    from spark_rapids_ml_tpu.telemetry import reset_memory_telemetry

    reset_config()
    reset_trace()
    reset_memory_telemetry()
    yield
    reset_config()
    reset_trace()
    reset_memory_telemetry()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram_labels():
    reg = MetricsRegistry()
    c = reg.counter("requests", "help text")
    c.inc()
    c.inc(2, site="fit")
    assert c.value() == 1
    assert c.value(site="fit") == 2
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(3)
    g.dec()
    assert g.value() == 2
    h = reg.histogram("latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    s = h.samples()[()]
    assert s["count"] == 3 and s["buckets"] == [1, 2]
    assert s["sum"] == pytest.approx(5.55)
    # re-registration returns the same family; kind conflicts are errors
    assert reg.counter("requests") is c
    with pytest.raises(ValueError):
        reg.gauge("requests")


def test_registry_snapshot_delta_and_reset():
    reg = MetricsRegistry()
    c = reg.counter("x")
    c.inc(5, kind="a")
    before = reg.snapshot()
    c.inc(3, kind="a")
    c.inc(1, kind="b")
    d = delta(before, reg.snapshot())
    assert d == {"x": {"kind=a": 3, "kind=b": 1}}
    view = reg.dict_view("v", initial={"n": 0})
    view["n"] = 7
    reg.reset()
    assert c.value(kind="a") == 0
    assert view["n"] == 0  # initial keys re-seeded


def test_dict_view_back_compat_surface():
    reg = MetricsRegistry()
    v = reg.dict_view("legacy", initial={"hits": 0})
    v["hits"] += 2
    v["label"] = "stage"  # non-numeric values stay readable
    v.update(bytes=1024, mb_per_s=3.5)
    assert v["hits"] == 2 and isinstance(v["hits"], int)
    assert v.get("missing") is None and "bytes" in v
    assert dict(v) == {
        "hits": 2, "bytes": 1024, "mb_per_s": 3.5, "label": "stage"
    }
    v.clear()
    assert len(v) == 0
    v.bump("fresh")  # creates-at-zero increment
    assert v["fresh"] == 1


def test_legacy_dict_names_read_through_registry():
    """The legacy metric dicts are views over the process registry:
    a mutation through the OLD name is visible in `dump_prometheus` and
    `snapshot()` immediately."""
    from spark_rapids_ml_tpu.parallel.device_cache import CACHE_METRICS
    from spark_rapids_ml_tpu.parallel.mesh import STAGE_COUNTS
    from spark_rapids_ml_tpu.resilience import RECOVERY_METRICS

    s0 = STAGE_COUNTS["dataset_stagings"]
    STAGE_COUNTS["dataset_stagings"] += 1
    assert (
        snapshot()["staging_counts"]["key=dataset_stagings"] == s0 + 1
    )
    STAGE_COUNTS["dataset_stagings"] = s0
    for view, family in (
        (STAGE_COUNTS, "staging_counts"),
        (CACHE_METRICS, "device_cache"),
        (RECOVERY_METRICS, "recovery"),
    ):
        samples = parse_prometheus(dump_prometheus())
        for k, val in view.items():
            if isinstance(val, (int, float)):
                key = (f"spark_rapids_ml_tpu_{family}", (("key", k),))
                assert samples[key] == float(val), (family, k)


def test_cache_mirror_counters_never_drift():
    """Satellite: `device_cache._note` used to drop kinds whose mirror
    key was missing from STAGE_COUNTS — every mirrored pair must now
    move in lockstep, including `inserts`."""
    from spark_rapids_ml_tpu.parallel import device_cache
    from spark_rapids_ml_tpu.parallel.mesh import STAGE_COUNTS

    kinds = ("hits", "misses", "evictions", "inserts", "novel_kind")
    before = {
        k: (
            device_cache.CACHE_METRICS.get(k, 0),
            STAGE_COUNTS.get("cache_" + k, 0),
        )
        for k in kinds
    }
    for k in kinds:
        device_cache._note(k)
    for k in kinds:
        c0, s0 = before[k]
        assert device_cache.CACHE_METRICS[k] - c0 == 1, k
        assert STAGE_COUNTS["cache_" + k] - s0 == 1, k
        # and the pair agrees absolutely for registry-seeded kinds
        assert (
            device_cache.CACHE_METRICS[k] - c0
            == STAGE_COUNTS["cache_" + k] - s0
        ), k


# ---------------------------------------------------------------------------
# spans + correlation
# ---------------------------------------------------------------------------


def test_spans_carry_timestamps_thread_and_run_id():
    wall0 = time.time()
    with run_context(prefix="fit") as rid:
        assert current_run_id() == rid
        with trace("outer"):
            with trace("inner"):
                pass
    assert current_run_id() == ""
    ev = {e.name: e for e in get_trace_events()}
    for name in ("outer", "inner"):
        e = ev[name]
        assert e.run_id == rid and e.kind == "span"
        assert wall0 <= e.t0 <= e.t1 <= time.time()
        assert e.thread_id == threading.get_ident()
    assert ev["outer"].t0 <= ev["inner"].t0
    assert ev["inner"].t1 <= ev["outer"].t1 + 1e-6


def test_summarize_renders_start_order():
    """Satellite: events append on stage EXIT, so the summary used to
    print children before parents; with t0 on every span the tree
    renders in start order."""
    with trace("parent"):
        with trace("child_a"):
            pass
        with trace("child_b"):
            pass
    with trace("sibling"):
        pass
    lines = summarize().splitlines()
    names = [ln.strip().split(":")[0] for ln in lines]
    assert names == ["parent", "child_a", "child_b", "sibling"]
    assert lines[0].startswith("parent") and lines[1].startswith("  ")


def test_guarded_timeout_leaves_closed_span_tree():
    """Cross-thread correlation: a guarded dispatch that times out
    MID-SPAN must leave a well-formed (closed) span tree in the caller's
    buffer — completed worker spans appear with the caller's run id,
    the hung span never appears half-open, and the timeout marker lands
    at the caller's depth."""
    from spark_rapids_ml_tpu.resilience import DispatchTimeout, guarded

    release = threading.Event()

    def work():
        with trace("worker_done"):
            pass
        with trace("worker_hung"):
            release.wait(5.0)

    with run_context(prefix="fit") as rid:
        with trace("fit_span"):
            with pytest.raises(DispatchTimeout):
                guarded(work, deadline=0.2, label="probe")
    release.set()
    time.sleep(0.05)
    events = get_trace_events()
    by_name = {e.name: e for e in events}
    assert by_name["worker_done"].run_id == rid
    assert by_name["dispatch_timeout[probe]"].run_id == rid
    assert by_name["dispatch_timeout[probe]"].kind == "instant"
    # spans only close on exit: every recorded span has t1 >= t0 and the
    # abandoned (hung) span is simply absent rather than dangling open
    for e in events:
        assert e.t1 >= e.t0
    hung = [e for e in events if e.name == "worker_hung"]
    assert all(e.t1 >= e.t0 for e in hung)  # closes late or not at all


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_chrome_trace_tracks_and_markers():
    from spark_rapids_ml_tpu.telemetry.exporters import MARKER_TID
    from spark_rapids_ml_tpu.tracing import event

    with run_context(prefix="fit") as rid:
        with trace("stage_x"):
            event("retry[x]", detail="attempt=1")
    ct = chrome_trace(run_id=rid)
    payload = json.loads(json.dumps(ct))  # must be JSON-serializable
    evs = payload["traceEvents"]
    spans = [e for e in evs if e.get("ph") == "X"]
    instants = [e for e in evs if e.get("ph") == "i"]
    meta = [e for e in evs if e.get("ph") == "M"]
    assert [s["name"] for s in spans] == ["stage_x"]
    assert instants[0]["name"] == "retry[x]"
    assert instants[0]["tid"] == MARKER_TID
    assert instants[0]["args"]["run_id"] == rid
    # the marker track and the recording thread's track are both named
    assert any(m["tid"] == MARKER_TID for m in meta)
    assert any(m["tid"] == spans[0]["tid"] for m in meta)
    # the instant falls inside its enclosing span
    s = spans[0]
    assert s["ts"] <= instants[0]["ts"] <= s["ts"] + s["dur"]


def test_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("hits", "total hits").inc(3, site="fit_kernel")
    reg.gauge("depth").set(2.5)
    reg.histogram("lat", buckets=(1.0,)).observe(0.5)
    text = dump_prometheus(reg)
    assert "# TYPE spark_rapids_ml_tpu_hits counter" in text
    parsed = parse_prometheus(text)
    assert parsed[
        ("spark_rapids_ml_tpu_hits", (("site", "fit_kernel"),))
    ] == 3.0
    assert parsed[("spark_rapids_ml_tpu_depth", ())] == 2.5
    assert parsed[("spark_rapids_ml_tpu_lat_count", ())] == 1.0
    assert parsed[("spark_rapids_ml_tpu_lat_bucket", (("le", "1.0"),))] == 1.0


def test_http_endpoint_serves_metrics():
    from spark_rapids_ml_tpu.telemetry import (
        start_http_server,
        stop_http_server,
    )

    reg = MetricsRegistry()
    reg.counter("pings").inc(4)
    srv = start_http_server(0, registry=reg)  # ephemeral port
    try:
        url = f"http://127.0.0.1:{srv.server_port}/metrics"
        body = urllib.request.urlopen(url, timeout=5).read().decode()
        assert parse_prometheus(body)[("spark_rapids_ml_tpu_pings", ())] == 4.0
        with pytest.raises(Exception):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.server_port}/nope", timeout=5
            )
    finally:
        stop_http_server()


def test_http_endpoint_concurrent_scrapes_during_active_writes():
    """4 threads scraping `/metrics` WHILE a writer hammers the
    registry: every response must carry the exact exposition content
    type (`text/plain; version=0.0.4; charset=utf-8`), parse cleanly
    (no torn output — Content-Length is computed from the rendered
    body, so a scrape mid-write still reads one consistent page), and
    nothing may deadlock against the registry lock."""
    import threading

    from spark_rapids_ml_tpu.telemetry import (
        start_http_server,
        stop_http_server,
    )

    stop_http_server()
    reg = MetricsRegistry()
    c = reg.counter("scrape_probe")
    h = reg.histogram("scrape_lat", buckets=(0.1, 1.0))
    stop_writer = threading.Event()

    def _writer():
        i = 0
        while not stop_writer.is_set():
            c.inc(site=f"s{i % 5}")
            h.observe(0.05 * (i % 30))
            i += 1

    srv = start_http_server(0, registry=reg)
    wt = threading.Thread(target=_writer, daemon=True)
    wt.start()
    failures = []

    def _scraper():
        try:
            url = f"http://127.0.0.1:{srv.server_port}/metrics"
            for _ in range(25):
                with urllib.request.urlopen(url, timeout=30) as resp:
                    assert resp.headers["Content-Type"] == (
                        "text/plain; version=0.0.4; charset=utf-8"
                    )
                    body = resp.read().decode()
                parsed = parse_prometheus(body)  # raises on torn lines
                # histogram internal consistency on every scrape: the
                # +Inf bucket IS the count (a torn page would drift)
                cnt = parsed.get(
                    ("spark_rapids_ml_tpu_scrape_lat_count", ())
                )
                inf = parsed.get(
                    ("spark_rapids_ml_tpu_scrape_lat_bucket",
                     (("le", "+Inf"),))
                )
                assert cnt == inf, (cnt, inf)
        except Exception as e:  # pragma: no cover - the assertion payload
            failures.append(e)

    try:
        scrapers = [
            threading.Thread(target=_scraper) for _ in range(4)
        ]
        for t in scrapers:
            t.start()
        for t in scrapers:
            t.join(timeout=120)
            assert not t.is_alive(), "scraper deadlocked"
    finally:
        stop_writer.set()
        wt.join(timeout=30)
        stop_http_server()
    assert not failures, failures


# ---------------------------------------------------------------------------
# heartbeat
# ---------------------------------------------------------------------------


def test_heartbeat_logs_and_gauges():
    import logging

    records = []
    handler = logging.Handler()
    handler.emit = lambda r: records.append(r.getMessage())
    lg = logging.getLogger("hb_test")
    lg.setLevel(logging.INFO)
    lg.addHandler(handler)
    try:
        hb = Heartbeat("probe_solver", total=10, log=lg, interval=0.01)
        hb.beat(1, loss=5.0)
        time.sleep(0.02)
        hb.beat(2, loss=4.0)
    finally:
        lg.removeHandler(handler)
    assert any("[heartbeat] probe_solver" in m and "it=2/10" in m
               for m in records)
    from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

    assert REGISTRY.get("solver_iteration").value(solver="probe_solver") == 2
    assert REGISTRY.get("solver_loss").value(solver="probe_solver") == 4.0
    hb.close()  # drop the series: later tests read global solver state


def test_heartbeat_silent_when_disabled():
    import logging

    records = []
    handler = logging.Handler()
    handler.emit = lambda r: records.append(r.getMessage())
    lg = logging.getLogger("hb_test_silent")
    lg.setLevel(logging.INFO)
    lg.addHandler(handler)
    try:
        hb = Heartbeat("quiet_solver", log=lg, interval=0.0)
        for i in range(5):
            hb.beat(i)
    finally:
        lg.removeHandler(handler)
    assert not records  # gauges still track, the log stays quiet
    from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

    assert REGISTRY.get("solver_iteration").value(solver="quiet_solver") == 4
    hb.close()  # drop the series: later tests read global solver state


# ---------------------------------------------------------------------------
# per-fit report + the end-to-end acceptance scenario
# ---------------------------------------------------------------------------


def test_fit_report_plain_fit(tmp_path, rng):
    from spark_rapids_ml_tpu.clustering import KMeans

    set_config(telemetry_dir=str(tmp_path / "tel"))
    X = rng.normal(size=(200, 4)).astype(np.float32)
    m = KMeans(k=2, seed=0, maxIter=5).fit(
        pd.DataFrame({"features": list(X)})
    )
    rep = m.fit_report()
    assert rep["estimator"] == "KMeans"
    assert rep["run_id"].startswith("fit-")
    assert rep["solver"]["n_iter"] == m.n_iter_
    assert rep["staging"].get("dataset_stagings", 0) >= 1
    roots = [s["name"] for s in rep["spans"]]
    assert roots and roots[0] == "fit[KMeans]"
    # the artifact landed under telemetry_dir and parses back
    files = list((tmp_path / "tel").glob("fit_KMeans_*.json"))
    assert len(files) == 1
    on_disk = json.loads(files[0].read_text())
    assert on_disk["run_id"] == rep["run_id"]


def test_fault_injected_fit_full_telemetry(tmp_path, rng):
    """The acceptance scenario: ONE KMeans fit that survives an injected
    OOM retry and a `device_lost` elastic recovery must produce (a) a
    Chrome trace whose retry/recovery instant events share the fit's
    run_id and fall inside the fit span, (b) a Prometheus dump whose
    recovery family matches RECOVERY_METRICS, and (c) a fit report whose
    iteration count matches the solver's n_iter and whose resilience
    section saw the retry and the salvage."""
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.parallel.mesh import active_devices
    from spark_rapids_ml_tpu.resilience import fault_inject
    from spark_rapids_ml_tpu.resilience.elastic import (
        RECOVERY_METRICS,
        reset_elastic,
    )

    set_config(
        telemetry_dir=str(tmp_path / "tel"),
        checkpoint_dir=str(tmp_path / "ckpt"),
        retry_backoff_s=0.01,
        retry_jitter=0.0,
    )
    X = rng.normal(size=(400, 6)).astype(np.float32)
    df = pd.DataFrame({"features": list(X)})
    try:
        with fault_inject("fit_kernel", "oom", times=1), fault_inject(
            "kmeans_lloyd", "device_lost", times=1, skip=3
        ):
            m = KMeans(k=3, seed=7, maxIter=8, tol=0.0).fit(df)
        rep = m.fit_report()
        rid = rep["run_id"]

        # (a) Chrome trace: markers share the run id, inside the fit span
        ct = chrome_trace(run_id=rid)
        evs = ct["traceEvents"]
        fit_span = next(
            e for e in evs
            if e.get("ph") == "X" and e["name"] == "fit[KMeans]"
        )
        instants = [e for e in evs if e.get("ph") == "i"]
        names = {e["name"] for e in instants}
        assert any(n.startswith("retry[") for n in names)
        assert any(n.startswith("elastic_recovery[") for n in names)
        for e in instants:
            assert e["args"]["run_id"] == rid, e["name"]
            assert (
                fit_span["ts"] <= e["ts"] <= fit_span["ts"] + fit_span["dur"]
            ), e["name"]

        # (b) Prometheus dump reconciles with RECOVERY_METRICS
        parsed = parse_prometheus(dump_prometheus())
        for k, v in RECOVERY_METRICS.items():
            assert parsed[
                ("spark_rapids_ml_tpu_recovery", (("key", k),))
            ] == float(v), k
        assert RECOVERY_METRICS["meshes_rebuilt"] == 1
        assert RECOVERY_METRICS["iterations_salvaged"] == 3

        # (c) the report: solver n_iter matches, resilience reconciles
        assert rep["solver"]["n_iter"] == m.n_iter_ == 8
        res = rep["resilience"]
        assert res["retries"] >= 2  # the OOM retry + the device-loss retry
        assert res["faults_injected"] == 2
        assert res["iterations_salvaged"] == 3
        assert res["recoveries"]["meshes_rebuilt"] == 1
        assert len(active_devices()) == 7  # shrunk mesh, pre-reset
    finally:
        reset_elastic()


def test_transform_mints_run_id(rng):
    from spark_rapids_ml_tpu.clustering import KMeans

    X = rng.normal(size=(120, 4)).astype(np.float32)
    df = pd.DataFrame({"features": list(X)})
    m = KMeans(k=2, seed=0, maxIter=3).fit(df)
    reset_trace()
    m.transform(df)
    runs = {
        e.run_id
        for e in get_trace_events()
        if e.name.startswith("transform_chunk")
    }
    assert len(runs) == 1
    assert runs.pop().startswith("transform-")


def test_fit_report_never_fails_fit(rng, monkeypatch):
    """Observability must not fail the fit it observed: a broken report
    write (unwritable telemetry_dir) degrades to a warning."""
    from spark_rapids_ml_tpu.clustering import KMeans

    set_config(telemetry_dir="/proc/definitely/not/writable")
    X = rng.normal(size=(150, 4)).astype(np.float32)
    m = KMeans(k=2, seed=0, maxIter=3).fit(
        pd.DataFrame({"features": list(X)})
    )
    assert m.fit_report() is not None  # report built, artifact skipped


# ---------------------------------------------------------------------------
# memory telemetry: providers, watermarks, budget drift
# ---------------------------------------------------------------------------


def test_simulated_provider_census_is_exact():
    """The CPU container has no `memory_stats()` (RealMemoryProvider
    reports nothing here); the simulated provider must census live
    sharded arrays byte-exactly per device, deterministically."""
    import jax

    from spark_rapids_ml_tpu.parallel.mesh import RowStager, get_mesh
    from spark_rapids_ml_tpu.telemetry.memory import (
        RealMemoryProvider,
        SimulatedMemoryProvider,
        sample_devices,
    )

    assert not RealMemoryProvider.available()
    prov = SimulatedMemoryProvider()
    before = {d: s["bytes_in_use"] for d, s in prov.sample().items()}
    mesh = get_mesh()
    st = RowStager(800, mesh, bucketing=False)
    Xs = st.stage(np.ones((800, 16), np.float32), np.float32)
    jax.block_until_ready(Xs)
    after = prov.sample()
    per_dev = st.local_padded // mesh.devices.size * 16 * 4
    for d in (int(dd.id) for dd in mesh.devices.flat):
        grew = after[d]["bytes_in_use"] - before.get(d, 0)
        assert grew >= per_dev, (d, grew, per_dev)
        # peak is a running max
        assert after[d]["peak_bytes_in_use"] >= after[d]["bytes_in_use"]
    # the module-level sampler (auto -> simulated here) fills the gauges
    set_config(memory_provider="auto")
    live = sample_devices()
    assert live and all(v > 0 for v in live.values())
    snap = snapshot()
    assert snap["device_bytes_in_use"], "per-device gauge not exported"
    del Xs


def test_memory_provider_off_noops():
    from spark_rapids_ml_tpu.telemetry.memory import (
        reset_memory_telemetry,
        sample_devices,
    )

    set_config(memory_provider="off")
    reset_memory_telemetry()
    assert sample_devices() == {}


def test_fit_report_memory_section_and_drift(rng):
    """A plain fit on the simulated provider lands per-device peak bytes
    and a finite budget_drift_ratio (staged-bytes prediction vs measured
    peak) in its report."""
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh

    X = rng.normal(size=(300, 8)).astype(np.float32)
    m = KMeans(k=3, seed=1, maxIter=4).fit(pd.DataFrame({"features": list(X)}))
    mem = m.fit_report().get("memory")
    assert mem is not None and mem["provider"] == "simulated"
    n_dev = get_mesh().devices.size
    assert len(mem["per_device_peak_bytes"]) == n_dev
    assert all(v > 0 for v in mem["per_device_peak_bytes"].values())
    assert mem["peak_total_bytes"] == sum(
        mem["per_device_peak_bytes"].values()
    )
    assert mem["predicted_bytes"]["staged"] > 0
    drift = mem["budget_drift_ratio"]["staged"]
    assert np.isfinite(drift) and drift > 0
    # the registry gauge carries the same ratio, labeled by estimator
    snap = snapshot()
    assert snap["budget_drift_ratio"]["est=KMeans:staged"] == pytest.approx(
        drift
    )


def test_budget_drift_across_cache_insert_evict_cycle(rng):
    """The device cache's n_dev+2 reservation is a byte-model prediction:
    an insert must record it (`budget_predicted_bytes{est=device_cache}`)
    and measure it (`budget_drift_ratio{est=device_cache}`), and the
    records survive an evict + re-insert cycle."""
    from spark_rapids_ml_tpu.parallel.device_cache import (
        clear_device_cache,
        get_device_cache,
        get_or_stage,
    )

    clear_device_cache()
    X = rng.normal(size=(600, 8)).astype(np.float32)
    try:
        entry = get_or_stage(X, None, None, np.float32, working_factor=2.0)
        assert entry is not None
        snap = snapshot()
        predicted = snap["budget_predicted_bytes"]["est=device_cache"]
        assert predicted == entry.nbytes > 0
        drift1 = snap["budget_drift_ratio"]["est=device_cache"]
        assert np.isfinite(drift1) and drift1 > 0
        # evict, then re-insert: the cycle re-records both sides
        get_device_cache().evict(entry.fingerprint)
        del entry
        entry2 = get_or_stage(X, None, None, np.float32, working_factor=2.0)
        assert entry2 is not None
        drift2 = snapshot()["budget_drift_ratio"]["est=device_cache"]
        assert np.isfinite(drift2) and drift2 > 0
        decisions = snapshot()["budget_decisions_total"]
        assert decisions.get("label=device_cache,over=false", 0) >= 2
    finally:
        clear_device_cache()


# ---------------------------------------------------------------------------
# compile telemetry: listener, labels, recompiles
# ---------------------------------------------------------------------------


def test_compile_listener_attributes_to_label_scope():
    """A fresh-shape jit compile inside a `compile_label` scope lands on
    `compile_seconds{fn=<label>}` and bumps `compiles_total`; the
    explicit `compile_span` path records phase=explicit."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.telemetry.compile import (
        compile_label,
        compile_span,
        install_jax_listener,
    )

    if not install_jax_listener():
        pytest.skip("jax.monitoring listener unavailable on this jax")
    before = snapshot()
    n = int(time.time()) % 97 + 131  # a shape this process never compiled
    with compile_label("unit_label"):
        jax.jit(lambda x: (x * 2).sum())(jnp.ones((n, 3)))
    d = delta(before, snapshot())
    fams = [ls for ls in d.get("compile_seconds", {}) if "fn=unit_label" in ls]
    assert fams, d.get("compile_seconds")
    assert any(
        "fn=unit_label" in ls for ls in d.get("compiles_total", {})
    )
    # the explicit span path records phase=explicit + a trace span
    reset_trace()
    with compile_span("explicit_seam"):
        pass
    assert any(
        e.name == "compile[explicit_seam]" for e in get_trace_events()
    )
    d2 = delta(before, snapshot())
    assert any(
        "fn=explicit_seam" in ls and "phase=explicit" in ls
        for ls in d2.get("compile_seconds", {})
    )


def test_recompiles_once_per_elastic_relower(tmp_path, rng):
    """Driven end to end via the `device_lost` fault kind: ONE elastic
    recovery re-lowers the staging programs exactly ONCE —
    `recompiles_total{fn=staging_programs,reason=elastic_shrink}` moves
    by 1, the report's compile section counts 1 recompile, and the
    marker sits inside the interrupted fit's span tree."""
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.resilience import fault_inject
    from spark_rapids_ml_tpu.resilience.elastic import reset_elastic

    set_config(
        checkpoint_dir=str(tmp_path / "ckpt"),
        retry_backoff_s=0.01,
        retry_jitter=0.0,
    )
    X = rng.normal(size=(400, 6)).astype(np.float32)
    df = pd.DataFrame({"features": list(X)})
    before = snapshot()
    try:
        with fault_inject("kmeans_lloyd", "device_lost", times=1, skip=3):
            m = KMeans(k=3, seed=7, maxIter=8, tol=0.0).fit(df)
        d = delta(before, snapshot())
        key = "fn=staging_programs,reason=elastic_shrink"
        assert d["recompiles_total"][key] == 1
        rep = m.fit_report()
        assert rep["compile"]["recompiles"] == 1
        assert rep["compile"]["recompiled"] == ["staging_programs"]

        def _names(nodes, out):
            for node in nodes:
                out.append(node["name"])
                _names(node.get("children", []), out)

        names: list = []
        _names(rep["spans"], names)
        assert names.count("recompile[staging_programs]") == 1
    finally:
        reset_elastic()


def test_profile_dir_cross_referenced_in_report(tmp_path, rng):
    """With `profile_dir` set the report names the XProf capture next to
    its run_id — the artifact and the trace stop being orphans."""
    from spark_rapids_ml_tpu.feature import PCA

    pdir = tmp_path / "xprof"
    set_config(profile_dir=str(pdir))
    X = rng.normal(size=(200, 6)).astype(np.float32)
    m = (
        PCA(k=2)
        .setInputCol("features")
        .setOutputCol("o")
        .fit(pd.DataFrame({"features": list(X)}))
    )
    rep = m.fit_report()
    assert rep["profile"]["dir"] == str(pdir)
    # the jax CPU profiler wrote a capture during the fit window
    assert rep["profile"].get("artifacts"), rep["profile"]
