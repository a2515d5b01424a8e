#
# Pod observatory tests (telemetry/fleet.py + its seams): heartbeat
# clock-offset estimation with the documented error bar, merged
# Perfetto traces (one track group per rank, monotone per track),
# pod-correlated pass ids + straggler attribution, deterministic pod
# incident ids with per-incident bundle dedupe and ring exchange,
# `file://` glob scrape targets, fleet-merged drift windows — and the
# 2-process acceptance runs: injected slowdown names the straggler,
# SIGKILL chaos yields exactly one incident-correlated bundle whose
# merged trace parses, and split shifted traffic scores drift exactly
# like one process over the combined rows with one alert per pod.
#
import glob
import json
import os
import signal
import socket
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fleet_reset():
    """Every test starts and ends with pristine fleet/pod/config state
    and an empty recorder ring."""
    from spark_rapids_ml_tpu.config import reset_config
    from spark_rapids_ml_tpu.resilience.pod import reset_pod
    from spark_rapids_ml_tpu.telemetry import utilization
    from spark_rapids_ml_tpu.telemetry.fleet import reset_fleet
    from spark_rapids_ml_tpu.telemetry.flight_recorder import RECORDER

    RECORDER.clear()
    utilization.clear()
    reset_fleet()
    reset_pod()
    reset_config()
    yield
    RECORDER.clear()
    reset_fleet()
    reset_pod()
    reset_config()


class FakeKV:
    """Dict-backed coordination-client stand-in (same string API as the
    pod tests' FakeKV: write-once set, bounded blocking get)."""

    def __init__(self, store=None):
        self.store = dict(store or {})
        self.gets = []

    def key_value_set(self, key, value):
        self.store.setdefault(key, value)

    def blocking_key_value_get(self, key, timeout_ms):
        self.gets.append(key)
        if key in self.store:
            return self.store[key]
        time.sleep(min(timeout_ms / 1000.0, 0.05))
        raise RuntimeError(f"DEADLINE_EXCEEDED: {key}")


# ---------------------------------------------------------------------------
# Clock-offset estimation
# ---------------------------------------------------------------------------


def test_clock_sample_rejects_legacy_beats():
    """Pre-observatory heartbeats wrote the literal "1": parsed as a
    float it is an implausible wall clock and must NOT poison the
    offset estimate."""
    from spark_rapids_ml_tpu.telemetry import fleet

    fleet.note_clock_sample(1, 1.0, time.time())
    fleet.note_clock_sample(1, 0.0, time.time())
    fleet.note_clock_sample(1, "not-a-clock", time.time())
    assert fleet.clock_offsets() == {}


def test_clock_offset_estimate_within_heartbeat_bar():
    """min(t_recv - ts_send) over samples estimates the peer skew with
    error bounded by the smallest delivery delay — itself bounded by
    the heartbeat interval.  A peer whose clock runs 3.7 s behind ours
    must come out within the documented bar."""
    from spark_rapids_ml_tpu.resilience.pod import heartbeat_interval_s
    from spark_rapids_ml_tpu.telemetry import fleet

    skew = 3.7  # local = peer + 3.7
    base = time.time()
    rng = np.random.default_rng(0)
    for i in range(20):
        t_recv = base + i
        delay = float(rng.uniform(0.0, 0.2))
        fleet.note_clock_sample(1, t_recv - skew - delay, t_recv)
    off, err = fleet.clock_offsets()[1]
    hb = heartbeat_interval_s()
    assert abs(off - skew) <= hb
    assert 0.0 <= err <= hb
    # the estimate over-shoots by at most the min delay, never under
    assert off >= skew


def test_probe_liveness_feeds_skewed_fakekv_clock():
    """End to end through the pod layer: a FakeKV holding beats whose
    values are a deliberately skewed wall clock must land in the
    estimator, corrected within the documented bar; a legacy "1" beat
    alongside is ignored."""
    from spark_rapids_ml_tpu.resilience.pod import (
        _probe_liveness, heartbeat_interval_s,
    )
    from spark_rapids_ml_tpu.telemetry import fleet

    skew = -2.5  # peer clock AHEAD of ours by 2.5 s
    client = FakeKV({
        "srmt/hb/1/0": repr(time.time() - skew),
        "srmt/hb/1/1": repr(time.time() - skew),
        "srmt/hb/2/0": "1",  # legacy peer
    })
    _probe_liveness(client, [0, 1, 2], 0)
    offs = fleet.clock_offsets()
    assert 2 not in offs, "legacy beat value must not produce an offset"
    off, err = offs[1]
    assert abs(off - skew) <= heartbeat_interval_s()
    assert err <= heartbeat_interval_s()


def test_merge_chrome_traces_monotone_and_labeled():
    """The merged trace keeps one track group per rank (pid = rank,
    process_name metadata), shifts peers uniformly (order within a
    track preserved), and documents the offsets in otherData."""
    from spark_rapids_ml_tpu.telemetry import fleet

    def mk(ts_list, pid):
        return {
            "traceEvents": [
                {"name": f"s{i}", "ph": "X", "ts": t, "dur": 1.0,
                 "pid": pid, "tid": 7, "args": {}}
                for i, t in enumerate(ts_list)
            ],
            "displayTimeUnit": "ms",
        }

    merged = fleet.merge_chrome_traces(
        {0: mk([100.0, 200.0, 300.0], 111),
         1: mk([150.0, 250.0, 350.0], 222)},
        offsets={1: (1.5, 0.2)},
    )
    # Perfetto-loadable: valid JSON, traceEvents present
    parsed = json.loads(json.dumps(merged))
    assert parsed["traceEvents"]
    xs = [e for e in parsed["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in xs} == {0, 1}
    names = [
        e for e in parsed["traceEvents"]
        if e["ph"] == "M" and e["name"] == "process_name"
    ]
    assert {e["args"]["name"] for e in names} == {"rank0", "rank1"}
    for rank in (0, 1):
        ts = [e["ts"] for e in xs if e["pid"] == rank]
        assert ts == sorted(ts), f"rank{rank} track not monotone"
    # rank 1 shifted by +1.5 s uniformly
    assert [e["ts"] for e in xs if e["pid"] == 1] == [
        150.0 + 1.5e6, 250.0 + 1.5e6, 350.0 + 1.5e6
    ]
    assert parsed["otherData"]["clock_offsets_s"]["1"] == [1.5, 0.2]


# ---------------------------------------------------------------------------
# Pass correlation + straggler attribution
# ---------------------------------------------------------------------------


def test_pass_id_stamps_trace_events():
    from spark_rapids_ml_tpu.telemetry import fleet
    from spark_rapids_ml_tpu.tracing import (
        current_pass_id, event, get_all_trace_events,
    )

    pid = fleet.begin_pod_pass()
    assert pid.startswith("pass-") and current_pass_id() == pid
    event("observatory_probe")
    assert fleet.complete_pod_pass() is not None
    assert current_pass_id() == ""  # cleared at pass close
    evs = [
        e for e in get_all_trace_events()
        if e.name == "observatory_probe"
    ]
    assert evs and evs[-1].pass_id == pid


def test_pass_report_phases_and_gauges_single_process():
    from spark_rapids_ml_tpu.telemetry import fleet, utilization
    from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

    utilization.clear()
    fleet.begin_pod_pass()
    t = time.perf_counter()
    # the report clips intervals to the pass window, so every endpoint
    # must already lie in the PAST when the pass completes
    time.sleep(0.09)
    utilization.note_interval("device", t, t + 0.05, cause="x")
    utilization.note_interval("host_prep", t, t + 0.02, cause="x")
    utilization.note_interval("reduce_wait", t + 0.05, t + 0.08, cause="x")
    rep = fleet.complete_pod_pass(run_id="r1")
    assert rep is not None and rep["run_id"] == "r1"
    phases = rep["ranks"]["0"]
    assert phases["device_accumulate"] == pytest.approx(0.05, abs=0.001)
    assert phases["decode"] == pytest.approx(0.02, abs=0.001)
    assert phases["reduce_wait"] == pytest.approx(0.03, abs=0.001)
    assert rep["slowest"]["device_accumulate"]["rank"] == 0
    samples = REGISTRY.get("pod_straggler_seconds").samples()
    key = (("phase", "device_accumulate"), ("rank", "0"))
    assert samples[key] == pytest.approx(0.05, abs=0.001)
    # stamp discipline for the fit report's last-run-state copy
    assert fleet.pass_report()["stamp"] >= rep["stamp"]


def test_pass_report_names_straggler_rank(monkeypatch):
    """2-rank exchange (seam monkeypatched): every rank computes the
    same table, and the slowest rank per phase is named."""
    from spark_rapids_ml_tpu.parallel import context
    from spark_rapids_ml_tpu.telemetry import fleet, utilization

    monkeypatch.setattr(context, "process_topology", lambda: (2, 0))

    def fake_reduce(tag, payload):
        assert tag == "pass_report"
        mine = json.loads(payload.decode("ascii"))
        peer = {
            "rank": 1,
            "pass_id": mine["pass_id"],
            "phases": {
                "decode": 0.01, "device_accumulate": 9.5,
                "reduce_wait": 0.0,
            },
        }
        return [payload, json.dumps(peer).encode("ascii")]

    monkeypatch.setattr(context, "reduce_blob_list", fake_reduce)
    utilization.clear()
    fleet.begin_pod_pass()
    t = time.perf_counter()
    time.sleep(0.05)  # interval endpoints must predate pass close
    utilization.note_interval("device", t, t + 0.03, cause="x")
    rep = fleet.complete_pod_pass()
    assert set(rep["ranks"]) == {"0", "1"}
    mine = rep["ranks"]["0"]["device_accumulate"]
    assert mine == pytest.approx(0.03, abs=0.001)
    assert rep["slowest"]["device_accumulate"]["rank"] == 1
    assert rep["slowest"]["device_accumulate"]["seconds"] == 9.5
    assert rep["slowest"]["device_accumulate"]["spread_s"] == (
        pytest.approx(9.5 - mine, abs=1e-5)
    )


# ---------------------------------------------------------------------------
# Incident ids, ring exchange, bundle dedupe
# ---------------------------------------------------------------------------


def test_incident_id_deterministic():
    from spark_rapids_ml_tpu.telemetry import fleet

    a = fleet.mint_incident_id("rank_loss", "dead=[1]", generation=2)
    b = fleet.mint_incident_id("rank_loss", "dead=[1]", generation=2)
    c = fleet.mint_incident_id("rank_loss", "dead=[1]", generation=3)
    assert a == b  # every survivor computes the same id, no comms
    assert a != c and a.startswith("inc-")


def test_exchange_incident_rings_absent_peers_named(monkeypatch):
    """The ring pull is deadline-bounded and best-effort: a dead rank's
    ring is missing and NAMED, a live peer's ring merges onto the
    common timeline, and the attachments parse."""
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.parallel import context
    from spark_rapids_ml_tpu.resilience import pod
    from spark_rapids_ml_tpu.telemetry import fleet

    store = {}
    peer_ring = {
        "traceEvents": [{
            "name": "peer_span", "ph": "X", "ts": 1e6, "dur": 5.0,
            "pid": 999, "tid": 3, "args": {},
        }],
        "displayTimeUnit": "ms",
    }
    store["inc/inc-test/1"] = json.dumps(peer_ring).encode("ascii")

    monkeypatch.setattr(context, "coordination_client", lambda: object())
    monkeypatch.setattr(
        context, "kv_publish", lambda k, p: store.setdefault(k, p)
    )

    def fake_fetch(key, timeout_ms, tag="", peer=None):
        if key in store:
            return store[key]
        raise RuntimeError(f"DEADLINE_EXCEEDED: {key}")

    monkeypatch.setattr(context, "kv_fetch", fake_fetch)
    monkeypatch.setattr(pod, "_current_boot_ranks", lambda: [0, 1, 2, 3])
    monkeypatch.setattr(pod, "_my_boot_rank", lambda: 0)
    set_config(pod_incident_ring_deadline_s=0.5)

    t0 = time.monotonic()
    att = fleet.exchange_incident_rings("inc-test", dead={2})
    assert time.monotonic() - t0 < 5.0  # bounded, never hangs
    info = att["pod_incident"]
    assert info["incident_id"] == "inc-test"
    assert info["ranks_present"] == [0, 1]
    assert "dead" in info["ranks_absent"]["2"]
    assert "3" in info["ranks_absent"]  # live-but-silent peer named too
    merged = json.loads(att["pod_trace.json"].decode("ascii"))
    assert any(
        e.get("name") == "peer_span" and e.get("pid") == 1
        for e in merged["traceEvents"]
    )
    # own ring published for the other survivors' pulls
    assert "inc/inc-test/0" in store


def test_note_failure_incident_dedupe_and_manifest(tmp_path):
    """Bundles of one pod incident share the id in their manifests, and
    one process never dumps the same incident twice — even under a
    DIFFERENT reason (the cascade: rank loss, then its reduce timeout)."""
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.telemetry.aggregate import (
        group_postmortems_by_incident,
    )
    from spark_rapids_ml_tpu.telemetry.flight_recorder import RECORDER

    set_config(flight_recorder_dir=str(tmp_path))
    b1 = RECORDER.note_failure("rank_loss", "x", incident_id="inc-77")
    assert b1 is not None
    with open(os.path.join(b1, "manifest.json")) as f:
        assert json.load(f)["incident_id"] == "inc-77"
    assert RECORDER.note_failure(
        "rank_loss", "again", incident_id="inc-77"
    ) is None
    assert RECORDER.note_failure(
        "reduce_timeout", "cascade", incident_id="inc-77"
    ) is None
    # a DIFFERENT incident under an un-cooled reason still dumps
    b2 = RECORDER.note_failure("reduce_timeout", "y", incident_id="inc-88")
    assert b2 is not None
    groups = group_postmortems_by_incident([str(tmp_path)])
    assert sorted(groups) == ["inc-77", "inc-88"]
    assert groups["inc-77"] == [b1] and groups["inc-88"] == [b2]


def test_group_postmortems_keys_plain_bundles_by_path(tmp_path):
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.telemetry.aggregate import (
        group_postmortems_by_incident,
    )
    from spark_rapids_ml_tpu.telemetry.flight_recorder import RECORDER

    set_config(flight_recorder_dir=str(tmp_path))
    b = RECORDER.note_failure("oom", "no pod dimension")
    groups = group_postmortems_by_incident([str(tmp_path)])
    assert groups == {b: [b]}


# ---------------------------------------------------------------------------
# file:// glob scrape targets
# ---------------------------------------------------------------------------


def test_scrape_endpoints_file_glob(tmp_path):
    """One pattern covers every rank's dump; zero matches is ABSENT
    under the pattern's own name (dead-rank semantics preserved)."""
    from spark_rapids_ml_tpu.telemetry.aggregate import (
        counter_total, scrape_endpoints,
    )

    page = '# TYPE retries_total counter\nretries_total{action="oom"} 3\n'
    for r in (0, 1, 2):
        (tmp_path / f"rank{r}.prom").write_text(page)
    res = scrape_endpoints({"pod": f"file://{tmp_path}/rank*.prom"})
    assert sorted(res.pages) == [
        "pod:rank0.prom", "pod:rank1.prom", "pod:rank2.prom"
    ]
    assert res.absent == {}
    assert counter_total(res.merged, "retries_total", action="oom") == 9

    gone = scrape_endpoints({"pod": f"file://{tmp_path}/nope*.prom"})
    assert gone.pages == {} and "pod" in gone.absent
    assert "no files matched" in gone.absent["pod"]

    # a literal (non-glob) file target keeps its given name
    one = scrape_endpoints({"r0": f"file://{tmp_path}/rank0.prom"})
    assert sorted(one.pages) == ["r0"]


# ---------------------------------------------------------------------------
# Fleet-merged drift windows
# ---------------------------------------------------------------------------


def _drift_seam(monkeypatch, store, nranks=2, rank=0, ranks=(0, 1)):
    from spark_rapids_ml_tpu.parallel import context
    from spark_rapids_ml_tpu.resilience import pod

    monkeypatch.setattr(
        context, "process_topology", lambda: (nranks, rank)
    )
    monkeypatch.setattr(context, "coordination_client", lambda: object())
    monkeypatch.setattr(
        context, "kv_publish", lambda k, p: store.setdefault(k, p)
    )

    def fake_fetch(key, timeout_ms, tag="", peer=None):
        if key in store:
            return store[key]
        raise RuntimeError(f"DEADLINE_EXCEEDED: {key}")

    monkeypatch.setattr(context, "kv_fetch", fake_fetch)
    monkeypatch.setattr(pod, "_current_boot_ranks", lambda: list(ranks))
    monkeypatch.setattr(pod, "_my_boot_rank", lambda: rank)


def test_fleet_drift_merge_matches_combined_rows(monkeypatch):
    """The acceptance property, seam-faked: rank 0's pod-merged
    drift_score over split traffic equals scoring the COMBINED rows in
    one process (rank-ordered sketch merge, exact at these row
    counts); the local partial stays visible under `process`."""
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.monitor.compare import divergence_table
    from spark_rapids_ml_tpu.monitor.fingerprint import (
        BaselineBuilder, builder_to_bytes,
    )
    from spark_rapids_ml_tpu.monitor.monitor import DriftMonitor
    from spark_rapids_ml_tpu.telemetry import fleet
    from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

    d = 3
    rng = np.random.default_rng(42)
    base_rows = rng.normal(size=(256, d))
    r0_rows = rng.normal(loc=2.0, size=(40, d))
    r1_rows = rng.normal(loc=-1.5, size=(40, d))

    bb = BaselineBuilder(d)
    bb.update(base_rows)
    baseline = bb.finalize([f"c{i}" for i in range(d)])

    store = {}
    _drift_seam(monkeypatch, store)
    # rank 1's closed window, already published on its monotonic key
    peer = BaselineBuilder(d)
    peer.update(r1_rows)
    store[f"drift/{fleet._drift_key('m')}/1/0"] = builder_to_bytes(peer)

    set_config(
        drift_window_s=0.05, drift_min_window_rows=1,
        drift_alert_threshold=0.0,
    )
    mon = DriftMonitor()
    mon.register("m", baseline)
    mon.observe("m", r0_rows)
    time.sleep(0.08)  # age the window past drift_window_s
    table = mon.refresh("m")
    assert table is not None
    assert table["window_rows"] == len(r0_rows) + len(r1_rows)

    # one process over the combined rows — must score identically
    ref = BaselineBuilder(d)
    ref.update(r0_rows)
    ref.update(r1_rows)
    ref_table = divergence_table(
        baseline, ref.finalize(baseline.columns), 8
    )
    assert table["overall"] == ref_table["overall"]

    partial = REGISTRY.get("drift_score_partial").samples()
    key = (("model", "m"), ("process", "0"))
    local_table = divergence_table(
        baseline, _local_view(r0_rows, d, baseline), 1
    )
    assert partial[key] == pytest.approx(local_table["overall"], abs=1e-9)
    mon.clear()


def _local_view(rows, d, baseline):
    from spark_rapids_ml_tpu.monitor.fingerprint import BaselineBuilder

    b = BaselineBuilder(d)
    b.update(rows)
    return b.finalize(baseline.columns)


def test_drift_alert_fires_once_per_pod(monkeypatch, tmp_path):
    """Only topology rank 0 dumps the sustained-breach bundle (under a
    deterministic incident id); every other rank computes the same
    breach and stays silent."""
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.monitor.fingerprint import BaselineBuilder
    from spark_rapids_ml_tpu.monitor.monitor import DriftMonitor

    d = 2
    rng = np.random.default_rng(1)
    bb = BaselineBuilder(d)
    bb.update(rng.normal(size=(256, d)))
    baseline = bb.finalize(["a", "b"])
    shifted = rng.normal(loc=30.0, size=(64, d))
    set_config(
        drift_window_s=1e-3, drift_min_window_rows=1,
        drift_alert_threshold=1e-6, drift_alert_sustain_s=0.0,
        flight_recorder_dir=str(tmp_path),
    )

    # rank 1: breach computed, bundle suppressed
    _drift_seam(monkeypatch, {}, nranks=2, rank=1, ranks=(0, 1))
    mon = DriftMonitor()
    mon.register("m", baseline)
    mon.observe("m", shifted)
    assert mon.refresh("m") is not None
    assert glob.glob(str(tmp_path / "postmortem_drift_*")) == []
    mon.clear()

    # rank 0: the pod's one bundle, incident id in the manifest
    _drift_seam(monkeypatch, {}, nranks=2, rank=0, ranks=(0, 1))
    mon0 = DriftMonitor()
    mon0.register("m", baseline)
    mon0.observe("m", shifted)
    assert mon0.refresh("m") is not None
    bundles = glob.glob(str(tmp_path / "postmortem_drift_*"))
    assert len(bundles) == 1
    with open(os.path.join(bundles[0], "manifest.json")) as f:
        assert json.load(f)["incident_id"].startswith("inc-")
    mon0.clear()


# ---------------------------------------------------------------------------
# 2-process acceptance (coordination service only)
# ---------------------------------------------------------------------------

_COMMON_PRELUDE = textwrap.dedent(
    """
    import json, os, signal, sys, time
    pid, nproc, port, outfile = (
        int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4]
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    sys.path.insert(0, os.environ["SRMT_REPO"])
    import numpy as np
    # pyarrow is first imported HERE, on the main thread.  The package
    # imports it lazily inside the chunk generators, which run on a
    # prefetch thread that ends with its pass; with pyarrow 25 the next
    # ParquetFile opened more than ~0.1 s after the thread of its first
    # import has exited dies by SIGSEGV (shown outside this repo by two
    # threads, one after the other, each opening a file).  The retried
    # pass of a chaos run is such an open, and it lost rank 0 whenever
    # the machine was busy.  Every real entry point has touched pyarrow
    # on a long-lived thread by then (pandas, a metadata read).
    import pyarrow  # noqa: F401
    from spark_rapids_ml_tpu import init_distributed
    from spark_rapids_ml_tpu.config import set_config
    """
)

_STRAGGLER_WORKER = _COMMON_PRELUDE + textwrap.dedent(
    """
    ppath, tracedir = sys.argv[5], sys.argv[6]
    set_config(
        coordinator_address=f"127.0.0.1:{port}", num_processes=nproc,
        process_id=pid, multiproc_reduce="wire",
        multiproc_reduce_timeout_s=60.0, fused_parquet_readers=1,
        pod_elastic="on", pod_heartbeat_interval_s=0.25,
        pod_death_grace_s=5.0,
    )
    assert init_distributed()

    if pid == 1:
        # the injected slowdown: stretch rank 1's DEVICE-ACCUMULATE
        # window (baseline.fold_chunk runs inside the timed device
        # step), so the straggler table must name rank 1 there
        from spark_rapids_ml_tpu.monitor import baseline as _b
        _orig = _b.fold_chunk
        def _slow(cX, cw):
            time.sleep(0.25)
            return _orig(cX, cw)
        _b.fold_chunk = _slow

    d = 4
    from spark_rapids_ml_tpu.fused import (
        fused_linreg_stats, iter_parquet_chunks,
    )

    def producer(n_dev):
        prep = {"s": 0.0, "iv": []}
        return (
            iter_parquet_chunks(
                ppath, "features", (), "label", None, 128, np.float64,
                prep=prep,
            ),
            prep,
        )

    fused_linreg_stats(producer, d, np.float64)
    from spark_rapids_ml_tpu.telemetry import fleet
    rep = fleet.pass_report()

    # every rank dumps its own trace; rank 0 merges after the barrier
    from spark_rapids_ml_tpu.telemetry.exporters import dump_chrome_trace
    tpath = os.path.join(tracedir, f"rank{pid}_trace.json")
    dump_chrome_trace(tpath)
    from spark_rapids_ml_tpu.parallel.context import allgather_bytes
    allgather_bytes("traces_done", b"x")

    if pid == 0:
        traces = {}
        for r in range(nproc):
            with open(os.path.join(tracedir, f"rank{r}_trace.json")) as f:
                traces[r] = json.load(f)
        merged = fleet.merge_chrome_traces(traces)
        with open(outfile, "w") as f:
            json.dump({
                "report": rep,
                "merged": merged,
                "offsets": {
                    str(k): list(v) for k, v in fleet.clock_offsets().items()
                },
            }, f)
    # normal exit: the atexit jax.distributed shutdown barrier holds
    # every rank until ALL reach it, so no rank outlives the
    # coordinator and trips the fatal-error poller
    """
)

_CHAOS_OBSERVATORY_WORKER = _COMMON_PRELUDE + textwrap.dedent(
    """
    ppath, frdir = sys.argv[5], sys.argv[6]
    set_config(
        coordinator_address=f"127.0.0.1:{port}", num_processes=nproc,
        process_id=pid, multiproc_reduce="wire",
        multiproc_reduce_timeout_s=60.0, fused_parquet_readers=1,
        pod_elastic="on", pod_heartbeat_interval_s=0.25,
        pod_death_grace_s=2.0,
        flight_recorder_dir=(frdir if pid == 0 else ""),
    )
    assert init_distributed()

    if pid == 1:
        from spark_rapids_ml_tpu import resilience as _res
        _real = _res.maybe_inject
        _hits = {"n": 0}
        def _killer(site):
            if site == "fused_accumulate":
                _hits["n"] += 1
                if _hits["n"] >= 2:
                    os.kill(os.getpid(), signal.SIGKILL)
            return _real(site)
        _res.maybe_inject = _killer

    d = 4
    from spark_rapids_ml_tpu.fused import (
        fused_linreg_stats, iter_parquet_chunks,
    )

    def producer(n_dev):
        prep = {"s": 0.0, "iv": []}
        return (
            iter_parquet_chunks(
                ppath, "features", (), "label", None, 128, np.float64,
                prep=prep,
            ),
            prep,
        )

    from spark_rapids_ml_tpu.resilience import retry
    retry.retry_call(
        lambda: fused_linreg_stats(producer, d, np.float64),
        label="chaos_obs",
    )

    if pid == 0:
        import glob as _g
        from spark_rapids_ml_tpu.telemetry import fleet
        bundles = sorted(
            _g.glob(os.path.join(frdir, "postmortem_rank_loss_*"))
        )
        out = {"bundles": [os.path.basename(b) for b in bundles],
               "report": fleet.pass_report()}
        if bundles:
            b = bundles[0]
            with open(os.path.join(b, "manifest.json")) as f:
                out["manifest"] = json.load(f)
            pt = os.path.join(b, "pod_trace.json")
            if os.path.exists(pt):
                with open(pt) as f:
                    out["pod_trace"] = json.load(f)
            pi = os.path.join(b, "pod_incident.json")
            if os.path.exists(pi):
                with open(pi) as f:
                    out["pod_incident"] = json.load(f)
        with open(outfile, "w") as f:
            json.dump(out, f)
    sys.stdout.flush(); sys.stderr.flush()
    os._exit(0)
    """
)

_DRIFT_WORKER = _COMMON_PRELUDE + textwrap.dedent(
    """
    frdir = sys.argv[5]
    my_fr = os.path.join(frdir, f"r{pid}")
    os.makedirs(my_fr, exist_ok=True)
    set_config(
        coordinator_address=f"127.0.0.1:{port}", num_processes=nproc,
        process_id=pid, multiproc_reduce="wire",
        multiproc_reduce_timeout_s=60.0,
        pod_elastic="on", pod_heartbeat_interval_s=0.25,
        pod_death_grace_s=5.0,
        drift_window_s=0.3, drift_min_window_rows=1,
        drift_alert_threshold=0.05, drift_alert_sustain_s=0.0,
        flight_recorder_dir=my_fr,
    )
    assert init_distributed()

    d = 3
    rng = np.random.default_rng(42)      # same on both ranks
    base_rows = rng.normal(size=(256, d))
    traffic = rng.normal(loc=3.0, size=(80, d))  # shifted vs baseline

    from spark_rapids_ml_tpu.monitor.fingerprint import BaselineBuilder
    from spark_rapids_ml_tpu.monitor.monitor import MONITOR
    bb = BaselineBuilder(d)
    bb.update(base_rows)
    baseline = bb.finalize([f"c{i}" for i in range(d)])
    MONITOR.register("m", baseline)

    # shifted traffic SPLIT across the pod: rank r serves every other row
    MONITOR.observe("m", traffic[pid::nproc])
    time.sleep(0.4)                      # age the window past close
    MONITOR.refresh("m")                 # rolls + publishes the blob

    from spark_rapids_ml_tpu.parallel.context import allgather_bytes
    from spark_rapids_ml_tpu.telemetry import fleet
    allgather_bytes("drift_published", b"x")

    table = None
    for _ in range(40):                  # pull until the peer blob lands
        if len(fleet.fetch_peer_drift_windows("m")) >= nproc - 1:
            table = MONITOR.refresh("m")
            break
        time.sleep(0.1)
    assert table is not None, "peer drift blob never arrived"
    allgather_bytes("drift_scored", b"x")

    if pid == 0:
        with open(outfile, "w") as f:
            json.dump({
                "overall": table["overall"],
                "window_rows": table["window_rows"],
            }, f)
    # normal exit: the shutdown barrier keeps ranks in lockstep
    """
)


def _launch_pod(script_body, nproc, tmp_path, args=(), timeout=420,
                allow_sigkill=False):
    """Run `nproc` worker processes against a local coordination
    service.  Rank 0 must exit 0; with `allow_sigkill`, a higher rank
    dying by SIGKILL is the expected chaos, otherwise every rank must
    exit cleanly."""
    script = tmp_path / "observatory_worker.py"
    script.write_text(script_body)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    outfile = tmp_path / "observatory_out.json"
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    env["SRMT_REPO"] = REPO
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i), str(nproc), str(port),
             str(outfile), *[str(a) for a in args]],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        for i in range(nproc)
    ]
    errs = []
    for p in procs:
        try:
            _, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
                try:
                    q.communicate(timeout=10)
                except Exception:
                    pass
            raise
        errs.append((p.returncode, err))
    assert errs[0][0] == 0, errs[0][1][-6000:]
    if allow_sigkill:
        assert any(rc == -signal.SIGKILL for rc, _ in errs[1:]), [
            rc for rc, _ in errs
        ]
    else:
        for rc, err in errs[1:]:
            assert rc == 0, err[-6000:]
    with open(outfile) as f:
        return json.load(f)


def _write_chaos_parquet(tmp_path, n=1000, d=4):
    import pandas as pd

    rng = np.random.default_rng(7)
    X = rng.integers(-10, 10, size=(n, d)).astype(np.float64)
    y = rng.integers(-10, 10, size=n).astype(np.float64)
    ppath = str(tmp_path / "obs.parquet")
    pd.DataFrame({"features": list(X), "label": y}).to_parquet(
        ppath, row_group_size=125
    )
    return ppath


def test_two_rank_straggler_table_and_merged_trace(tmp_path):
    """The pod-observatory smoke: a 2-rank fused fit with an injected
    device-side slowdown on rank 1 — the straggler table (same on
    every rank) names rank 1 for device_accumulate, and the merged
    per-rank trace dumps form one Perfetto-loadable timeline with both
    ranks' pass spans sharing one pod pass id."""
    ppath = _write_chaos_parquet(tmp_path)
    tracedir = tmp_path / "traces"
    tracedir.mkdir()
    out = _launch_pod(
        _STRAGGLER_WORKER, 2, tmp_path, args=(ppath, str(tracedir)),
    )
    rep = out["report"]
    assert set(rep["ranks"]) == {"0", "1"}
    assert rep["slowest"]["device_accumulate"]["rank"] == 1
    assert rep["slowest"]["device_accumulate"]["spread_s"] > 0.5

    merged = out["merged"]
    # every rank contributes at least its pass-begin instant (X spans
    # are wait-gated — the SLOW rank may legitimately never wait)
    stamped = [
        e for e in merged["traceEvents"] if e.get("ph") in ("X", "i")
    ]
    assert {e["pid"] for e in stamped} == {0, 1}
    per_track = {}
    for e in stamped:
        if e.get("ph") == "X":
            per_track.setdefault((e["pid"], e["tid"]), []).append(e["ts"])
    for (rank, tid), ts in per_track.items():
        assert ts == sorted(ts), f"rank{rank}/tid{tid} not monotone"
    # cross-rank correlation: one pod pass id on spans of BOTH ranks
    ids = {
        rank: {
            e["args"]["pass_id"]
            for e in merged["traceEvents"]
            if e.get("pid") == rank and e.get("args", {}).get("pass_id")
        }
        for rank in (0, 1)
    }
    assert ids[0] & ids[1], f"no shared pass id across ranks: {ids}"
    assert rep["pass_id"] in (ids[0] & ids[1])


def test_two_rank_chaos_one_incident_bundle(tmp_path):
    """SIGKILL chaos variant: rank 1 dies mid-accumulate; the survivor
    writes exactly ONE rank_loss bundle carrying the incident id, its
    merged pod trace parses (Perfetto-loadable), the dead rank's ring
    is named absent, and the retried pass still yields a pass
    report."""
    ppath = _write_chaos_parquet(tmp_path, n=4000)
    frdir = tmp_path / "fr"
    out = _launch_pod(
        _CHAOS_OBSERVATORY_WORKER, 2, tmp_path,
        args=(ppath, str(frdir)), allow_sigkill=True,
    )
    assert len(out["bundles"]) == 1, out["bundles"]
    manifest = out["manifest"]
    assert manifest["reason"] == "rank_loss"
    assert manifest["incident_id"].startswith("inc-")
    assert "pod_trace.json" in manifest.get("attachments", ())
    trace = out["pod_trace"]
    assert trace["traceEvents"], "merged pod trace is empty"
    assert {
        e["pid"] for e in trace["traceEvents"] if e.get("ph") == "X"
    } == {0}, "only the survivor's ring can be present"
    incident = out["pod_incident"]
    assert incident["incident_id"] == manifest["incident_id"]
    assert "1" in incident["ranks_absent"]  # the corpse, named
    # the retried (post-shrink) pass still closed with a report
    assert out["report"].get("ranks", {}).get("0")


def test_two_rank_fleet_drift_parity_and_single_alert(tmp_path):
    """Fleet drift acceptance: shifted traffic split across 2 ranks
    scores EXACTLY like one process over the combined rows (the sketch
    wire merge is exact at these row counts), and the sustained breach
    produces exactly one drift bundle across the whole pod."""
    frdir = tmp_path / "fr"
    frdir.mkdir()
    out = _launch_pod(_DRIFT_WORKER, 2, tmp_path, args=(str(frdir),))

    d = 3
    rng = np.random.default_rng(42)  # the workers' exact generator
    base_rows = rng.normal(size=(256, d))
    traffic = rng.normal(loc=3.0, size=(80, d))
    from spark_rapids_ml_tpu.monitor.compare import divergence_table
    from spark_rapids_ml_tpu.monitor.fingerprint import BaselineBuilder

    bb = BaselineBuilder(d)
    bb.update(base_rows)
    baseline = bb.finalize([f"c{i}" for i in range(d)])
    ref = BaselineBuilder(d)
    ref.update(traffic[0::2])
    ref.update(traffic[1::2])
    ref_table = divergence_table(baseline, ref.finalize(baseline.columns), 8)

    assert out["window_rows"] == len(traffic)
    assert out["overall"] == ref_table["overall"]
    bundles = glob.glob(str(frdir / "*" / "postmortem_drift_*"))
    assert len(bundles) == 1, bundles
