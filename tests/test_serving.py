#
# Serving layer (spark_rapids_ml_tpu/serving/) — micro-batch coalescing
# parity, admission control, model residency (pin / LRU-evict / re-pin,
# zero weight re-staging across requests), latency metric families, the
# HTTP front end, and fault-injected degradation (OOM shrinks the
# coalescing cap, device_lost drains the queue on the elastic-shrunken
# mesh) — all on the 8-device CPU mesh.
#
import threading
import time

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu.classification import LogisticRegression
from spark_rapids_ml_tpu.config import reset_config, set_config
from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.parallel.mesh import active_devices
from spark_rapids_ml_tpu.resilience import fault_inject
from spark_rapids_ml_tpu.resilience.elastic import reset_elastic
from spark_rapids_ml_tpu.serving import (
    ServingClient,
    ServingOverload,
    ServingServer,
)
from spark_rapids_ml_tpu.serving.registry import PINS
from spark_rapids_ml_tpu.telemetry import dump_prometheus, parse_prometheus


@pytest.fixture(autouse=True)
def _clean():
    reset_config()
    set_config(retry_backoff_s=0.01, retry_jitter=0.0)
    yield
    reset_config()
    reset_elastic()
    # the external-reservation ledger is process-global: a registry a
    # test abandoned (without registry.clear()) must not starve the next
    # test's tiny device_cache_bytes budget
    from spark_rapids_ml_tpu.parallel.device_cache import get_device_cache

    cache = get_device_cache()
    for tag in list(cache._external):
        cache.release_external(tag)


@pytest.fixture(scope="module")
def rng_m():
    return np.random.default_rng(7)


# d=16: wide enough that the weight matrices clear the registry's
# _PIN_MIN_BYTES scalar cutoff (the pinning under test must happen)
_D = 16


@pytest.fixture(scope="module")
def pca_model(rng_m):
    X = rng_m.normal(size=(300, _D)).astype(np.float32)
    df = pd.DataFrame({"features": list(X)})
    return PCA(k=3).setInputCol("features").setOutputCol("proj").fit(df)


@pytest.fixture(scope="module")
def logreg_model(rng_m):
    X = rng_m.normal(size=(300, _D)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    df = pd.DataFrame({"features": list(X), "label": y})
    return LogisticRegression(maxIter=25).fit(df)


def _serve(**models) -> ServingServer:
    server = ServingServer()
    for name, model in models.items():
        server.register(name, model)
    return server.start()


def _q(rng, n=1, d=_D):
    return rng.normal(size=(n, d)).astype(np.float32)


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------


def test_single_request_matches_direct_transform(pca_model, rng):
    server = _serve(pca=pca_model)
    try:
        q = _q(rng, 5)
        out = server.transform("pca", q, timeout=60)
        ref = pca_model._transform_array(q)
        assert sorted(out) == sorted(ref)
        assert np.array_equal(out["proj"], ref["proj"])
        # the client surface: single-output models return the bare array
        client = ServingClient(server)
        assert np.array_equal(client.transform("pca", q), ref["proj"])
        assert client.models() == ["pca"]
    finally:
        server.stop()


def test_multi_output_model_all_columns(logreg_model, rng):
    server = _serve(lr=logreg_model)
    try:
        q = _q(rng, 7)
        out = server.transform("lr", q, timeout=60)
        ref = logreg_model._transform_array(q)
        assert sorted(out) == sorted(ref)
        for col in ref:
            assert np.array_equal(out[col], ref[col]), col
    finally:
        server.stop()


def test_coalescing_parity_n_concurrent_rows_exact(pca_model, rng):
    """N concurrent 1-row requests coalesce into ONE dispatched batch
    whose per-request slices are EXACTLY the one-shot batched transform
    of the same rows (same staging layout, same compiled program)."""
    server = _serve(pca=pca_model)
    try:
        rows = [_q(rng, 1) for _ in range(16)]
        server.pause()  # deterministic coalescing: all 16 queue first
        # futs[i] belongs to rows[i] BY INDEX: appends land in thread-
        # completion order, which the GIL does not promise matches the
        # submission order (the parity check below is per-request)
        futs = [None] * len(rows)

        def _submit(i, r):
            futs[i] = server.submit("pca", r)

        threads = [
            threading.Thread(target=_submit, args=(i, r))
            for i, r in enumerate(rows)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        b0 = server._batches
        server.resume()
        outs = [f.result(timeout=60)["proj"] for f in futs]
        assert server._batches - b0 == 1, "16 requests must be one batch"
        got = np.concatenate(outs, axis=0)
        # submit order is thread-scheduling dependent; compare as rows
        want = pca_model._transform_array(
            np.concatenate(rows, axis=0)
        )["proj"]
        for r, o in zip(rows, outs):
            one = pca_model._transform_array(r)["proj"]
            assert np.array_equal(o, one)
        assert got.shape == want.shape
    finally:
        server.stop()


def test_coalesced_batch_equals_batched_transform_exact(pca_model, rng):
    """Order-pinned version: sequential submits while paused — the
    concatenated scatter equals one batched transform bit-for-bit."""
    server = _serve(pca=pca_model)
    try:
        rows = [_q(rng, 1) for _ in range(12)]
        server.pause()
        futs = [server.submit("pca", r) for r in rows]
        server.resume()
        got = np.concatenate(
            [f.result(timeout=60)["proj"] for f in futs], axis=0
        )
        want = pca_model._transform_array(
            np.concatenate(rows, axis=0)
        )["proj"]
        assert np.array_equal(got, want)
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# residency
# ---------------------------------------------------------------------------


def test_zero_weight_restaging_across_100_requests(pca_model, rng):
    """A pinned model's weights move to the mesh exactly ONCE: 100
    requests later the pin count is still 1 (no evict, no re-pin)."""
    server = _serve(zr_pca=pca_model)
    try:
        for _ in range(100):
            server.transform("zr_pca", _q(rng, 1), timeout=60)
        assert PINS.value(model="zr_pca", event="pin") == 1
        assert PINS.value(model="zr_pca", event="repin") == 0
        assert PINS.value(model="zr_pca", event="evict") == 0
        rep = server.report()
        assert rep["zr_pca"]["requests"] == 100
        assert rep["zr_pca"]["pinned"] is True
    finally:
        server.stop()


def test_lru_eviction_and_transparent_repin(pca_model, logreg_model, rng):
    """Under budget pressure the registry LRU-evicts a pinned model
    (releasing its external reservation); the next request for it
    transparently re-pins and still answers correctly."""
    server = ServingServer()
    server.register("ev_a", pca_model)
    server.register("ev_b", logreg_model)
    bytes_a = server.registry.resolve("ev_a").nbytes
    bytes_b = server.registry.resolve("ev_b").nbytes
    server.registry.clear()
    # room for the larger model alone, never for both
    set_config(device_cache_bytes=int(max(bytes_a, bytes_b) * 1.2))
    server.register("ev_a", pca_model)
    server.register("ev_b", logreg_model)  # does not fit next to ev_a
    assert PINS.value(model="ev_a", event="evict") == 1
    assert server.registry.pinned_names() == ["ev_b"]
    server.start()
    try:
        q = _q(rng, 3)
        out = server.transform("ev_a", q, timeout=60)  # re-pin on demand
        assert PINS.value(model="ev_a", event="repin") == 1
        assert np.array_equal(
            out["proj"], pca_model._transform_array(q)["proj"]
        )
        assert "ev_a" in server.registry.pinned_names()
    finally:
        server.stop()


def test_pinned_bytes_are_budget_accounted(pca_model):
    from spark_rapids_ml_tpu.parallel.device_cache import (
        cache_resident_bytes,
    )

    base = cache_resident_bytes()
    server = ServingServer()
    server.register("acct", pca_model)
    nbytes = server.registry.resolve("acct").nbytes
    assert nbytes > 0
    assert cache_resident_bytes() - base == nbytes
    server.registry.clear()
    assert cache_resident_bytes() == base


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_admission_control_rejects_then_recovers(pca_model, rng):
    set_config(serving_max_queue=3)
    server = _serve(adm=pca_model)
    try:
        server.pause()
        futs = [server.submit("adm", _q(rng, 1)) for _ in range(3)]
        with pytest.raises(ServingOverload) as ei:
            server.submit("adm", _q(rng, 1))
        assert ei.value.reason == "queue_full"
        from spark_rapids_ml_tpu.serving.server import REJECTIONS

        assert REJECTIONS.value(model="adm", reason="queue_full") >= 1
        server.resume()
        for f in futs:
            f.result(timeout=60)  # queued work survives the rejection
        server.transform("adm", _q(rng, 1), timeout=60)  # gate reopened
    finally:
        server.stop()


def test_submit_validation(pca_model, rng):
    server = _serve(val=pca_model)
    try:
        with pytest.raises(KeyError):
            server.submit("nope", _q(rng, 1))
        with pytest.raises(ValueError):
            server.submit("val", np.zeros((1, 5), np.float32))  # wrong d
        with pytest.raises(ValueError):
            server.submit("val", np.zeros((0, _D), np.float32))
    finally:
        server.stop()
    with pytest.raises(ServingOverload):
        server.submit("val", _q(rng, 1))  # stopped server


def test_failed_request_does_not_kill_server(pca_model, rng):
    """A fatal per-batch error fails THOSE futures; the server keeps
    serving."""

    def boom(X):
        raise ValueError("bad batch")

    from spark_rapids_ml_tpu.knn import NearestNeighbors

    knn = NearestNeighbors(k=2).fit(
        np.random.default_rng(1).normal(size=(50, _D)).astype(np.float32)
    )
    server = ServingServer()
    server.register("ok", pca_model)
    # a host-path model whose dispatch callable always fails
    server.register("boom", knn, n_features=_D, transform=boom)
    server.start()
    try:
        f = server.submit("boom", _q(rng, 1))
        with pytest.raises(ValueError, match="bad batch"):
            f.result(timeout=60)
        out = server.transform("ok", _q(rng, 2), timeout=60)
        assert out["proj"].shape == (2, 3)
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# metrics / report
# ---------------------------------------------------------------------------


def test_latency_families_and_report(pca_model, rng):
    server = _serve(met=pca_model)
    try:
        for _ in range(5):
            server.transform("met", _q(rng, 2), timeout=60)
        parsed = parse_prometheus(dump_prometheus())
        pre = "spark_rapids_ml_tpu_"
        for phase in ("queue", "dispatch", "total"):
            key = (
                pre + "serving_request_latency_seconds_count",
                (("model", "met"), ("phase", phase)),
            )
            assert parsed.get(key, 0) == 5, (phase, key)
        assert parsed[
            (pre + "serving_batch_rows_count", (("model", "met"),))
        ] >= 1
        assert parsed[
            (pre + "serving_requests_total", (("model", "met"),))
        ] == 5
        assert (pre + "serving_pinned_models", ()) in parsed
        rep = server.report()["met"]
        assert rep["latency_samples"] == 5
        assert rep["p50_ms"] > 0 and rep["p99_ms"] >= rep["p50_ms"]
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# degradation under injected faults
# ---------------------------------------------------------------------------


def test_injected_oom_shrinks_coalescing_cap(pca_model, rng):
    server = _serve(oomm=pca_model)
    try:
        cap0 = int(
            __import__(
                "spark_rapids_ml_tpu.config", fromlist=["get_config"]
            ).get_config("serving_max_batch_rows")
        )
        with fault_inject("serving_dispatch", "oom", times=1):
            out = server.transform("oomm", _q(rng, 4), timeout=60)
        assert out["proj"].shape == (4, 3)  # the request survived
        assert server._shrunk_cap is not None
        assert server._shrunk_cap <= cap0 // 2
        from spark_rapids_ml_tpu.resilience.retry import RETRIES

        assert RETRIES.value(label="serving_dispatch", action="oom") >= 1
    finally:
        server.stop()


def test_oom_cap_regrows_after_sustained_clean_batches(pca_model, rng):
    """One transient OOM must not cap coalescing for the process
    lifetime: sustained clean batches double the cap back up."""
    import spark_rapids_ml_tpu.serving.server as srv_mod

    server = _serve(regrow=pca_model)
    try:
        with fault_inject("serving_dispatch", "oom", times=1):
            server.transform("regrow", _q(rng, 2), timeout=60)
        assert server._shrunk_cap is not None
        for _ in range(srv_mod._CAP_REGROW_BATCHES * 2):
            server._note_clean_batch()
        assert server._shrunk_cap is None  # fully restored
    finally:
        server.stop()


def test_device_lost_mid_load_drains_queue_on_shrunk_mesh(pca_model, rng):
    """An injected device loss mid-load: elastic recovery shrinks the
    mesh, every pinned model re-pins on the survivors, and EVERY queued
    request completes — none lost, none erred."""
    n_before = len(active_devices())
    server = _serve(dl_pca=pca_model)
    try:
        server.pause()
        rows = [_q(rng, 1) for _ in range(20)]
        futs = [server.submit("dl_pca", r) for r in rows]
        with fault_inject("serving_dispatch", "device_lost", times=1):
            server.resume()
            outs = [f.result(timeout=120) for f in futs]
        assert len(outs) == 20
        assert len(active_devices()) == n_before - 1
        assert PINS.value(model="dl_pca", event="repin") >= 1
        # degraded-mesh answers still match the reference transform
        for r, o in zip(rows, outs):
            ref = pca_model._transform_array(r)["proj"]
            np.testing.assert_allclose(o["proj"], ref, rtol=1e-5)
    finally:
        server.stop()
        reset_elastic()


def test_unregister_with_queued_requests_fails_them_not_the_server(
    pca_model, rng
):
    """Unregistering a model with requests still queued must FAIL those
    futures (KeyError at dispatch) and leave the dispatcher serving —
    not kill the thread and hang every future forever."""
    server = _serve(gone=pca_model, stay=pca_model)
    try:
        server.pause()
        doomed = [server.submit("gone", _q(rng, 1)) for _ in range(3)]
        ok = server.submit("stay", _q(rng, 1))
        server.registry.unregister("gone")
        server.resume()
        for f in doomed:
            with pytest.raises(KeyError):
                f.result(timeout=60)
        assert ok.result(timeout=60)["proj"].shape == (1, 3)
        # the dispatcher survived: fresh traffic still flows
        server.transform("stay", _q(rng, 2), timeout=60)
    finally:
        server.stop()


def test_width_blind_model_adopts_first_request_width(rng):
    """A model registered without n_features pins the FIRST request's
    width; a later mismatched request is rejected at admission instead
    of poisoning a coalesced batch."""

    def echo(X):
        return {"rows": np.asarray(X).sum(axis=1)}

    from spark_rapids_ml_tpu.knn import NearestNeighbors

    knn = NearestNeighbors(k=2).fit(
        np.random.default_rng(2).normal(size=(30, 4)).astype(np.float32)
    )
    server = ServingServer()
    server.register("wide", knn, transform=echo)
    # blank the width the registration inferred from the model's n_cols:
    # the case under test is a registration with NO known width
    server.registry._host["wide"]["n_features"] = None
    server.start()
    try:
        server.transform("wide", np.zeros((1, 6), np.float32), timeout=60)
        with pytest.raises(ValueError, match="expects 6 features"):
            server.submit("wide", np.zeros((1, 4), np.float32))
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# host-path models + HTTP front end
# ---------------------------------------------------------------------------


def test_host_path_model_with_custom_transform(rng):
    """Models without a device transform (kNN-style) serve through a
    caller-provided host callable; coalescing still applies."""
    from spark_rapids_ml_tpu.knn import NearestNeighbors

    X = rng.normal(size=(200, 8)).astype(np.float32)
    knn = NearestNeighbors(k=3).fit(X)

    def nn_transform(Q):
        dist, pos = knn._search(np.asarray(Q, np.float32), 3)
        return {"distances": dist, "indices": pos}

    server = ServingServer()
    server.register("knn", knn, n_features=8, transform=nn_transform)
    server.start()
    try:
        assert server.registry.resolve("knn").device is False
        q = X[:5] + 1e-6
        out = server.transform("knn", q, timeout=60)
        assert out["indices"].shape == (5, 3)
        assert np.array_equal(out["indices"][:, 0], np.arange(5))
    finally:
        server.stop()


def test_http_endpoint_roundtrip(pca_model, rng):
    import json
    import urllib.error
    import urllib.request

    from spark_rapids_ml_tpu.serving.http import start_serving_http

    server = _serve(web=pca_model)
    http = start_serving_http(server, port=0)
    base = f"http://127.0.0.1:{http.server_port}"
    try:
        q = _q(rng, 3)
        body = json.dumps({"instances": q.tolist()}).encode()
        req = urllib.request.Request(
            f"{base}/v1/models/web:transform", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            payload = json.load(resp)
        assert payload["model"] == "web" and payload["rows"] == 3
        np.testing.assert_allclose(
            np.asarray(payload["outputs"]["proj"], np.float32),
            pca_model._transform_array(q)["proj"],
            rtol=1e-6,
        )
        with urllib.request.urlopen(f"{base}/v1/models", timeout=30) as r:
            assert "web" in json.load(r)["models"]
        with urllib.request.urlopen(f"{base}/v1/report", timeout=30) as r:
            assert json.load(r)["web"]["requests"] >= 1
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                urllib.request.Request(
                    f"{base}/v1/models/nope:transform", data=body
                ),
                timeout=30,
            )
        assert ei.value.code == 404
    finally:
        http.shutdown()
        http.server_close()
        server.stop()


# ---------------------------------------------------------------------------
# request-scoped tracing, exemplars, slow capture, SLO burn rate
# ---------------------------------------------------------------------------


def test_request_ids_minted_and_adoptable(pca_model, rng):
    server = _serve(rid=pca_model)
    try:
        fut = server.submit("rid", _q(rng))
        assert fut.request_id.startswith("req-")
        fut.result(timeout=60)
        fut2 = server.submit("rid", _q(rng), request_id="caller-7")
        assert fut2.request_id == "caller-7"
        fut2.result(timeout=60)
        client = ServingClient(server)
        fut3 = client.submit("rid", _q(rng), request_id="client-9")
        assert fut3.request_id == "client-9"
        fut3.result(timeout=60)
    finally:
        server.stop()


def test_latency_exemplars_carry_request_ids(pca_model, rng):
    from spark_rapids_ml_tpu.serving.server import LATENCY

    server = _serve(exm=pca_model)
    try:
        fut = server.submit("exm", _q(rng), request_id="exemplar-probe")
        fut.result(timeout=60)
        for phase in ("queue", "dispatch", "total"):
            ex = LATENCY.exemplars(model="exm", phase=phase)
            assert any(e["id"] == "exemplar-probe" for e in ex), (phase, ex)
        # exemplars surface in the opt-in dump and the classic dump
        # still round-trips through the parser with them present
        page = dump_prometheus(exemplars=True)
        assert 'request_id="exemplar-probe"' in page

        def _stable(parsed):
            # the lock_* contention counters move between two dumps by
            # design (each dump publishes the latest lock accounting,
            # and the dispatcher keeps acquiring); the exemplar
            # round-trip contract is about every OTHER family
            return {
                k: v for k, v in parsed.items()
                if not k[0].startswith("spark_rapids_ml_tpu_lock_")
            }

        assert _stable(parse_prometheus(page)) == _stable(
            parse_prometheus(dump_prometheus())
        )
    finally:
        server.stop()


def test_slow_request_capture_has_full_span_tree(pca_model, rng):
    set_config(serving_slow_trace_ms=0.0001)  # everything is "slow"
    server = _serve(slow=pca_model)
    try:
        fut = server.submit("slow", _q(rng), request_id="slow-probe")
        fut.result(timeout=60)
        deadline = time.time() + 10
        while not server.slow_traces() and time.time() < deadline:
            time.sleep(0.01)
        traces = server.slow_traces()
        assert traces, "no slow capture despite a 0.0001ms threshold"
        entry = traces[-1]
        assert entry["model"] == "slow"
        assert any(
            r["request_id"] == "slow-probe" for r in entry["requests"]
        )
        names = set()

        def walk(nodes):
            for n in nodes:
                names.add(n["name"])
                walk(n.get("children", []))

        walk(entry["spans"])
        # the full request path: dispatch with its coalesce/stage/
        # compute children plus the collect/scatter of the same batch
        for want in (
            "serving_dispatch[slow]", "serving_coalesce", "serving_stage",
            "serving_compute", "serving_collect[slow]", "serving_scatter",
        ):
            assert want in names, (want, sorted(names))
        assert server.report()["_totals"]["slow_traces"] >= 1
    finally:
        server.stop()


def test_slow_capture_off_by_default(pca_model, rng):
    server = _serve(fast=pca_model)
    try:
        server.transform("fast", _q(rng), timeout=60)
        assert server.slow_traces() == []
    finally:
        server.stop()


def test_slo_burn_rate_gauges(pca_model, rng):
    from spark_rapids_ml_tpu.serving.server import SLO_BURN

    # an impossible target: every request breaches -> burn = 100x budget
    set_config(serving_slo_p99_ms=0.0001)
    server = _serve(burn=pca_model)
    try:
        for _ in range(3):
            server.transform("burn", _q(rng), timeout=60)
        deadline = time.time() + 10
        while (
            SLO_BURN.value(default=None, model="burn", window="1m") is None
            and time.time() < deadline
        ):
            server.transform("burn", _q(rng), timeout=60)
            time.sleep(0.3)
        burn = SLO_BURN.value(default=None, model="burn", window="1m")
        assert burn is not None and burn > 1.0, burn
        rep = server.report()["burn"]
        assert rep["slo_burn_1m"] == burn
        assert rep["slo_p99_target_ms"] == 0.0  # rounds from 1e-4 ms
        # a generous per-model override flips the same model to healthy
        set_config(serving_slo_targets="burn=60000")
        time.sleep(1.1)  # past the per-model refresh rate limit
        server.transform("burn", _q(rng), timeout=60)
        deadline = time.time() + 10
        while (
            SLO_BURN.value(default=1e9, model="burn", window="1m") > 0
            and time.time() < deadline
        ):
            time.sleep(1.1)
            server.transform("burn", _q(rng), timeout=60)
        assert SLO_BURN.value(model="burn", window="1m") == 0.0
    finally:
        server.stop()


def test_slo_gauges_absent_without_target(pca_model, rng):
    from spark_rapids_ml_tpu.serving.server import SLO_BURN

    SLO_BURN.remove(model="quiet", window="1m")
    SLO_BURN.remove(model="quiet", window="5m")
    server = _serve(quiet=pca_model)
    try:
        server.transform("quiet", _q(rng), timeout=60)
        assert SLO_BURN.value(default=None, model="quiet", window="1m") is None
        assert "slo_burn_1m" not in server.report()["quiet"]
    finally:
        server.stop()


def test_idle_dispatcher_refreshes_slo_gauges(pca_model, rng):
    # regression: the dispatcher's idle wait must break out to run
    # _refresh_slo_all — burn gauges decay when traffic STOPS, with no
    # later request driving the collect-path refresh (before the fix the
    # inner cv-wait loop never broke while running+idle, so a burn spike
    # scraped as live forever once traffic ended)
    set_config(serving_slo_p99_ms=60000)
    server = _serve(idle=pca_model)
    try:
        server.transform("idle", _q(rng), timeout=60)
        calls: list = []
        orig = server._update_slo
        server._update_slo = (
            lambda name: (calls.append(name), orig(name))[1]
        )
        deadline = time.time() + 5
        while "idle" not in calls and time.time() < deadline:
            time.sleep(0.1)
        assert "idle" in calls  # refreshed with zero in-flight traffic
    finally:
        server.stop()


def test_http_request_id_header_roundtrip(pca_model, rng):
    import json
    import urllib.request

    from spark_rapids_ml_tpu.serving.http import start_serving_http

    server = _serve(hdr=pca_model)
    http = start_serving_http(server, port=0)
    base = f"http://127.0.0.1:{http.server_port}"
    try:
        body = json.dumps({"instances": _q(rng).tolist()}).encode()
        req = urllib.request.Request(
            f"{base}/v1/models/hdr:transform", data=body,
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "edge-42"},
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            payload = json.load(resp)
        assert payload["request_id"] == "edge-42"
        # no header -> the server mints one and still names it
        req = urllib.request.Request(
            f"{base}/v1/models/hdr:transform", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            payload = json.load(resp)
        assert payload["request_id"].startswith("req-")
    finally:
        http.shutdown()
        http.server_close()
        server.stop()


def test_sustained_overload_leaves_postmortem(pca_model, rng, tmp_path):
    from spark_rapids_ml_tpu.serving import server as srv_mod
    from spark_rapids_ml_tpu.telemetry.flight_recorder import RECORDER

    set_config(
        flight_recorder_dir=str(tmp_path), serving_max_queue=1,
    )
    RECORDER.clear()  # fresh cooldown state for this test
    server = _serve(ovl=pca_model)
    server.pause()  # requests queue, nothing drains -> queue_full storm
    try:
        rejections = 0
        fut = server.submit("ovl", _q(rng))  # occupies the queue slot
        for _ in range(srv_mod._OVERLOAD_DUMP_COUNT + 5):
            with pytest.raises(ServingOverload):
                server.submit("ovl", _q(rng))
            rejections += 1
        bundles = list(tmp_path.glob("postmortem_serving_overload_*"))
        assert len(bundles) == 1, (rejections, bundles)
        import json as _json

        manifest = _json.loads((bundles[0] / "manifest.json").read_text())
        assert manifest["reason"] == "serving_overload"
        assert "model=ovl" in manifest["detail"]
    finally:
        server.resume()
        fut.result(timeout=60)
        server.stop()


# ---------------------------------------------------------------------------
# throughput (nightly)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_coalesced_qps_beats_sequential_3x(logreg_model, rng):
    """At batchable load (many tiny concurrent requests) the coalesced
    server must beat sequential per-request transforms by >= 3x QPS."""
    n = 200
    rows = [_q(rng, 1) for _ in range(n)]
    # sequential per-request baseline: each row pays the full chunked
    # transform driver
    t0 = time.perf_counter()
    for r in rows:
        logreg_model._transform_array(r)
    seq_qps = n / (time.perf_counter() - t0)

    set_config(serving_max_wait_ms=5.0)
    server = _serve(qps=logreg_model)
    try:
        server.transform("qps", rows[0], timeout=60)  # warm the bucket
        t0 = time.perf_counter()
        futs = [server.submit("qps", r) for r in rows]
        for f in futs:
            f.result(timeout=120)
        srv_qps = n / (time.perf_counter() - t0)
    finally:
        server.stop()
    assert srv_qps >= 3.0 * seq_qps, (srv_qps, seq_qps)
