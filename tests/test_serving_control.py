#
# Closed-loop serving control plane (spark_rapids_ml_tpu/serving/
# control.py) — AIMD convergence and hysteresis, the brownout phase
# machine (spike -> shed -> recover, exactly one cooldown-guarded
# flight-recorder bundle), priority-class admission and weighted
# dispatch (batch cannot starve interactive at 10:1 skew), padding-
# bucket compile reuse, the `serving_admission` fault site, and the
# dispatcher-lag liveness fix — all on the 8-device CPU mesh.
#
import glob
import json
import time

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu.classification import LogisticRegression
from spark_rapids_ml_tpu.config import get_config, reset_config, set_config
from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.resilience import fault_inject
from spark_rapids_ml_tpu.resilience.elastic import reset_elastic
from spark_rapids_ml_tpu.serving import (
    ServingController,
    ServingOverload,
    ServingServer,
)
from spark_rapids_ml_tpu.serving.control import (
    BROWNOUT_PHASES,
    PRIORITY_CLASSES,
    resolve_priority,
)
from spark_rapids_ml_tpu.tracing import last_fact


@pytest.fixture(autouse=True)
def _clean():
    reset_config()
    set_config(retry_backoff_s=0.01, retry_jitter=0.0)
    yield
    reset_config()
    reset_elastic()
    from spark_rapids_ml_tpu.parallel.device_cache import get_device_cache

    cache = get_device_cache()
    for tag in list(cache._external):
        cache.release_external(tag)


@pytest.fixture(scope="module")
def rng_m():
    return np.random.default_rng(11)


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
# AIMD controller unit dynamics
# ---------------------------------------------------------------------------


def test_aimd_multiplicative_decrease_and_additive_regrow():
    """Burn over the high water HALVES both actuator scales per tick;
    burn under the low water regrows them ADDITIVELY (1/8 per tick)
    back to 1.0 — classic AIMD, the same halving the OOM cap
    degradation uses with a converging regrow."""
    ctl = ServingController()
    t = 1000.0
    ctl.tick("m", 4.0, 10.0, 1024, 2.0, now=t)
    assert ctl.cap_scale("m") == 0.5
    assert ctl.wait_scale("m") == 0.5
    ctl.tick("m", 4.0, 10.0, 1024, 2.0, now=t + 2)
    assert ctl.cap_scale("m") == 0.25
    t += 2  # the decrease tick above consumed this interval slot
    # recovery: +0.125 per low tick, capped at 1.0
    steps = 0
    while ctl.cap_scale("m") < 1.0:
        t += 2
        ctl.tick("m", 0.0, 10.0, 1024, 2.0, now=t)
        steps += 1
        assert steps < 20, "additive regrow must converge to 1.0"
    assert steps == 6  # 0.25 -> 1.0 in 1/8 steps
    assert ctl.wait_scale("m") == 1.0


def test_aimd_hysteresis_band_holds():
    """Burn between the low and high waters changes NOTHING — the
    hysteresis band is what keeps the actuators from oscillating at a
    single threshold."""
    ctl = ServingController()
    t = 1000.0
    ctl.tick("m", 4.0, 10.0, 1024, 2.0, now=t)
    assert ctl.cap_scale("m") == 0.5
    for i in range(5):
        ctl.tick("m", 0.75, 10.0, 1024, 2.0, now=t + 2 * (i + 1))
    assert ctl.cap_scale("m") == 0.5  # held, neither shrunk nor grown
    assert ctl.wait_scale("m") == 0.5


def test_aimd_tick_rate_limited_and_floored():
    """Ticks inside `serving_controller_interval_s` are ignored (the
    burn gauge itself refreshes at ~1 Hz; faster would double-halve on
    one signal), and the scale floors above zero — brownout is the next
    escalation, not ever-smaller batches."""
    ctl = ServingController()
    t = 1000.0
    ctl.tick("m", 4.0, 10.0, 1024, 2.0, now=t)
    ctl.tick("m", 4.0, 10.0, 1024, 2.0, now=t + 0.2)  # inside interval
    assert ctl.cap_scale("m") == 0.5
    for i in range(32):
        ctl.tick("m", 4.0, 10.0, 1024, 2.0, now=t + 2.0 * (i + 1))
    assert ctl.cap_scale("m") >= 1.0 / 64.0
    assert ctl.cap_scale("m") > 0


def test_controller_off_restores_static_knobs():
    set_config(serving_controller="off")
    ctl = ServingController()
    ctl2 = ServingController()
    assert ctl.cap_scale("m") == 1.0 and ctl.wait_scale("m") == 1.0
    # admission degrades to the global bound only
    ok, reason, _ = ctl2.admit("m", "batch", 5, 5, 10)
    assert ok
    ok, reason, _ = ctl2.admit("m", "batch", 10, 10, 10)
    assert not ok and reason == "queue_full"


# ---------------------------------------------------------------------------
# brownout phase machine
# ---------------------------------------------------------------------------


def test_brownout_spike_shed_recover_with_one_bundle(tmp_path):
    """Sustained burn escalates normal -> shed_batch ->
    shed_interactive (one phase per sustain window, timers re-armed);
    sustained recovery de-escalates one phase per recovery window; the
    episode leaves EXACTLY one parsed reason="brownout" bundle (the
    recorder's per-reason cooldown absorbs the second escalation)."""
    from spark_rapids_ml_tpu.telemetry.flight_recorder import RECORDER

    set_config(
        flight_recorder_dir=str(tmp_path),
        serving_brownout_sustain_s=1.0,
        serving_brownout_recover_s=1.0,
        serving_controller_interval_s=0.5,
    )
    RECORDER.clear()
    ctl = ServingController()
    t = 5000.0
    # phase 0 holds until the burn SUSTAINS: one hot tick is not enough
    ctl.tick("m", 10.0, 50.0, 1024, 2.0, now=t)
    assert ctl.phase("m") == 0
    ctl.tick("m", 10.0, 50.0, 1024, 2.0, now=t + 1.2)
    assert ctl.phase("m") == 1  # shed_batch
    # the NEXT escalation needs its own sustain window
    ctl.tick("m", 10.0, 50.0, 1024, 2.0, now=t + 1.9)
    assert ctl.phase("m") == 1
    ctl.tick("m", 10.0, 50.0, 1024, 2.0, now=t + 2.6)
    assert ctl.phase("m") == 2  # shed_interactive (terminal)
    ctl.tick("m", 10.0, 50.0, 1024, 2.0, now=t + 4.0)
    assert ctl.phase("m") == 2
    assert ctl.brownout_summary() == {"m": "shed_interactive"}
    # recovery: burn below the low water, one phase per recover window
    ctl.tick("m", 0.0, 5.0, 1024, 2.0, now=t + 10.0)
    ctl.tick("m", 0.0, 5.0, 1024, 2.0, now=t + 11.2)
    assert ctl.phase("m") == 1
    ctl.tick("m", 0.0, 5.0, 1024, 2.0, now=t + 12.4)
    assert ctl.phase("m") == 0
    assert ctl.brownout_summary() == {}
    bundles = glob.glob(str(tmp_path / "postmortem_brownout_*"))
    assert len(bundles) == 1, bundles
    manifest = json.loads(
        (tmp_path / bundles[0].split("/")[-1] / "manifest.json").read_text()
    )
    assert manifest["reason"] == "brownout"
    assert "model=m" in manifest["detail"]
    assert "normal->shed_batch" in manifest["detail"]


def test_brownout_flap_cannot_ratchet():
    """A burn that dips mid-sustain re-arms the escalation timer — a
    flapping signal can never ratchet straight to shed_interactive."""
    set_config(
        serving_brownout_sustain_s=1.0, serving_controller_interval_s=0.1
    )
    ctl = ServingController()
    t = 7000.0
    for i in range(6):
        # hot for 0.6s, then a clean mid-band tick resets hi_since
        ctl.tick("m", 10.0, 50.0, 1024, 2.0, now=t)
        ctl.tick("m", 10.0, 50.0, 1024, 2.0, now=t + 0.6)
        ctl.tick("m", 0.8, 50.0, 1024, 2.0, now=t + 0.8)
        t += 1.0
    assert ctl.phase("m") == 0


# ---------------------------------------------------------------------------
# priority admission + weighted dispatch
# ---------------------------------------------------------------------------


def test_priority_resolution_chain():
    assert resolve_priority(None, None) == "interactive"
    assert resolve_priority(None, "batch") == "batch"
    assert resolve_priority("interactive", "batch") == "interactive"
    set_config(serving_priority_default="batch")
    assert resolve_priority(None, None) == "batch"
    with pytest.raises(ValueError, match="unknown priority class"):
        resolve_priority("realtime", None)


def test_batch_class_bounded_to_queue_share(pca_model, rng):
    """Batch-priority requests admit into at most `serving_batch_share`
    of the queue; interactive still has the full queue — background
    scoring can never wedge the latency path out of admission."""
    set_config(serving_max_queue=8, serving_batch_share=0.25)
    server = _serve(share=pca_model)
    try:
        server.pause()
        futs = [
            server.submit("share", _q(rng), priority="batch")
            for _ in range(2)  # the 25% share of 8
        ]
        with pytest.raises(ServingOverload) as ei:
            server.submit("share", _q(rng), priority="batch")
        assert ei.value.reason == "queue_full"
        # interactive traffic is untouched by the batch bound
        futs += [
            server.submit("share", _q(rng), priority="interactive")
            for _ in range(4)
        ]
        server.resume()
        for f in futs:
            f.result(timeout=60)
    finally:
        server.stop()


def test_batch_cannot_starve_interactive_10_to_1(logreg_model, rng):
    """10:1 batch:interactive skew, 1-row coalescing cap: EVERY
    interactive request completes while most of the batch backlog is
    still queued — the weighted credit gives a contested round to batch
    only once per 1/share interactive wins."""
    set_config(
        serving_max_batch_rows=1,  # one request per dispatch round
        serving_max_queue=128,  # batch share bound (32) clears the 20
        serving_batch_share=0.25,
    )
    server = _serve(skew=logreg_model)
    try:
        server.transform("skew", _q(rng), timeout=60)  # warm the program
        server.pause()
        done_at = {}

        def _stamp(key):
            return lambda f: done_at.__setitem__(key, time.perf_counter())

        b_futs = []
        for i in range(20):
            f = server.submit("skew", _q(rng), priority="batch")
            f.add_done_callback(_stamp(("b", i)))
            b_futs.append(f)
        i_futs = []
        for i in range(2):
            f = server.submit("skew", _q(rng), priority="interactive")
            f.add_done_callback(_stamp(("i", i)))
            i_futs.append(f)
        server.resume()
        for f in i_futs + b_futs:
            f.result(timeout=120)
        t_interactive = max(
            done_at[("i", i)] for i in range(len(i_futs))
        )
        batch_before = sum(
            1 for i in range(len(b_futs))
            if done_at[("b", i)] <= t_interactive
        )
        # despite 20 batch requests enqueued FIRST, interactive finished
        # with the bulk of the batch backlog still pending
        assert batch_before <= len(b_futs) // 2, (
            batch_before, sorted(done_at.items(), key=lambda kv: kv[1])
        )
    finally:
        server.stop()


def test_model_default_priority_registration(pca_model, rng):
    """A model registered priority="batch" makes UNTAGGED requests
    batch-class (shed under brownout share rules); registration rejects
    unknown classes."""
    server = ServingServer()
    server.register("bg", pca_model, priority="batch")
    with pytest.raises(ValueError, match="unknown priority class"):
        server.register("bad", pca_model, priority="urgent")
    set_config(serving_max_queue=8, serving_batch_share=0.25)
    server.start()
    try:
        server.pause()
        futs = [server.submit("bg", _q(rng)) for _ in range(2)]
        with pytest.raises(ServingOverload):  # batch share bound: 2 of 8
            server.submit("bg", _q(rng))
        # an explicit per-request class overrides the model default
        futs.append(
            server.submit("bg", _q(rng), priority="interactive")
        )
        server.resume()
        for f in futs:
            f.result(timeout=60)
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# spike -> shed -> recover on a live server
# ---------------------------------------------------------------------------


def test_live_spike_sheds_batch_then_recovers(pca_model, rng, tmp_path):
    """End to end on a live dispatcher: an impossible SLO target drives
    the 1m burn over the brownout threshold, the controller escalates
    to shed_batch (batch submits rejected reason="shed", interactive
    still admitted, shed counts in the report), then a generous target
    plus fresh traffic recovers the phase and re-admits batch."""
    from spark_rapids_ml_tpu.serving.control import SHED
    from spark_rapids_ml_tpu.telemetry.flight_recorder import RECORDER

    set_config(
        flight_recorder_dir=str(tmp_path),
        serving_slo_targets="live=0.0001",  # everything breaches
        serving_controller_interval_s=0.05,
        serving_brownout_sustain_s=0.2,
        serving_brownout_recover_s=0.2,
    )
    RECORDER.clear()
    server = _serve(live=pca_model)
    try:
        deadline = time.time() + 30
        while (
            server._controller.phase("live") < 1
            and time.time() < deadline
        ):
            server.transform("live", _q(rng), timeout=60)
            time.sleep(0.05)
        assert server._controller.phase("live") >= 1, "brownout never hit"
        shed0 = SHED.value(default=0, model="live", **{"class": "batch"})
        with pytest.raises(ServingOverload) as ei:
            server.submit("live", _q(rng), priority="batch")
        assert ei.value.reason == "shed"
        assert (
            SHED.value(default=0, model="live", **{"class": "batch"})
            == shed0 + 1
        )
        # interactive is NOT shed in shed_batch phase
        server.transform("live", _q(rng), timeout=60)
        rep = server.report()
        assert rep["live"]["controller"]["shed"].get("batch", 0) >= 1
        assert rep["live"]["controller"]["brownout_phase"] in (
            "shed_batch", "shed_interactive",
        )
        assert rep["_totals"]["controller"]["brownout"].get("live")
        # exactly one brownout black box for the episode
        assert len(glob.glob(str(tmp_path / "postmortem_brownout_*"))) == 1
        # recovery: a generous target zeroes the burn on its next
        # refresh; traffic keeps the dispatcher ticking the controller
        set_config(serving_slo_targets="live=60000")
        deadline = time.time() + 30
        while (
            server._controller.phase("live") > 0
            and time.time() < deadline
        ):
            server.transform("live", _q(rng), timeout=60)
            time.sleep(0.05)
        assert server._controller.phase("live") == 0, "never recovered"
        server.transform("live", _q(rng), priority="batch", timeout=60)
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# padding buckets (compile reuse across churning sizes)
# ---------------------------------------------------------------------------


def test_padding_buckets_reuse_compiled_program(pca_model, rng):
    """Churning request sizes inside one {1,1.5}x2^k bucket stage to
    the SAME padded shape: zero new backend compiles after warmup (the
    jit-audit guarantee extended to serving), the decision is the
    dispatch's `serving_bucket` fact, and the report lists the padding
    class."""
    from spark_rapids_ml_tpu.parallel.mesh import bucket_rows
    from spark_rapids_ml_tpu.telemetry import delta, snapshot
    from spark_rapids_ml_tpu.telemetry.compile import install_jax_listener

    if not install_jax_listener():
        pytest.skip("jax.monitoring listener unavailable on this jax")
    assert bool(get_config("serving_padding_buckets"))  # default on
    server = _serve(pad=pca_model)
    try:
        server.transform("pad", _q(rng, 3), timeout=60)  # warm the bucket
        before = snapshot()
        for n in (1, 7, 33, 120, 255):  # all pad to the 256 bucket
            out = server.transform("pad", _q(rng, n), timeout=60)
            assert out["proj"].shape == (n, 3)  # padding trimmed
        d = delta(before, snapshot())
        assert not d.get("compiles_total"), d.get("compiles_total")
        # recorded on the dispatch thread, not this one
        decision = last_fact("serving_bucket", all_threads=True)
        assert decision["model"] == "pad"
        assert decision["rows"] == 255
        assert decision["bucket"] == bucket_rows(255)
        assert decision["pad_rows"] == bucket_rows(255) - 255
        rep = server.report()["pad"]
        assert bucket_rows(255) in rep["controller"]["padding_classes"]
    finally:
        server.stop()


def test_padding_buckets_off_stages_exact(pca_model, rng):
    set_config(serving_padding_buckets=False)
    server = _serve(nopad=pca_model)
    try:
        out = server.transform("nopad", _q(rng, 5), timeout=60)
        assert out["proj"].shape == (5, 3)
        # no decision recorded for this model, on any thread
        decision = last_fact("serving_bucket", all_threads=True)
        assert decision.get("model") != "nopad"
        assert server.report()["nopad"]["controller"]["padding_classes"] == []
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# serving_admission fault site + dispatcher-lag liveness
# ---------------------------------------------------------------------------


def test_admission_fault_site_rejects_before_enqueue(pca_model, rng):
    """An injected `serving_admission` fault raises to the SUBMITTING
    caller before the request touches a queue; the dispatcher never
    sees it and the server keeps serving."""
    server = _serve(inj=pca_model)
    try:
        with fault_inject("serving_admission", "oom", times=1):
            with pytest.raises(Exception, match="injected"):
                server.submit("inj", _q(rng))
        assert server._queued == 0  # nothing leaked into the queues
        assert server.report()["_totals"]["queued"] == 0
        out = server.transform("inj", _q(rng, 2), timeout=60)
        assert out["proj"].shape == (2, 3)
    finally:
        server.stop()


def test_dispatcher_lag_publishes_on_saturated_dispatch(pca_model, rng):
    """Regression (the stale-gauge fix): full-cap batches dispatch on
    the inner loop's FIRST pass — no timed-out idle wake ever runs —
    and the lag gauge must still publish every round instead of
    freezing at the last idle value."""
    from spark_rapids_ml_tpu.serving.server import DISPATCH_LAG

    set_config(serving_max_batch_rows=1)  # every request is a full batch
    server = _serve(lag=pca_model)
    try:
        server.transform("lag", _q(rng), timeout=60)  # warm
        server.pause()
        futs = [server.submit("lag", _q(rng)) for _ in range(30)]
        DISPATCH_LAG.set(-1.0)  # sentinel an idle wake would also clear
        server.resume()
        for f in futs:
            f.result(timeout=120)
        # 30 full-cap rounds back-to-back: the saturated dispatch path
        # (not the idle timeout) must have republished the gauge
        assert DISPATCH_LAG.value() >= 0.0
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# report / detail surfaces
# ---------------------------------------------------------------------------


def test_report_carries_controller_state(pca_model, rng):
    server = _serve(rep=pca_model)
    try:
        server.transform("rep", _q(rng), timeout=60)
        entry = server.report()["rep"]["controller"]
        assert entry["cap"] >= 1
        assert entry["max_wait_ms"] == float(
            get_config("serving_max_wait_ms")
        )
        assert entry["brownout_phase"] == BROWNOUT_PHASES[0]
        assert entry["shed"] == {}
        totals = server.report()["_totals"]["controller"]
        assert totals["enabled"] is True
        assert totals["priority_shares"] == {
            "interactive": 1.0,
            "batch": float(get_config("serving_batch_share")),
        }
        assert totals["shed"] == {c: 0 for c in PRIORITY_CLASSES}
        assert totals["brownout"] == {}
        # model_detail (the GET /v1/models/<name> payload) carries it too
        assert server.model_detail("rep")["controller"]["cap"] >= 1
    finally:
        server.stop()


def test_http_x_priority_header(pca_model, rng):
    import urllib.error
    import urllib.request

    from spark_rapids_ml_tpu.serving.http import start_serving_http

    set_config(serving_max_queue=8, serving_batch_share=0.25)
    server = _serve(hweb=pca_model)
    http = start_serving_http(server, port=0)
    base = f"http://127.0.0.1:{http.server_port}"
    try:
        body = json.dumps({"instances": _q(rng).tolist()}).encode()

        def _post(headers):
            req = urllib.request.Request(
                f"{base}/v1/models/hweb:transform", data=body,
                headers={"Content-Type": "application/json", **headers},
            )
            with urllib.request.urlopen(req, timeout=60) as resp:
                return json.load(resp)

        assert _post({"X-Priority": "interactive"})["rows"] == 1
        assert _post({"X-Priority": "batch"})["rows"] == 1
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post({"X-Priority": "urgent"})
        assert ei.value.code == 400  # unknown class -> ValueError -> 400
        # controller state rides the model-detail route
        with urllib.request.urlopen(
            f"{base}/v1/models/hweb", timeout=30
        ) as r:
            detail = json.load(r)
        assert detail["controller"]["brownout_phase"] == "normal"
    finally:
        http.shutdown()
        http.server_close()
        server.stop()
