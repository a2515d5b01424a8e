#
# Resilience-layer tests — the recovery paths the reference gets for free
# from Spark's barrier re-scheduling, exercised deterministically on the
# CPU mesh via fault injection (resilience/faults.py): guarded dispatch
# under a watchdog deadline, declarative retry policies (OOM / transient /
# preemption), and the estimator-wide checkpoint/resume contract.
#
import os
import subprocess
import time

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu.config import reset_config, set_config
from spark_rapids_ml_tpu.resilience import (
    DispatchTimeout,
    RetryPolicy,
    SimulatedPreemption,
    checkpoint_file_for,
    classify_error,
    fault_inject,
    guarded,
    is_oom,
    is_preemption,
    is_transient,
    load_checkpoint,
    maybe_inject,
    retry_call,
    save_checkpoint,
)
from spark_rapids_ml_tpu.tracing import get_trace_events, reset_trace


@pytest.fixture(autouse=True)
def _clean():
    reset_config()
    reset_trace()
    yield
    reset_config()
    reset_trace()


def _fast_retries(**overrides):
    conf = dict(retry_backoff_s=0.01, retry_jitter=0.0)
    conf.update(overrides)
    set_config(**conf)


# ---------------------------------------------------------------------------
# classifiers
# ---------------------------------------------------------------------------


def test_error_classifiers():
    assert is_oom(RuntimeError("RESOURCE_EXHAUSTED: out of HBM"))
    assert is_oom(RuntimeError("Out of memory allocating 1234 bytes"))
    assert not is_oom(ValueError("bad shape"))
    assert is_transient(DispatchTimeout("fit_kernel", 1.0))
    assert is_transient(RuntimeError("DEADLINE_EXCEEDED: transfer stall"))
    assert is_transient(RuntimeError("UNAVAILABLE: Socket closed"))
    assert is_preemption(SimulatedPreemption("fit_kernel"))
    assert is_preemption(RuntimeError("TPU worker preempted by scheduler"))
    assert classify_error(SimulatedPreemption("s")) == "preemption"
    assert classify_error(RuntimeError("RESOURCE_EXHAUSTED")) == "oom"
    assert classify_error(DispatchTimeout("s", 1.0)) == "transient"
    assert classify_error(ValueError("nope")) == "fatal"


def test_preemption_classifier_real_coordinator_strings():
    """The satellite contract: is_preemption must recognize the REAL
    coordinator/runtime failure strings a worker death produces — each
    pinned here verbatim — while plain user RuntimeErrors stay fatal."""
    # status-code family: a restarted worker lost its coordination state
    assert is_preemption(RuntimeError("DATA_LOSS: worker state lost"))
    # heartbeat family: the coordination service stopped hearing from a task
    assert is_preemption(
        RuntimeError("coordination service heartbeat timed out")
    )
    assert is_preemption(RuntimeError("UNAVAILABLE: Heartbeat request failed"))
    # transport family: the coordination channel's socket closed under it
    assert is_preemption(
        RuntimeError("Coordination service agent: Socket closed before barrier")
    )
    # a NON-coordination socket error is transient (backoff), not preemption
    assert not is_preemption(RuntimeError("UNAVAILABLE: Socket closed"))
    assert classify_error(RuntimeError("UNAVAILABLE: Socket closed")) == "transient"
    # plain user errors stay fatal
    assert not is_preemption(RuntimeError("heartbeat animation glitch"))
    assert classify_error(RuntimeError("something broke")) == "fatal"
    # device loss classifies to its OWN action, ahead of preemption
    dl = RuntimeError(
        "INTERNAL: failed to execute XLA Runtime executable: device 2 "
        "has been lost"
    )
    from spark_rapids_ml_tpu.resilience import is_device_loss

    assert is_device_loss(dl)
    assert classify_error(dl) == "device_loss"


def test_guarded_passthrough_when_disabled():
    # deadline <= 0 (the default conf): no watchdog thread, direct call
    assert guarded(lambda: 42, deadline=0.0) == 42
    assert guarded(lambda: 42) == 42


def test_guarded_returns_value_and_reraises():
    assert guarded(lambda: "ok", deadline=5.0, label="t") == "ok"
    with pytest.raises(ValueError, match="boom"):
        guarded(lambda: (_ for _ in ()).throw(ValueError("boom")),
                deadline=5.0, label="t")


def test_guarded_deadline_raises_typed_timeout():
    t0 = time.monotonic()
    with pytest.raises(DispatchTimeout, match="watchdog deadline"):
        guarded(lambda: time.sleep(5.0), deadline=0.2, label="hang_site")
    assert time.monotonic() - t0 < 2.0  # the caller got control back
    # the deadline is surfaced as a trace event
    ev = [e for e in get_trace_events() if e.name == "dispatch_timeout[hang_site]"]
    assert ev and "deadline=0.2" in ev[0].detail


# ---------------------------------------------------------------------------
# retry policies
# ---------------------------------------------------------------------------


def test_retry_call_transient_then_success():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("DEADLINE_EXCEEDED: transient")
        return "done"

    policy = RetryPolicy(max_attempts=3, backoff_s=0.01, jitter=0.0)
    assert retry_call(flaky, label="t", policy=policy) == "done"
    assert calls["n"] == 3
    retries = [e for e in get_trace_events() if e.name == "retry[t]"]
    assert len(retries) == 2


def test_retry_call_exhausts_attempts():
    def always():
        raise RuntimeError("UNAVAILABLE: still down")

    policy = RetryPolicy(max_attempts=2, backoff_s=0.01, jitter=0.0)
    with pytest.raises(RuntimeError, match="UNAVAILABLE"):
        retry_call(always, label="t", policy=policy)


def test_retry_call_fatal_propagates_immediately():
    calls = {"n": 0}

    def fatal():
        calls["n"] += 1
        raise ValueError("not retryable")

    with pytest.raises(ValueError):
        retry_call(fatal, label="t", policy=RetryPolicy(max_attempts=5))
    assert calls["n"] == 1


def test_retry_call_oom_hook_runs():
    calls = {"n": 0, "hook": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")
        return "ok"

    def hook():
        calls["hook"] += 1

    policy = RetryPolicy(max_attempts=2, backoff_s=0.01, jitter=0.0)
    assert retry_call(flaky, label="t", policy=policy, on_oom=hook) == "ok"
    assert calls["hook"] == 1


def test_retry_policy_backoff_grows():
    p = RetryPolicy(backoff_s=0.5, backoff_mult=2.0, jitter=0.0)
    assert p.backoff(1) == pytest.approx(0.5)
    assert p.backoff(3) == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# fault injection
# ---------------------------------------------------------------------------


def test_fault_inject_times_and_skip():
    with fault_inject("site_a", "oom", times=2, skip=1):
        maybe_inject("site_a")  # skipped occurrence passes through
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            maybe_inject("site_a")
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            maybe_inject("site_a")
        maybe_inject("site_a")  # exhausted: passes
    maybe_inject("site_a")  # disarmed on exit


def test_fault_inject_conf_spec():
    set_config(fault_inject_spec="site_b:timeout:1")
    with pytest.raises(DispatchTimeout):
        maybe_inject("site_b")
    maybe_inject("site_b")  # single-shot
    set_config(fault_inject_spec="")
    maybe_inject("site_b")


def test_fault_inject_unknown_kind_rejected():
    with pytest.raises(ValueError, match="unknown fault kind"):
        # deliberately invalid kind: the arm must be rejected (the
        # fault-site rule exempts fault_inject under pytest.raises)
        with fault_inject("s", "segfault"):
            pass


# ---------------------------------------------------------------------------
# mid-fit recovery: each injected fault class ends in a model equal to the
# fault-free run (the acceptance contract)
# ---------------------------------------------------------------------------


def _kmeans_df(rng, n=240):
    X = rng.normal(size=(n, 4)).astype(np.float32)
    return pd.DataFrame({"features": list(X)}), X


def test_fit_recovers_injected_oom(rng):
    from spark_rapids_ml_tpu.clustering import KMeans

    df, _ = _kmeans_df(rng)
    _fast_retries()
    m0 = KMeans(k=2, seed=1).fit(df)
    with fault_inject("fit_kernel", "oom", times=1):
        m1 = KMeans(k=2, seed=1).fit(df)
    np.testing.assert_allclose(
        m0.cluster_centers_, m1.cluster_centers_, rtol=1e-6
    )
    assert any(e.name == "retry[fit_kernel]" for e in get_trace_events())


def test_fit_recovers_injected_timeout(rng):
    from spark_rapids_ml_tpu.clustering import KMeans

    df, _ = _kmeans_df(rng)
    _fast_retries()
    m0 = KMeans(k=2, seed=1).fit(df)
    with fault_inject("fit_kernel", "timeout", times=1):
        m1 = KMeans(k=2, seed=1).fit(df)
    np.testing.assert_allclose(
        m0.cluster_centers_, m1.cluster_centers_, rtol=1e-6
    )


def test_fit_recovers_watchdog_hang(rng):
    # a HANG (not an error) inside the dispatch: only the guarded watchdog
    # turns it into a typed, retryable failure
    from spark_rapids_ml_tpu.clustering import KMeans

    df, _ = _kmeans_df(rng)
    _fast_retries(dispatch_deadline_s=0.5)
    m0 = KMeans(k=2, seed=1).fit(df)
    with fault_inject("fit_kernel", "hang", times=1, seconds=1.5):
        m1 = KMeans(k=2, seed=1).fit(df)
    np.testing.assert_allclose(
        m0.cluster_centers_, m1.cluster_centers_, rtol=1e-6
    )
    names = [e.name for e in get_trace_events()]
    assert "dispatch_timeout[fit_kernel]" in names


def test_fit_recovers_injected_preemption(rng):
    from spark_rapids_ml_tpu.clustering import KMeans

    df, _ = _kmeans_df(rng)
    _fast_retries()
    m0 = KMeans(k=2, seed=1).fit(df)
    with fault_inject("fit_kernel", "preemption", times=1):
        m1 = KMeans(k=2, seed=1).fit(df)
    np.testing.assert_allclose(
        m0.cluster_centers_, m1.cluster_centers_, rtol=1e-6
    )


def test_transform_recovers_injected_oom(rng):
    from spark_rapids_ml_tpu.clustering import KMeans

    df, X = _kmeans_df(rng, n=400)
    m = KMeans(k=2, seed=0).fit(df)
    ref = np.asarray(m._transform_array(X)[m.getOrDefault("predictionCol")])
    with fault_inject("transform_dispatch", "oom", times=1):
        out = np.asarray(
            m._transform_array(X)[m.getOrDefault("predictionCol")]
        )
    np.testing.assert_array_equal(ref, out)


def test_transform_recovers_injected_timeout(rng):
    from spark_rapids_ml_tpu.clustering import KMeans

    df, X = _kmeans_df(rng, n=400)
    _fast_retries()
    m = KMeans(k=2, seed=0).fit(df)
    ref = np.asarray(m._transform_array(X)[m.getOrDefault("predictionCol")])
    with fault_inject("transform_dispatch", "timeout", times=1):
        out = np.asarray(
            m._transform_array(X)[m.getOrDefault("predictionCol")]
        )
    np.testing.assert_array_equal(ref, out)
    assert any(
        e.name == "retry[transform_dispatch]" for e in get_trace_events()
    )


# ---------------------------------------------------------------------------
# satellite: the _stage_or_stream OOM retry runs OUTSIDE the except block —
# a failed-then-retried fit must not leak the poisoned buffers (the second
# attempt succeeds after an injected staging OOM)
# ---------------------------------------------------------------------------


def test_streaming_fit_retries_after_injected_staging_oom(tmp_path, rng):
    from spark_rapids_ml_tpu.regression import LinearRegression

    X = rng.normal(size=(500, 4)).astype(np.float32)
    y = (X @ np.array([1.0, 2.0, -1.0, 0.5])).astype(np.float64)
    df = pd.DataFrame({"features": list(X), "label": y})
    path = str(tmp_path / "d.parquet")
    df.to_parquet(path)

    m_ref = LinearRegression().fit(df)
    with fault_inject("stage_parquet", "oom", times=1):
        m = LinearRegression().fit(path)  # succeeds via streamed stats
    np.testing.assert_allclose(m.coef_, m_ref.coef_, rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# estimator-wide checkpoint/resume: an interrupted iterative fit resumes
# from its checkpoint rather than restarting at iteration 0
# ---------------------------------------------------------------------------


def test_kmeans_checkpoint_resume_after_crash(tmp_path, rng):
    from spark_rapids_ml_tpu.clustering import KMeans

    df, _ = _kmeans_df(rng, n=400)
    set_config(checkpoint_dir=str(tmp_path), retry_max_attempts=1)
    kw = dict(k=3, seed=1, maxIter=8, tol=0.0)
    m0 = KMeans(**kw).fit(df)  # checkpoint_dir forces the stepwise solver
    assert not list(tmp_path.glob("*.npz")), "completed fit cleans up"
    # crash at Lloyd iteration 4 (3 iterations complete); retries disabled
    # so the preemption surfaces like a real process death
    with pytest.raises(SimulatedPreemption):
        with fault_inject("kmeans_lloyd", "preemption", times=1, skip=3):
            KMeans(**kw).fit(df)
    assert list(tmp_path.glob("kmeans-mem-*.npz")), "crash leaves the state"
    reset_trace()
    m1 = KMeans(**kw).fit(df)  # fresh process restart: resumes
    resumes = [e for e in get_trace_events() if e.name == "kmeans_resume"]
    assert resumes and resumes[0].detail == "it=3", (
        "must resume at iteration 3, not restart at 0"
    )
    np.testing.assert_allclose(
        m0.cluster_centers_, m1.cluster_centers_, rtol=1e-5, atol=1e-5
    )
    assert not list(tmp_path.glob("*.npz"))


def test_kmeans_preemption_autoresumes_within_one_fit(tmp_path, rng):
    # with retries enabled the fit self-heals IN ONE CALL: the preemption
    # triggers reinit + re-dispatch, and the re-dispatched solver picks up
    # the per-iteration checkpoint instead of re-seeding
    from spark_rapids_ml_tpu.clustering import KMeans

    df, _ = _kmeans_df(rng, n=400)
    _fast_retries(checkpoint_dir=str(tmp_path))
    kw = dict(k=3, seed=1, maxIter=8, tol=0.0)
    m0 = KMeans(**kw).fit(df)
    reset_trace()
    with fault_inject("kmeans_lloyd", "preemption", times=1, skip=3):
        m1 = KMeans(**kw).fit(df)
    names = [e.name for e in get_trace_events()]
    assert "retry[fit_kernel]" in names
    assert "kmeans_resume" in names
    np.testing.assert_allclose(
        m0.cluster_centers_, m1.cluster_centers_, rtol=1e-5, atol=1e-5
    )


def test_logreg_checkpoint_resume_after_crash(tmp_path, rng):
    from spark_rapids_ml_tpu.classification import LogisticRegression

    X = rng.normal(size=(400, 4)).astype(np.float32)
    y = (X[:, 0] > 0).astype(float)
    df = pd.DataFrame({"features": list(X), "label": y})
    set_config(checkpoint_dir=str(tmp_path), retry_max_attempts=1)
    kw = dict(maxIter=20, regParam=0.01)
    m0 = LogisticRegression(**kw).fit(df)  # forces host-dispatched L-BFGS
    with pytest.raises(SimulatedPreemption):
        with fault_inject("lbfgs_iteration", "preemption", times=1, skip=3):
            LogisticRegression(**kw).fit(df)
    assert list(tmp_path.glob("logreg-mem-*.npz"))
    reset_trace()
    m1 = LogisticRegression(**kw).fit(df)
    resumes = [e for e in get_trace_events() if e.name == "lbfgs_resume"]
    assert resumes and resumes[0].detail == "it=3"
    np.testing.assert_allclose(
        np.asarray(m0.coef_), np.asarray(m1.coef_), rtol=1e-5, atol=1e-6
    )
    assert not list(tmp_path.glob("*.npz"))


def test_linreg_fista_checkpoint_resume_after_crash(tmp_path, rng):
    from spark_rapids_ml_tpu.regression import LinearRegression

    X = rng.normal(size=(300, 6)).astype(np.float32)
    beta = np.array([1.5, -2.0, 0.0, 0.0, 3.0, 0.0])
    y = (X @ beta + 0.01 * rng.normal(size=300)).astype(np.float64)
    df = pd.DataFrame({"features": list(X), "label": y})
    set_config(checkpoint_dir=str(tmp_path), retry_max_attempts=1)
    kw = dict(regParam=0.1, elasticNetParam=0.5, maxIter=60, tol=0.0)
    m0 = LinearRegression(**kw).fit(df)
    with pytest.raises(SimulatedPreemption):
        with fault_inject("linreg_fista", "preemption", times=1, skip=5):
            LinearRegression(**kw).fit(df)
    assert list(tmp_path.glob("linreg-fista-*.npz"))
    reset_trace()
    m1 = LinearRegression(**kw).fit(df)
    resumes = [e for e in get_trace_events() if e.name == "fista_resume"]
    assert resumes and resumes[0].detail == "it=5"
    np.testing.assert_allclose(
        np.asarray(m0.coef_), np.asarray(m1.coef_), rtol=1e-6, atol=1e-8
    )
    assert not list(tmp_path.glob("*.npz"))


# ---------------------------------------------------------------------------
# satellite: checkpoint_file_for collision behavior — two solvers with
# different content tags in one checkpoint_dir never read each other's state
# ---------------------------------------------------------------------------


def test_checkpoint_tags_never_collide(tmp_path):
    d = str(tmp_path)
    tag_a = "kmeans|/data/a.parquet|n=1000|d=4|k=3|seed=1"
    tag_b = "kmeans|/data/a.parquet|n=1000|d=4|k=9|seed=1"
    tag_c = "logreg|/data/a.parquet|n=1000|d=4|C=2|l2=0.1"
    paths = [checkpoint_file_for(d, t) for t in (tag_a, tag_b, tag_c)]
    assert len(set(paths)) == 3, "distinct tags -> distinct filenames"
    assert os.path.basename(paths[0]).startswith("kmeans-")
    assert os.path.basename(paths[2]).startswith("logreg-")

    save_checkpoint(paths[0], tag_a, {"centers": np.zeros((3, 4)), "it": 5})
    save_checkpoint(paths[1], tag_b, {"centers": np.ones((9, 4)), "it": 2})
    a = load_checkpoint(paths[0], tag_a)
    b = load_checkpoint(paths[1], tag_b)
    assert a["centers"].shape == (3, 4) and int(a["it"]) == 5
    assert b["centers"].shape == (9, 4) and int(b["it"]) == 2
    # even under a forced filename collision the in-file tag refuses the
    # foreign state: solver B can never consume solver A's checkpoint
    with pytest.warns(UserWarning, match="different fit"):
        assert load_checkpoint(paths[0], tag_b) is None


def test_two_estimators_share_checkpoint_dir(tmp_path, rng):
    # end-to-end collision check: two interrupted fits with different
    # hyperparams park distinct files in ONE dir and each resumes its own
    from spark_rapids_ml_tpu.clustering import KMeans

    df, _ = _kmeans_df(rng, n=400)
    set_config(checkpoint_dir=str(tmp_path), retry_max_attempts=1)
    for k in (2, 4):
        with pytest.raises(SimulatedPreemption):
            with fault_inject("kmeans_lloyd", "preemption", times=1, skip=2):
                KMeans(k=k, seed=1, maxIter=8, tol=0.0).fit(df)
    assert len(list(tmp_path.glob("kmeans-mem-*.npz"))) == 2
    m2 = KMeans(k=2, seed=1, maxIter=8, tol=0.0).fit(df)
    m4 = KMeans(k=4, seed=1, maxIter=8, tol=0.0).fit(df)
    assert m2.cluster_centers_.shape == (2, 4)
    assert m4.cluster_centers_.shape == (4, 4)
    assert not list(tmp_path.glob("*.npz"))


# ---------------------------------------------------------------------------
# satellite: parallel/context.py shutdown/re-init
# ---------------------------------------------------------------------------


def test_shutdown_distributed_idempotent():
    from spark_rapids_ml_tpu.parallel import context

    # single-host: nothing live to tear down, and calling twice is safe
    assert context.shutdown_distributed() is False
    assert context.shutdown_distributed() is False


def test_shutdown_resets_fire_once_state(monkeypatch):
    from spark_rapids_ml_tpu.parallel import context

    monkeypatch.setattr(context, "_distributed_initialized", True)
    context.shutdown_distributed()
    assert context._distributed_initialized is False


def test_reinit_distributed_single_host(monkeypatch):
    import jax

    from spark_rapids_ml_tpu.parallel import context

    def no_cluster(*a, **k):
        raise RuntimeError("no coordinator resolvable")

    monkeypatch.setattr(jax.distributed, "initialize", no_cluster)
    # a stale fire-once flag (the pre-preemption runtime) must not short-
    # circuit the re-init: reinit shuts down first, then bootstraps fresh
    monkeypatch.setattr(context, "_distributed_initialized", True)
    assert context.reinit_distributed() is False
    assert context._distributed_initialized is False


# ---------------------------------------------------------------------------
# satellite: native build timeout carries the command line + partial stderr
# ---------------------------------------------------------------------------


def test_native_build_timeout_context(monkeypatch):
    import spark_rapids_ml_tpu.native as native

    def hung_compiler(cmd, **kw):
        raise subprocess.TimeoutExpired(
            cmd, native._BUILD_TIMEOUT_S,
            stderr=b"In file included from staging.cpp:1:\npartial diagnostics",
        )

    monkeypatch.setattr(native.subprocess, "run", hung_compiler)
    monkeypatch.setattr(native, "_load_failed", False)
    with pytest.raises(native.NativeBuildTimeout) as ei:
        native._build()
    msg = str(ei.value)
    assert "g++" in msg and "staging.cpp" in msg  # the command line
    assert "partial diagnostics" in msg  # the partial stderr
    assert "timed out after 300s" in msg
    # the failure is latched: the next staging call must NOT re-run the
    # full hung compile and pay the timeout again
    assert native._load_failed is True


# ---------------------------------------------------------------------------
# review hardening: multi-fault scheduling, watchdog trace propagation,
# and the streaming_checkpoint_dir alias scope
# ---------------------------------------------------------------------------


def test_multi_fault_site_scheduling():
    # a fault still inside its skip window must not suppress another fault
    # armed at the same site; one occurrence counts once against every
    # armed fault's skip
    fired = []
    with fault_inject("sched_site", "preemption", times=1, skip=5):
        with fault_inject("sched_site", "oom", times=1, skip=0):
            for i in range(8):
                try:
                    maybe_inject("sched_site")
                except SimulatedPreemption:
                    fired.append((i, "preemption"))
                except RuntimeError:
                    fired.append((i, "oom"))
    assert fired == [(0, "oom"), (5, "preemption")]


def test_guarded_worker_preserves_trace_events():
    # tracing storage is thread-local; the watchdog worker adopts the
    # caller's buffer so events inside a guarded dispatch stay visible
    from spark_rapids_ml_tpu.tracing import event

    reset_trace()

    def traced():
        event("inside_guarded", detail="seen")
        return "ok"

    assert guarded(traced, deadline=5.0, label="t") == "ok"
    ev = [e for e in get_trace_events() if e.name == "inside_guarded"]
    assert ev and ev[0].detail == "seen"


def test_streaming_alias_scope(tmp_path):
    # streaming_checkpoint_dir is a fallback for STREAMING fits only: it
    # must never arm in-memory checkpointing (which would silently force
    # the slower stepwise solvers on existing streaming-checkpoint users)
    from spark_rapids_ml_tpu.resilience.checkpoint import resolve_checkpoint_dir

    set_config(streaming_checkpoint_dir=str(tmp_path))
    assert resolve_checkpoint_dir() == ""
    assert resolve_checkpoint_dir(streaming=True) == str(tmp_path)
    set_config(checkpoint_dir=str(tmp_path / "est"))
    assert resolve_checkpoint_dir() == str(tmp_path / "est")
    assert resolve_checkpoint_dir(streaming=True) == str(tmp_path / "est")
