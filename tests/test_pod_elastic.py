#
# Pod-scale fault domain tests (resilience/pod.py + the parallel/context
# seams): bounded cross-process waits with typed ReduceTimeout/RankLost,
# liveness-driven rank-death detection, generation-scoped KV namespaces
# (zombie-rank safety), the shrink-to-survivors RecoveryPlan and its
# share reassignment, hang-doctor stall attribution for blocked reduces,
# and the 2-rank chaos harness: kill -9 one worker mid-fused-pass and
# prove the survivor completes the fit BYTE-identical to a fault-free
# single-process run.
#
import base64
import glob
import json
import os
import signal
import socket
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _pod_reset():
    """Every test in this file starts and ends with a pristine pod layer
    and default config: no topology override, generation 0, zeroed
    counters, empty chunk cache."""
    from spark_rapids_ml_tpu.config import reset_config
    from spark_rapids_ml_tpu.parallel import device_cache as dc
    from spark_rapids_ml_tpu.resilience.pod import reset_pod

    reset_pod()
    reset_config()
    yield
    dc.clear_chunk_cache()
    reset_pod()
    reset_config()


class FakeKV:
    """A dict-backed stand-in for the coordination-service client: the
    same string API (write-once set, blocking get that raises on a
    missing key after the timeout)."""

    def __init__(self, store=None, get_delay_s=0.0, block_full=False):
        self.store = dict(store or {})
        self.get_delay_s = get_delay_s
        self.block_full = block_full
        self.gets = []

    def key_value_set(self, key, value):
        self.store.setdefault(key, value)

    def blocking_key_value_get(self, key, timeout_ms):
        self.gets.append(key)
        if key in self.store:
            if self.get_delay_s:
                time.sleep(self.get_delay_s)
            return self.store[key]
        # the real client blocks for timeout_ms then raises; sleeping
        # (the full window with block_full, else a bounded slice) keeps
        # kv_wait's deadline accounting honest
        time.sleep(
            timeout_ms / 1000.0 if self.block_full
            else min(timeout_ms / 1000.0, 0.25)
        )
        raise RuntimeError(f"DEADLINE_EXCEEDED: {key}")


# ---------------------------------------------------------------------------
# DETECT: bounded waits, typed errors, liveness
# ---------------------------------------------------------------------------


def test_no_raw_kv_waits_in_context():
    """Satellite 1: every cross-process KV get in parallel/context.py
    must route through the pod layer's bounded kv_wait — a raw client
    blocking_key_value_get call is an unbounded hang waiting to
    happen."""
    src = open(
        os.path.join(REPO, "spark_rapids_ml_tpu", "parallel", "context.py")
    ).read()
    offenders = [
        ln.strip()
        for ln in src.splitlines()
        if ".blocking_key_value_get(" in ln.split("#", 1)[0]
    ]
    assert offenders == [], offenders
    assert "kv_wait" in src  # the sanctioned path is actually in use


def test_kv_wait_disabled_times_out_typed_and_bounded():
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.resilience.pod import (
        POD_METRICS, ReduceTimeout, kv_wait,
    )

    set_config(pod_elastic="off")
    t0 = time.monotonic()
    with pytest.raises(ReduceTimeout) as ei:
        kv_wait(FakeKV(), "srmt/g0/ag/t/0/1", 300, tag="t#0", peer=1)
    waited = time.monotonic() - t0
    assert waited < 5.0  # bounded: never the prior unbounded block
    assert ei.value.tag == "t#0" and ei.value.key == "srmt/g0/ag/t/0/1"
    assert "multiproc_reduce_timeout_s" in str(ei.value)
    assert POD_METRICS["reduce_timeouts"] >= 1


def test_kv_wait_returns_payload_and_notes_interval():
    from spark_rapids_ml_tpu.resilience.pod import kv_wait
    from spark_rapids_ml_tpu.telemetry import utilization

    utilization.clear()
    client = FakeKV({"k": "v"}, get_delay_s=0.01)
    assert kv_wait(client, "k", 1000, tag="fused_pass#0", peer=1) == "v"
    evs = [e for e in utilization.timeline() if e[1] == "reduce_wait"]
    assert evs, "kv_wait must land a reduce_wait utilization interval"
    # the cause names the blocked reduce tag AND the peer rank
    assert evs[-1][2] == "fused_pass#0:rank1"
    assert evs[-1][5] == "any"  # visible to fit and serving views alike


def test_kv_wait_rank_lost_early_via_liveness():
    """With pod_elastic on, a peer whose heartbeat never advances past
    the grace window raises RankLost EARLY — long before the full
    reduce deadline — naming the dead boot rank."""
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.parallel.context import set_topology_override
    from spark_rapids_ml_tpu.resilience.pod import RankLost, kv_wait

    set_config(
        pod_elastic="on", pod_heartbeat_interval_s=0.05,
        pod_death_grace_s=0.2,
    )
    set_topology_override(2, 0)
    t0 = time.monotonic()
    with pytest.raises(RankLost) as ei:
        # 30s deadline: the early liveness exit is what keeps this fast
        kv_wait(FakeKV(), "srmt/g0/ag/t/0/1", 30_000, tag="t#0", peer=1)
    assert time.monotonic() - t0 < 10.0
    assert ei.value.lost_ranks == [1]
    assert ei.value.tag == "t#0"


def test_kv_wait_straggler_keeps_waiting_to_deadline():
    """A slow-but-beating peer is NOT a corpse: kv_wait must run to the
    full deadline (ReduceTimeout), never declare RankLost."""
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.parallel.context import set_topology_override
    from spark_rapids_ml_tpu.resilience.pod import ReduceTimeout, kv_wait

    set_config(
        pod_elastic="on", pod_heartbeat_interval_s=0.05,
        pod_death_grace_s=30.0,  # generous grace: the peer counts as live
    )
    set_topology_override(2, 0)
    with pytest.raises(ReduceTimeout):
        kv_wait(FakeKV(), "srmt/g0/ag/t/0/1", 400, tag="t#0", peer=1)


def test_reduce_disabled_wire_raises_typed_not_hang(monkeypatch):
    """Acceptance: with pod_elastic=off, a wire reduce against a dead
    peer produces a typed error within multiproc_reduce_timeout_s —
    never a hang (the wedge guard in CI backs this assertion)."""
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.parallel import context
    from spark_rapids_ml_tpu.resilience.pod import ReduceTimeout

    set_config(
        pod_elastic="off", multiproc_reduce="wire",
        multiproc_reduce_timeout_s=0.5, multiproc_agreement_check=False,
    )
    context.set_topology_override(2, 0)
    monkeypatch.setattr(context, "_coordination_client", lambda: FakeKV())
    t0 = time.monotonic()
    with pytest.raises(ReduceTimeout):
        context.reduce_host_arrays({"s": np.ones(3)}, "t_pod_off")
    assert time.monotonic() - t0 < 10.0


# ---------------------------------------------------------------------------
# SHRINK: generations, zombie safety, the RecoveryPlan
# ---------------------------------------------------------------------------


def test_zombie_generation_keys_are_never_read(monkeypatch):
    """Zombie-rank safety: a payload written under a dead generation's
    namespace is invisible to the recovered quorum — the allgather reads
    ONLY the current generation's keys."""
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.parallel import context
    from spark_rapids_ml_tpu.resilience.pod import advance_generation

    set_config(pod_elastic="off", multiproc_reduce_timeout_s=5.0)
    fake = FakeKV({
        # the zombie: rank 1's stale partial, written under generation 0
        "srmt/g0/ag/z/0/1": base64.b64encode(b"zombie").decode(),
        # the fresh quorum's payload under generation 1
        "srmt/g1/ag/z/0/1": base64.b64encode(b"fresh").decode(),
    })
    monkeypatch.setattr(context, "_coordination_client", lambda: fake)
    context.set_topology_override(2, 0)
    assert advance_generation("test") == 1
    out = context.allgather_bytes("z", b"mine")
    assert out == [b"mine", b"fresh"]
    assert all(not k.startswith("srmt/g0/") for k in fake.gets), fake.gets
    # this rank's own payload landed in the new generation's namespace
    assert "srmt/g1/ag/z/0/0" in fake.store


def test_recovery_plan_reassigns_dead_shares_deterministically():
    from spark_rapids_ml_tpu.parallel.context import (
        process_topology, set_topology_override,
    )
    from spark_rapids_ml_tpu.resilience.pod import (
        POD_METRICS, active_recovery_plan, recover_from_rank_loss,
        simulate_rank_loss,
    )

    set_topology_override(4, 0)
    exc = simulate_rank_loss("t", rank=3)
    assert exc.lost_ranks == [3]
    assert recover_from_rank_loss(exc)
    plan = active_recovery_plan()
    assert plan is not None
    assert plan.prior_n == 4 and plan.share_n == 4
    assert plan.dead_ranks == (3,) and plan.survivors == (0, 1, 2)
    assert plan.boot_ranks == (0, 1, 2)
    # every original share covered exactly once across the survivors
    covered = sorted(
        s for v in plan.assignments.values() for s, _o in v
    )
    assert covered == [0, 1, 2, 3]
    # each survivor keeps its own share (cache affinity): owner == boot
    for r in (0, 1, 2):
        assert plan.assignments[r][0] == (r, r)
    assert process_topology() == (3, 0)
    assert POD_METRICS["rank_losses_detected"] == 1
    assert POD_METRICS["shares_reassigned"] == 1
    assert POD_METRICS["pod_recoveries_total"] == 1

    # CHAINED loss: share_n is inherited from the ORIGINAL partition and
    # the newly-dead survivor's entries are redistributed
    exc2 = simulate_rank_loss("t")
    assert recover_from_rank_loss(exc2)
    plan2 = active_recovery_plan()
    assert plan2.share_n == 4  # not 3: the parquet partition is fixed
    covered2 = sorted(
        s for v in plan2.assignments.values() for s, _o in v
    )
    assert covered2 == [0, 1, 2, 3]
    assert process_topology() == (2, 0)
    assert POD_METRICS["generation"] == 2


def test_straggler_timeout_without_dead_rank_declines_recovery():
    """A ReduceTimeout with nobody provably dead must NOT shrink the
    quorum (the peer may just be slow): recover returns False and the
    caller falls back to the full re-bootstrap path."""
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.parallel.context import (
        set_topology_override, topology_overridden,
    )
    from spark_rapids_ml_tpu.resilience.pod import (
        ReduceTimeout, active_recovery_plan, recover_from_rank_loss,
    )

    set_config(pod_elastic="on")
    set_topology_override(2, 0)
    assert not recover_from_rank_loss(ReduceTimeout("t", waited_s=1.0))
    assert active_recovery_plan() is None
    assert topology_overridden()  # untouched: no shrink happened


def test_rank_loss_classification_respects_pod_elastic_gate():
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.resilience.pod import RankLost, ReduceTimeout
    from spark_rapids_ml_tpu.resilience.retry import classify_error

    set_config(pod_elastic="on")
    assert classify_error(RankLost([1], tag="t")) == "rank_loss"
    assert classify_error(ReduceTimeout("t")) == "rank_loss"
    set_config(pod_elastic="off")
    # off: typed, bounded, FATAL — the operator asked for no elasticity
    assert classify_error(RankLost([1], tag="t")) == "fatal"
    assert classify_error(ReduceTimeout("t")) == "fatal"


# ---------------------------------------------------------------------------
# Satellite 2: repeated reinit cycles, config-driven coordinator moves
# ---------------------------------------------------------------------------


def test_reinit_cycles_have_no_state_bleed(monkeypatch):
    """Three full reinit_distributed cycles against three coordinator
    addresses published via set_config: each cycle must re-read the
    address, bump the generation, clear the per-tag KV sequence
    counters, and drop any recovery plan / topology override — no state
    bleeds from one bootstrap into the next."""
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.parallel import context
    from spark_rapids_ml_tpu.resilience import pod

    seen = []
    monkeypatch.setattr(context, "shutdown_distributed", lambda: None)
    monkeypatch.setattr(
        context,
        "init_distributed",
        lambda coordinator_address=None, num_processes=None, process_id=None: (
            seen.append(coordinator_address) or True
        ),
    )
    addrs = ["10.0.0.1:1234", "10.0.0.2:5678", "10.0.0.3:9012"]
    gens = []
    try:
        for i, addr in enumerate(addrs):
            # dirty every piece of per-bootstrap state the reinit must wipe
            with context._kv_lock:
                context._kv_seq[f"tag{i}"] = 7
            context.set_topology_override(2, 0)
            exc = pod.simulate_rank_loss("cycle")
            assert pod.recover_from_rank_loss(exc)
            assert pod.active_recovery_plan() is not None
            context._reduce_backend_resolved = "wire"

            set_config(coordinator_address=addr)
            assert context.reinit_distributed()

            with context._kv_lock:
                assert context._kv_seq == {}, f"cycle {i}: kv seq bled"
            assert pod.active_recovery_plan() is None
            assert not context.topology_overridden()
            assert pod.simulated_dead_ranks() == frozenset()
            assert context._reduce_backend_resolved is None
            gens.append(pod.generation())
    finally:
        set_config(coordinator_address="")
    assert seen == addrs
    # each cycle bumped the generation past the recovery's own bump
    assert gens == sorted(set(gens)) and len(gens) == 3


# ---------------------------------------------------------------------------
# RESUME: the one-box state machine, end to end
# ---------------------------------------------------------------------------


def _write_parquet(tmp_path, n=512, d=4, seed=0, row_group_size=64):
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(seed)
    X = rng.integers(-8, 8, size=(n, d)).astype(np.float64)
    y = rng.integers(-8, 8, size=n).astype(np.float64)
    path = str(tmp_path / "pod.parquet")
    cols = {f"f{i}": X[:, i] for i in range(d)}
    cols["label"] = y
    pq.write_table(pa.table(cols), path, row_group_size=row_group_size)
    return path, X, y


def test_injected_rank_loss_recovers_with_byte_parity(tmp_path):
    """The whole detect -> shrink -> resume machine on one box: the
    `rank_lost` fault kind fails a fused pass mid-flight, the retry loop
    recovers (simulated 2-rank topology shrinks to the survivor), and
    the restarted pass covers EVERY original share — statistics byte-
    identical to the fault-free fit, one rank_loss flight-recorder
    bundle with the pass manifest and liveness table attached."""
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.fused import fused_linreg_stats, iter_parquet_chunks
    from spark_rapids_ml_tpu.resilience import retry
    from spark_rapids_ml_tpu.resilience.faults import fault_inject
    from spark_rapids_ml_tpu.resilience.pod import POD_METRICS, reset_pod

    d = 4
    path, _X, _y = _write_parquet(tmp_path, d=d)
    frdir = str(tmp_path / "fr")
    set_config(pod_elastic="on", flight_recorder_dir=frdir)
    fcols = tuple(f"f{i}" for i in range(d))

    def producer(n_dev):
        prep = {"s": 0.0, "iv": []}
        return (
            iter_parquet_chunks(
                path, None, fcols, "label", None, 128, np.float64,
                prep=prep,
            ),
            prep,
        )

    ref = fused_linreg_stats(producer, d, np.float64)
    reset_pod()

    with fault_inject("fused_accumulate", "rank_lost", times=1):
        got = retry.retry_call(
            lambda: fused_linreg_stats(producer, d, np.float64),
            label="pod_linreg",
        )

    for k in sorted(ref):
        assert (
            np.asarray(ref[k]).tobytes() == np.asarray(got[k]).tobytes()
        ), f"{k} diverged from the fault-free fit"
    assert POD_METRICS["rank_losses_detected"] == 1
    assert POD_METRICS["pod_recoveries_total"] == 1
    assert POD_METRICS["shares_reassigned"] == 1
    bundles = glob.glob(os.path.join(frdir, "postmortem_rank_loss_*"))
    assert len(bundles) == 1
    names = set(os.listdir(bundles[0]))
    assert {"liveness.json", "recovery_plan.json"} <= names
    man = json.load(open(os.path.join(bundles[0], "manifest.json")))
    assert man["reason"] == "rank_loss"
    liveness = json.load(open(os.path.join(bundles[0], "liveness.json")))
    assert liveness["1"]["simulated_dead"] is True
    plan = json.load(open(os.path.join(bundles[0], "recovery_plan.json")))
    assert plan["share_n"] == 2 and plan["survivors"] == [0]


def test_kv_timeout_fault_kind_is_typed():
    from spark_rapids_ml_tpu.resilience.faults import fault_inject, maybe_inject
    from spark_rapids_ml_tpu.resilience.pod import ReduceTimeout

    with fault_inject("kv_wait", "kv_timeout", times=1, seconds=1.5):
        with pytest.raises(ReduceTimeout) as ei:
            maybe_inject("kv_wait")
    assert ei.value.waited_s == 1.5 and "kv_wait" in ei.value.key


# ---------------------------------------------------------------------------
# Satellite 4: vanished spill blob x rank loss — degrade, don't diverge
# ---------------------------------------------------------------------------


def test_vanished_spill_composes_with_rank_loss_recovery(tmp_path):
    """A survivor whose own spilled chunk-cache stream vanished from
    `chunk_cache_spill_dir` must degrade to source replay during the
    reassigned-share recovery pass — both failure modes at once, byte
    parity still held."""
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.fused import iter_parquet_chunks
    from spark_rapids_ml_tpu.parallel import device_cache as dc
    from spark_rapids_ml_tpu.parallel.context import set_topology_override
    from spark_rapids_ml_tpu.resilience.pod import (
        recover_from_rank_loss, simulate_rank_loss,
    )

    d = 3
    path, X, _y = _write_parquet(tmp_path, n=400, d=d, row_group_size=50)
    spill_dir = str(tmp_path / "spill")
    set_config(
        pod_elastic="on", chunk_cache="on", chunk_cache_host_bytes=1,
        chunk_cache_spill_dir=spill_dir,
    )
    fcols = tuple(f"f{i}" for i in range(d))

    def _rows(chunks):
        # chunks may be tail-padded; cw is the validity mask then
        out = []
        for cX, _cy, cw in chunks:
            cX = np.array(cX)
            out.append(cX if cw is None else cX[np.asarray(cw) > 0])
        return out

    # phase 1: simulated rank 0 of 2 decodes (and spills) ONLY its share
    set_topology_override(2, 0)
    mine = _rows(iter_parquet_chunks(
        path, None, fcols, None, None, 64, np.float64
    ))
    assert 0 < sum(c.shape[0] for c in mine) < 400
    assert glob.glob(os.path.join(spill_dir, "*.spill"))

    # rank 1 dies; the survivor's own spill blobs ALSO vanish
    assert recover_from_rank_loss(simulate_rank_loss("t"))
    for f in glob.glob(os.path.join(spill_dir, "*.spill")):
        os.unlink(f)

    # phase 2: the recovery pass — own share degrades to source replay
    # (checksum_failures bumps), the reassigned share decodes fresh
    before = dc.CHUNK_METRICS["checksum_failures"]
    rows = _rows(iter_parquet_chunks(
        path, None, fcols, None, None, 64, np.float64
    ))
    assert dc.CHUNK_METRICS["checksum_failures"] > before
    got = np.concatenate(rows, axis=0)
    assert got.tobytes() == X.tobytes()  # every row, once, in file order


# ---------------------------------------------------------------------------
# Satellite 3: hang-doctor attribution for blocked reduces
# ---------------------------------------------------------------------------


def test_hang_doctor_names_blocked_reduce_and_peer(tmp_path):
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.resilience.pod import kv_wait
    from spark_rapids_ml_tpu.telemetry.flight_recorder import RECORDER
    from spark_rapids_ml_tpu.telemetry.hang_doctor import HangDoctor

    set_config(
        pod_elastic="off", hang_doctor="off", hang_doctor_stall_s=0.3,
        flight_recorder_dir=str(tmp_path),
    )
    RECORDER.clear()
    done = threading.Event()

    def blocked():
        try:
            kv_wait(
                FakeKV(block_full=True), "srmt/g0/ag/fused_pass/0/1",
                3_000, tag="fused_pass#0", peer=1,
            )
        except Exception:
            pass
        finally:
            done.set()

    t = threading.Thread(target=blocked, name="pod-reduce-waiter")
    t.start()
    doc = HangDoctor(force_enabled=True)
    try:
        time.sleep(0.5)
        bdir = doc.tick()
        assert bdir and os.path.isdir(bdir)
        wf = json.load(open(os.path.join(bdir, "waitfor.json")))
        assert wf["kind"] == "reduce_wait"
        waits = wf["reduce_waits"]
        assert waits and waits[0]["tag"] == "fused_pass#0"
        assert waits[0]["peer"] == 1
        man = json.load(open(os.path.join(bdir, "manifest.json")))
        assert "fused_pass#0" in man["detail"]
        assert "rank 1" in man["detail"]
        # same episode: no second bundle while the wait persists
        assert doc.tick() is None
    finally:
        done.wait(timeout=15)
        t.join(timeout=15)
        RECORDER.clear()


# ---------------------------------------------------------------------------
# The 2-rank chaos harness (coordination service only)
# ---------------------------------------------------------------------------


_CHAOS_WORKER = textwrap.dedent(
    """
    import json, os, signal, sys
    pid, nproc, port, outfile, ppath, frdir = (
        int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4],
        sys.argv[5], sys.argv[6],
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    sys.path.insert(0, os.environ["SRMT_REPO"])
    import numpy as np
    # on the main thread, before any prefetch thread can be the first to
    # import it: see tests/test_fleet_observatory.py _COMMON_PRELUDE
    import pyarrow  # noqa: F401
    from spark_rapids_ml_tpu import init_distributed
    from spark_rapids_ml_tpu.config import set_config
    set_config(
        coordinator_address=f"127.0.0.1:{port}", num_processes=nproc,
        process_id=pid, multiproc_reduce="wire",
        multiproc_reduce_timeout_s=60.0, fused_parquet_readers=1,
        pod_elastic="on", pod_heartbeat_interval_s=0.25,
        pod_death_grace_s=2.0,
        flight_recorder_dir=(frdir if pid == 0 else ""),
    )
    assert init_distributed()
    import jax
    assert jax.process_count() == nproc

    if pid == 1:
        # the chaos: SIGKILL myself on the SECOND chunk of the fused
        # pass — a mid-pass hard death, no atexit, no cleanup.  Patch
        # the package-level fault hook (accumulate_chunks resolves
        # `maybe_inject` from the package at call time).
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

    d = 6
    CHUNK = 128
    from spark_rapids_ml_tpu.fused import (
        fused_linreg_stats, iter_parquet_chunks,
    )

    def producer(n_dev):
        prep = {"s": 0.0, "iv": []}
        return (
            iter_parquet_chunks(
                ppath, "features", (), "label", None, CHUNK, np.float64,
                prep=prep,
            ),
            prep,
        )

    from spark_rapids_ml_tpu.resilience import retry
    from spark_rapids_ml_tpu.resilience.pod import POD_METRICS
    lin = retry.retry_call(
        lambda: fused_linreg_stats(producer, d, np.float64),
        label="chaos_linreg",
    )

    # only the survivor reaches this point
    def hexd(a):
        return np.ascontiguousarray(np.asarray(a, np.float64)).tobytes().hex()

    if pid == 0:
        import glob
        out = {
            "linreg": {k: hexd(v) for k, v in sorted(lin.items())},
            "metrics": {k: int(v) for k, v in POD_METRICS.items()},
            "bundles": sorted(
                os.path.basename(b)
                for b in glob.glob(
                    os.path.join(frdir, "postmortem_rank_loss_*")
                )
            ),
        }
        with open(outfile, "w") as f:
            json.dump(out, f)
        f_sync = open(outfile)
        f_sync.close()
    # hard exit: the atexit jax.distributed shutdown barrier can only
    # time out against a SIGKILLed peer and then SIGABRTs the process
    # (the coordination runtime still considers the BOOT world
    # authoritative) — the fit's work is already durably reported above
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)
    """
)


def _launch_chaos(script_body, nproc, tmp_path, args=(), timeout=600):
    """Like test_multihost_datapath._launch, but kill-tolerant: rank 0
    must exit 0; HIGHER ranks may die by SIGKILL (that is the test)."""
    script = tmp_path / "chaos_worker.py"
    script.write_text(script_body)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    outfile = tmp_path / "chaos_out.json"
    env = {
        k: v
        for k, v in os.environ.items()
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
    # rank 0 (the survivor) must succeed...
    assert errs[0][0] == 0, errs[0][1][-6000:]
    # ...and at least one higher rank must actually have been SIGKILLed
    assert any(rc == -signal.SIGKILL for rc, _ in errs[1:]), [
        rc for rc, _ in errs
    ]
    with open(outfile) as f:
        return json.load(f)


def test_two_rank_chaos_kill_mid_pass_survivor_parity(tmp_path):
    """THE acceptance chaos run: 2 ranks fit a fused linear regression,
    rank 1 is SIGKILLed mid-pass; rank 0 must detect the death via
    liveness, shrink to a quorum of one, replay + decode every share,
    and produce coefficients BYTE-identical to a fault-free
    single-process fit — plus exactly one rank_loss bundle and
    rank_losses_detected == 1."""
    import pandas as pd

    d = 6
    rng = np.random.default_rng(7)
    X = rng.integers(-10, 10, size=(4000, d)).astype(np.float64)
    y = rng.integers(-10, 10, size=4000).astype(np.float64)
    ppath = str(tmp_path / "chaos.parquet")
    pd.DataFrame({"features": list(X), "label": y}).to_parquet(
        ppath, row_group_size=250
    )
    frdir = str(tmp_path / "fr")

    out = _launch_chaos(
        _CHAOS_WORKER, 2, tmp_path, args=(ppath, frdir), timeout=420
    )

    # fault-free reference, computed in this process (single rank): the
    # integer-valued data makes every partial sum exact, so the device
    # count difference cannot perturb a single byte
    from spark_rapids_ml_tpu.fused import fused_linreg_stats, iter_parquet_chunks

    def producer(n_dev):
        prep = {"s": 0.0, "iv": []}
        return (
            iter_parquet_chunks(
                ppath, "features", (), "label", None, 128, np.float64,
                prep=prep,
            ),
            prep,
        )

    ref = fused_linreg_stats(producer, d, np.float64)

    def hexd(a):
        return np.ascontiguousarray(np.asarray(a, np.float64)).tobytes().hex()

    for k in sorted(ref):
        assert out["linreg"][k] == hexd(ref[k]), (
            f"{k}: survivor diverged from the fault-free fit"
        )
    assert out["metrics"]["rank_losses_detected"] == 1
    assert out["metrics"]["pod_recoveries_total"] == 1
    assert out["metrics"]["shares_reassigned"] == 1
    assert len(out["bundles"]) == 1, out["bundles"]
