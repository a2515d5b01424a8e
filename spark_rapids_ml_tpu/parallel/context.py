#
# TpuContext — the analog of the reference's `CumlContext` context manager
# (reference common/cuml_context.py:35-206).  The reference bootstraps NCCL
# (rank 0 creates a unique id, Spark barrier `allGather` distributes it,
# cuml_context.py:96-102), optionally builds a UCX endpoint mesh for p2p
# (cuml_context.py:104-115), and injects both into a RAFT handle.
#
# On TPU the same responsibilities map to:
#   - NCCL uid allGather bootstrap  ->  `jax.distributed.initialize`
#     (coordinator address + process id + process count)
#   - RAFT handle with comms        ->  `jax.sharding.Mesh` over the global
#     device set; XLA emits ICI/DCN collectives from shardings
#   - UCX p2p endpoint mesh         ->  `jax.lax.ppermute` / all_to_all
#     (no explicit endpoints: the compiler schedules transfers)
#   - teardown destroy()/abort()    ->  `jax.distributed.shutdown`
#
from __future__ import annotations

import base64
import hashlib
import io
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from ..config import get_config
from ..telemetry.locks import named_lock
from ..utils import get_logger
from .mesh import get_mesh

_distributed_initialized = False

# The EFFECTIVE process topology every cross-process seam gates on.
# `jax.process_count()` is the BOOT view — the runtime caches it, and it
# stays stale after a rank dies (tearing the backend down would
# invalidate every live array).  Pod recovery (resilience/pod.py)
# installs the surviving quorum here instead: reductions, share
# partitioning, and cache keys all follow the override while the local
# device mesh keeps the backend view.  None -> the jax view.
_topology_override: "Optional[tuple]" = None


def process_topology() -> "tuple[int, int]":
    """(nranks, rank) as the data path should see it: the pod-recovery
    override when one is installed (survivor quorum, or a simulated
    topology from the `rank_lost` fault kind), the jax.distributed view
    otherwise.  Every reduction gate and ingest-share computation reads
    this instead of `jax.process_count()` directly."""
    if _topology_override is not None:
        return _topology_override
    return int(jax.process_count()), int(jax.process_index())


def topology_overridden() -> bool:
    return _topology_override is not None


def set_topology_override(nranks: int, rank: int) -> None:
    global _topology_override
    if not (0 <= int(rank) < int(nranks)):
        raise ValueError(f"invalid topology override ({nranks}, {rank})")
    _topology_override = (int(nranks), int(rank))


def clear_topology_override() -> None:
    global _topology_override
    _topology_override = None


class RankDivergenceError(RuntimeError):
    """The content fingerprints of a cross-process reduction disagree
    across ranks: the processes are merging statistics computed from
    DIFFERENT inputs (shapes, dtypes, or accumulator keys differ).
    Raised before any merge happens — a silently mis-merged model is
    strictly worse than a loud failure.  Carries the per-rank
    fingerprints so the operator can see which rank diverged."""

    def __init__(self, tag: str, fingerprints: List[str]) -> None:
        self.tag = tag
        self.fingerprints = list(fingerprints)
        lines = ", ".join(
            f"rank{r}={fp[:16]}" for r, fp in enumerate(self.fingerprints)
        )
        super().__init__(
            f"cross-process reduction {tag!r}: content fingerprints "
            f"diverge across ranks ({lines}) — the processes are not "
            "reducing the same statistic layout; check that every rank "
            "ingested the same dataset schema and program set"
        )


class DeviceLoss(RuntimeError):
    """One or more devices vanished mid-fit (spot reclaim of a worker's
    chips, ICI/PCIe failure).  Typed — carrying the lost device list —
    so the elastic recovery layer (resilience/elastic.py) can shrink the
    mesh to the survivors instead of treating the failure as an opaque
    crash.  The message is deliberately shaped like the jaxlib runtime
    error family ('failed to execute ... device') so the string
    classifier (resilience/retry.py `is_device_loss`) routes real and
    typed losses identically."""

    def __init__(self, lost_devices) -> None:
        self.lost_devices = list(lost_devices)
        ids = [getattr(d, "id", d) for d in self.lost_devices]
        super().__init__(
            f"failed to execute on device(s) {ids}: device lost "
            "(detected by the post-dispatch health probe)"
        )


def probe_device_health(devices=None) -> list:
    """Cheap post-dispatch health probe: a tiny host->device->host
    round-trip per device (a scalar, so the probe costs microseconds per
    chip).  Returns the devices that failed the round-trip — on a
    healthy mesh, an empty list.  A collective that hung or died only
    says 'something failed'; this probe turns it into WHICH devices are
    gone, the input the elastic recovery layer plans its degraded mesh
    from.  Simulated losses (the `device_lost` fault kind) are layered
    on top by `resilience.elastic.probe_lost_devices`, which is what
    recovery paths should call."""
    import numpy as np

    from ..telemetry.registry import counter
    from ..tracing import trace

    devices = list(devices) if devices is not None else list(jax.devices())
    lost = []
    with trace("device_health_probe"):
        for d in devices:
            try:
                host = np.asarray(
                    jax.device_get(jax.device_put(np.zeros((), np.float32), d))
                )
                if host.shape != ():  # pragma: no cover - defensive
                    lost.append(d)
            except Exception:
                lost.append(d)
    counter(
        "device_health_probes_total", "Per-device health round-trips"
    ).inc(len(devices))
    if lost:
        counter(
            "device_probe_failures_total",
            "Devices that failed the health round-trip",
        ).inc(len(lost))
    return lost


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Bootstrap `jax.distributed` for multi-host (pod) fits — the analog of
    the reference's NCCL-uid allGather bootstrap (cuml_context.py:96-102).

    Resolution order for the coordinator:
      1. explicit arguments,
      2. library config (`set_config(coordinator_address=..., ...)` or the
         `SPARK_RAPIDS_ML_TPU_COORDINATOR_ADDRESS` env tier),
      3. ambient cluster environment (TPU pod metadata / `JAX_COORDINATOR_*`
         / SLURM / OMPI vars), which `jax.distributed.initialize()` reads
         with no arguments.

    Call this before any other JAX use on each process.  Returns True if
    distributed mode was (already) initialized, False when no coordinator
    could be resolved (single-host mode).  Idempotent.
    """
    global _distributed_initialized
    # NB: do not touch jax.process_count()/jax.devices() here — they
    # initialize the XLA backend, after which distributed init is rejected
    if _distributed_initialized or jax.distributed.is_initialized():
        _distributed_initialized = True
        _start_pod_liveness()
        return True
    coord = coordinator_address or get_config("coordinator_address")
    if coord:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=(
                num_processes
                if num_processes is not None
                else get_config("num_processes")
            ),
            process_id=(
                process_id if process_id is not None else get_config("process_id")
            ),
        )
        _distributed_initialized = True
        _start_pod_liveness()
        return True
    import os

    env_indicated = any(
        v in os.environ
        for v in ("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS")
    )
    try:
        # cluster auto-detection: jax resolves the coordinator itself on
        # TPU pods (metadata server), GKE, SLURM and OMPI; on plain
        # single-host machines it raises, which means single-host mode
        jax.distributed.initialize()
    except (ValueError, RuntimeError) as e:
        if env_indicated:
            # the environment names a coordinator: a bootstrap failure here
            # is a real error, not "no cluster" — silently degrading would
            # fit a different model on every host
            raise
        get_logger("spark_rapids_ml_tpu.init_distributed").debug(
            f"no cluster auto-detected ({type(e).__name__}: {e}); "
            "running single-host"
        )
        return False
    _distributed_initialized = True
    _start_pod_liveness()
    return True


def _start_pod_liveness() -> None:
    """Best-effort heartbeat bootstrap (resilience/pod.py): with
    `pod_elastic` on, every rank beats from the moment distributed mode
    comes up, so a peer killed before its first reduction is still
    nameable by the survivors' liveness probe."""
    try:
        from ..resilience.pod import maybe_start_heartbeat

        maybe_start_heartbeat()
    except Exception:  # pragma: no cover - liveness must never block init
        pass


def shutdown_distributed() -> bool:
    """Tear down `jax.distributed` so a later `init_distributed` can
    bootstrap fresh — the analog of the reference's NCCL comm
    destroy/abort (cuml_context.py:163-180), which the fire-once module
    global above otherwise makes impossible.  Idempotent: returns True
    when a live runtime was shut down, False when there was nothing to
    tear down (single-host mode, or already shut down)."""
    global _distributed_initialized
    was_live = False
    if jax.distributed.is_initialized():
        jax.distributed.shutdown()
        was_live = True
    _distributed_initialized = False
    return was_live


def reinit_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Re-bootstrap `jax.distributed` after a preemption: the preempted
    worker's coordinator channel is dead, so `init_distributed`'s
    idempotence (correct in the steady state) would silently hand back the
    STALE runtime.  Shutdown first, then the normal resolution order.
    Returns True when distributed mode came (back) up, False in
    single-host mode.  The resilience layer's preemption hook
    (resilience/retry.py) calls this before re-dispatching; iterative
    solvers then resume from their checkpoint.

    The coordinator address is re-resolved from CONFIG at call time
    (unless overridden by the explicit argument): a coordinator that
    restarted elsewhere publishes its new address via
    `set_config(coordinator_address=...)` / the env tier, and a reinit
    that reused the first bootstrap's cached address would reconnect
    every worker to a dead endpoint."""
    shutdown_distributed()
    global _reduce_backend_resolved
    _reduce_backend_resolved = None  # re-probe collectives on the new runtime
    globals().pop("_psum_probe_result", None)
    # a re-bootstrap is a fresh quorum: the pod layer drops its recovery
    # plan / topology override / liveness history and bumps the reduction
    # GENERATION, so no KV key (or zombie write) from the previous
    # bootstrap can bleed into the new one
    try:
        from ..resilience.pod import on_reinit

        on_reinit()
    except Exception:  # pragma: no cover - import-order defensive
        pass
    coord = coordinator_address or get_config("coordinator_address")
    return init_distributed(
        coordinator_address=coord,
        num_processes=num_processes,
        process_id=process_id,
    )


# ---------------------------------------------------------------------------
# Cross-process broadcast/allgather seam — the analog of the reference's
# NCCL-uid allGather bootstrap (cuml_context.py:96-102), generalized into
# a small-payload exchange plane over the jax.distributed coordination
# service's KV store.  Collective-capable builds (TPU pods, GPU) reduce
# dense accumulators with one jitted psum over the pod mesh; builds whose
# XLA backend cannot run cross-process collectives (CPU) fall back to
# allgathering the versioned wire payloads here and folding on host in
# rank order — deterministic, so integer-representable partial sums stay
# byte-identical to the single-process fold.
# ---------------------------------------------------------------------------

_kv_lock = named_lock("multiproc_kv")
# per-tag monotonic sequence numbers: every rank calls the same reduction
# sites in the same order (the SPMD contract the psum path relies on
# anyway), so the counters stay in lockstep and successive reductions on
# one tag never collide in the shared KV namespace
_kv_seq: Dict[str, int] = {}
_reduce_backend_resolved: Optional[str] = None
_psum_fns: Dict = {}


def _coordination_client():
    """The live coordination-service client, or None outside distributed
    mode.  jax keeps it on `jax._src.distributed.global_state` (the same
    handle `multihost_utils` and cluster bootstrap use) and offers no
    public accessor."""
    from jax._src import distributed as _dist

    return _dist.global_state.client


def _reduce_timeout_ms() -> int:
    return max(1, int(float(get_config("multiproc_reduce_timeout_s")) * 1000))


def reset_kv_epoch() -> None:
    """Drop every per-tag sequence counter: called on each generation
    bump (resilience/pod.py) so the recovered quorum restarts its key
    sequences at 0 inside the NEW generation's disjoint namespace."""
    with _kv_lock:
        _kv_seq.clear()


def _gen_prefix() -> str:
    # every KV key carries the reduction generation: a zombie rank that
    # keeps writing after the quorum shrank lands its payloads in the
    # dead generation's namespace, where no survivor ever reads
    from ..resilience.pod import generation

    return f"srmt/g{generation()}"


def _kv_put(client, key: str, payload: bytes) -> None:
    # the KV store's string API is the one stable across the jaxlib
    # versions we support; base64 keeps arbitrary wire bytes intact
    # (symmetric with _kv_take — never mix with the *_bytes variants)
    client.key_value_set(key, base64.b64encode(payload).decode("ascii"))


def _kv_take(
    client,
    key: str,
    timeout_ms: int,
    tag: str = "",
    peer: Optional[int] = None,
) -> bytes:
    # EVERY cross-process get goes through the pod layer's bounded wait:
    # typed ReduceTimeout/RankLost instead of an unbounded client block
    # (tests assert no raw blocking_key_value_get remains in this module)
    from ..resilience.pod import kv_wait

    return base64.b64decode(
        kv_wait(client, key, timeout_ms, tag=tag, peer=peer)
    )


def coordination_client():
    """Public handle to the live coordination-service client, or None
    outside distributed mode — the pod observatory's entry point to the
    KV seam without reaching into module privates."""
    return _coordination_client()


def kv_publish(key: str, payload: bytes) -> None:
    """Write one generation-namespaced, write-once KV payload under
    `srmt/g{gen}/{key}` (base64 on the wire, symmetric with `kv_fetch`).
    NON-collective — the publish side of the pod observatory's
    pull-based exchanges (incident rings, fleet drift blobs): nobody is
    obligated to read it, and a zombie's late write lands in a dead
    generation's namespace like every other KV key."""
    client = _coordination_client()
    if client is None:
        raise RuntimeError(
            "kv_publish: jax.distributed is not initialized (no "
            "coordination client)"
        )
    _kv_put(client, f"{_gen_prefix()}/{key}", payload)


def kv_fetch(
    key: str,
    timeout_ms: int,
    tag: str = "",
    peer: Optional[int] = None,
) -> bytes:
    """Bounded read of one `kv_publish` payload: goes through the pod
    layer's `kv_wait`, so a missing payload surfaces as typed
    `ReduceTimeout` (or `RankLost` when the peer's heartbeat is gone),
    never an unbounded client block — the pull side of the
    observatory's non-collective exchanges."""
    client = _coordination_client()
    if client is None:
        raise RuntimeError(
            "kv_fetch: jax.distributed is not initialized (no "
            "coordination client)"
        )
    return _kv_take(
        client, f"{_gen_prefix()}/{key}", timeout_ms, tag=tag, peer=peer
    )


def allgather_bytes(
    tag: str, payload: bytes, timeout_s: Optional[float] = None
) -> List[bytes]:
    """Exchange one opaque payload per process; returns every rank's
    payload in rank order, on every rank.  Single-process: [payload].
    Collective contract: every process calls the same `allgather_bytes`
    sites in the same order (SPMD), or tags/sequence numbers desync.
    Every peer wait is bounded (`multiproc_reduce_timeout_s`) and typed:
    a dead or diverged peer surfaces as `ReduceTimeout` — or, with
    `pod_elastic` on and its heartbeat stopped past the grace window, an
    early `RankLost` naming the corpse — never a hang.  Keys live in the
    current reduction GENERATION's namespace, so a zombie rank's delayed
    writes are invisible to a recovered quorum."""
    nranks, rank = process_topology()
    if nranks == 1:
        return [bytes(payload)]
    client = _coordination_client()
    if client is None:
        raise RuntimeError(
            "allgather_bytes: jax.distributed is not initialized (no "
            "coordination client); call init_distributed() first"
        )
    from ..resilience import pod as _pod

    _pod.maybe_start_heartbeat()
    with _kv_lock:
        seq = _kv_seq.get(tag, 0)
        _kv_seq[tag] = seq + 1
    base = f"{_gen_prefix()}/ag/{tag}/{seq}"
    timeout_ms = (
        int(timeout_s * 1000) if timeout_s is not None else _reduce_timeout_ms()
    )
    _kv_put(client, f"{base}/{rank}", payload)
    out: List[bytes] = []
    for peer in range(nranks):
        out.append(
            _kv_take(
                client,
                f"{base}/{peer}",
                timeout_ms,
                tag=f"{tag}#{seq}",
                peer=peer,
            )
        )
    # cleanup: after everyone has read, each rank deletes its own key so
    # a long-running process doesn't grow the coordination store without
    # bound.  Barrier first — deleting before a slow peer's read would
    # turn its read into a spurious timeout.  Both steps are
    # best-effort: older clients lack the APIs, and leaked keys are
    # harmless (seq numbers never reuse a name).  Skipped entirely under
    # an active recovery plan: the coordination service still counts the
    # dead ranks as barrier participants, so every barrier would stall
    # to its full timeout.
    if _pod.active_recovery_plan() is None:
        try:
            barrier = getattr(client, "wait_at_barrier", None)
            if barrier is not None:
                barrier(f"{_gen_prefix()}/agb/{tag}/{seq}", timeout_ms)
                delete = getattr(client, "key_value_delete", None)
                if delete is not None:
                    delete(f"{base}/{rank}")
        except Exception:  # pragma: no cover - version/timing dependent
            pass
    return out


def broadcast_bytes(
    tag: str,
    payload: Optional[bytes] = None,
    root: int = 0,
    timeout_s: Optional[float] = None,
) -> bytes:
    """One-to-all: rank `root` publishes `payload`; every rank returns
    it.  The direct analog of the NCCL-uid broadcast (root creates the
    uid, the barrier allGather hands it to everyone).  Non-root ranks
    may pass payload=None.  Bounded and generation-scoped like
    `allgather_bytes`."""
    nranks, rank = process_topology()
    if nranks == 1:
        return bytes(payload or b"")
    client = _coordination_client()
    if client is None:
        raise RuntimeError(
            "broadcast_bytes: jax.distributed is not initialized (no "
            "coordination client); call init_distributed() first"
        )
    from ..resilience.pod import maybe_start_heartbeat

    maybe_start_heartbeat()
    with _kv_lock:
        seq = _kv_seq.get(f"bc/{tag}", 0)
        _kv_seq[f"bc/{tag}"] = seq + 1
    key = f"{_gen_prefix()}/bc/{tag}/{seq}"
    timeout_ms = (
        int(timeout_s * 1000) if timeout_s is not None else _reduce_timeout_ms()
    )
    if rank == root:
        if payload is None:
            raise ValueError("broadcast_bytes: root rank needs a payload")
        _kv_put(client, key, payload)
        return bytes(payload)
    return _kv_take(client, key, timeout_ms, tag=f"bc/{tag}#{seq}", peer=root)


def _observe_reduce(phase: str, seconds: float) -> None:
    from ..telemetry.registry import histogram

    histogram(
        "multiproc_reduce_seconds",
        "Cross-process reduction wall time by phase",
    ).observe(seconds, phase=phase)


def content_fingerprint(tag: str, arrays: Dict[str, np.ndarray]) -> str:
    """Structural fingerprint of a reduction payload: the tag plus every
    accumulator's (name, shape, dtype) in sorted order.  Content VALUES
    are deliberately excluded — ranks legitimately hold different
    partial sums; what must agree is the LAYOUT they claim to be
    reducing."""
    h = hashlib.blake2b(digest_size=16)
    h.update(tag.encode())
    for name in sorted(arrays):
        a = np.asarray(arrays[name])
        h.update(
            f"|{name}:{a.dtype.str}:{tuple(a.shape)}".encode()
        )
    return h.hexdigest()


def check_rank_agreement(tag: str, fingerprint: str) -> None:
    """Allgather a small fingerprint and require every rank to present
    the same one; divergence raises `RankDivergenceError` BEFORE any
    merge.  No-op single-process or when `multiproc_agreement_check` is
    off."""
    if process_topology()[0] == 1 or not get_config("multiproc_agreement_check"):
        return
    t0 = time.perf_counter()
    fps = [
        b.decode("ascii", "replace")
        for b in allgather_bytes(f"agree/{tag}", fingerprint.encode("ascii"))
    ]
    _observe_reduce("agreement", time.perf_counter() - t0)
    if any(fp != fps[0] for fp in fps):
        raise RankDivergenceError(tag, fps)


def psum_capable() -> bool:
    """Whether this build's XLA backend can run cross-process
    collectives (TPU/GPU yes; the CPU backend rejects them).  Probed
    once per process with a tiny allgather; the probe is itself a
    collective, so every rank must reach it (they do — it only runs
    from reduction sites, which are SPMD).  Single-process: trivially
    True."""
    if jax.process_count() == 1:
        return True
    global _psum_probe_result
    try:
        return _psum_probe_result  # type: ignore[name-defined]
    except NameError:
        pass
    try:
        from jax.experimental import multihost_utils

        multihost_utils.process_allgather(np.zeros((1,), np.float32))
        result = True
    except Exception as e:
        get_logger("spark_rapids_ml_tpu.multiproc").info(
            "cross-process XLA collectives unavailable on this backend "
            f"({type(e).__name__}); host-fold reductions go over the "
            "coordination-service wire"
        )
        result = False
    _psum_probe_result = result
    return result


def resolve_reduce_backend() -> str:
    """'psum' or 'wire', honoring the `multiproc_reduce` conf ('auto'
    probes the backend once).  Cached; `reinit_distributed` clears the
    cache because a new runtime may have different capabilities."""
    global _reduce_backend_resolved
    if _reduce_backend_resolved is not None:
        return _reduce_backend_resolved
    conf = str(get_config("multiproc_reduce")).lower()
    if conf not in ("auto", "psum", "wire"):
        raise ValueError(
            f"multiproc_reduce must be auto|psum|wire, got {conf!r}"
        )
    if conf == "auto":
        backend = "psum" if psum_capable() else "wire"
    else:
        backend = conf
    _reduce_backend_resolved = backend
    return backend


def cross_process_reduce_ready() -> bool:
    """Whether cross-process reductions can run at all right now: true
    single-process, and in distributed mode whenever the coordination
    client is live (the wire path needs nothing else; psum capability
    only picks WHICH backend)."""
    if process_topology()[0] == 1:
        return True
    return _coordination_client() is not None


def _lead_device_mesh():
    """1-D mesh with one device per process (each process's
    lowest-indexed device) — the reduction axis for the jitted psum."""
    from jax.sharding import Mesh

    leads = {}
    for d in jax.devices():
        if d.process_index not in leads:
            leads[d.process_index] = d
    devs = np.array([leads[p] for p in sorted(leads)])
    return Mesh(devs, ("proc",))


def _psum_reduce_stacked(vec: np.ndarray) -> np.ndarray:
    """Sum this process's flat f64 partial with its peers' via ONE jitted
    cross-process reduction: each rank contributes row `rank` of a
    global (nranks, n) array sharded over the lead-device mesh; a jitted
    sum over the process axis lets GSPMD emit the all-reduce, and the
    replicated output is read back on every host."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _lead_device_mesh()
    nranks = jax.process_count()
    lead = mesh.devices.flat[jax.process_index()]
    local = jax.device_put(vec[None, :], lead)
    garr = jax.make_array_from_single_device_arrays(
        (nranks, vec.shape[0]),
        NamedSharding(mesh, P("proc", None)),
        [local],
    )
    key = (
        tuple(int(d.id) for d in mesh.devices.flat),
        vec.shape[0],
        str(vec.dtype),
    )
    with _kv_lock:
        fn = _psum_fns.get(key)
        if fn is None:
            fn = jax.jit(
                lambda x: x.sum(axis=0),
                out_shardings=NamedSharding(mesh, P()),
            )
            _psum_fns[key] = fn
    return np.asarray(jax.device_get(fn(garr)))


def reduce_host_arrays(
    arrays: Dict[str, np.ndarray], tag: str
) -> Dict[str, np.ndarray]:
    """Sum a dict of per-process partial accumulators across every rank;
    returns the global sums (same keys/shapes/dtypes) on every rank.
    Single-process: the input, unchanged — so call sites need no gate.

    This is the `pass_complete` reduction of the multi-host data path:
    each process folds only its own ingest share locally, then ONE
    reduction here replaces the replicated host folds.  Backend per
    `multiproc_reduce`: 'psum' concatenates the accumulators into one
    flat f64 vector and folds it with a single jitted collective;
    'wire' allgathers the npz-serialized payloads over the coordination
    service and folds on host in ascending rank order — deterministic,
    so exactly-representable partials (integer-valued test data) reduce
    byte-identically to the single-process fold.  The agreement check
    (conf `multiproc_agreement_check`) runs first either way."""
    if process_topology()[0] == 1:
        return arrays
    from ..telemetry.registry import counter

    arrays = {k: np.asarray(v) for k, v in arrays.items()}
    check_rank_agreement(tag, content_fingerprint(tag, arrays))
    backend = resolve_reduce_backend()
    if topology_overridden():
        # post-shrink (or simulated) quorums must not touch the psum
        # path: the jitted collective spans the BOOT lead-device mesh,
        # which still contains the dead rank's devices — the wire fold
        # over the surviving quorum is the only sound backend
        backend = "wire"
    t0 = time.perf_counter()
    if backend == "psum":
        names = sorted(arrays)
        flat = np.concatenate(
            [np.asarray(arrays[n], np.float64).ravel() for n in names]
        )
        # the psum dispatch is a cross-process wait like any other: a
        # dead peer would park the jitted collective forever, so it runs
        # under the same bounded deadline and surfaces typed
        from ..resilience.guard import DispatchTimeout, guarded
        from ..resilience.pod import ReduceTimeout

        try:
            total = guarded(
                lambda: _psum_reduce_stacked(flat),
                deadline=float(get_config("multiproc_reduce_timeout_s")),
                label=f"psum[{tag}]",
            )
        except DispatchTimeout as e:
            raise ReduceTimeout(
                tag, key=f"psum/{tag}", waited_s=e.deadline
            ) from e
        out: Dict[str, np.ndarray] = {}
        off = 0
        for n in names:
            a = arrays[n]
            out[n] = (
                total[off : off + a.size].reshape(a.shape).astype(a.dtype)
                if a.dtype != np.float64
                else total[off : off + a.size].reshape(a.shape)
            )
            off += a.size
    else:
        buf = io.BytesIO()
        np.savez(buf, **{k: np.asarray(v) for k, v in arrays.items()})
        blobs = allgather_bytes(f"reduce/{tag}", buf.getvalue())
        out = {
            k: np.zeros_like(np.asarray(v, np.float64))
            for k, v in arrays.items()
        }
        for blob in blobs:  # ascending rank order — deterministic
            with np.load(io.BytesIO(blob)) as z:
                for k in out:
                    out[k] = out[k] + np.asarray(z[k], np.float64)
        out = {
            k: v.astype(arrays[k].dtype) if arrays[k].dtype != v.dtype else v
            for k, v in out.items()
        }
    _observe_reduce(backend, time.perf_counter() - t0)
    counter(
        "multiproc_reductions_total",
        "Cross-process reductions completed, by backend",
    ).inc(backend=backend)
    return out


def reduce_blob_list(tag: str, payload: bytes) -> List[bytes]:
    """Allgather one versioned wire blob per rank (sketch states via
    `sketch_to_bytes`, fingerprint-builder states) in rank order, timed
    under the `sketch` phase.  The caller merges with the format's own
    associative merge — the wire format IS the cross-process contract,
    exactly as the reference ships sketch bytes through NCCL."""
    if process_topology()[0] == 1:
        return [bytes(payload)]
    t0 = time.perf_counter()
    blobs = allgather_bytes(f"blob/{tag}", payload)
    _observe_reduce("sketch", time.perf_counter() - t0)
    return blobs


class TpuContext:
    """Context manager wrapping one distributed fit.

    Single-host (the common case in tests and on one v5e board): a no-op
    wrapper that exposes rank/nranks and the mesh.  Multi-host: initializes
    `jax.distributed` from config (coordinator_address / process_id /
    num_processes) the first time, mirroring CumlContext's lazy NCCL init on
    __enter__ (reference cuml_context.py:121-161).
    """

    def __init__(
        self,
        num_workers: Optional[int] = None,
        enable_collectives: bool = True,
        require_p2p: bool = False,
    ) -> None:
        self._num_workers = num_workers
        self._enable_collectives = enable_collectives
        self._require_p2p = require_p2p  # exact-kNN/DBSCAN analog of require_ucx
        self._logger = get_logger(type(self))
        self.mesh = None

    @property
    def rank(self) -> int:
        return process_topology()[1]

    @property
    def nranks(self) -> int:
        return process_topology()[0]

    def __enter__(self) -> "TpuContext":
        if get_config("coordinator_address") and not _distributed_initialized:
            # Lazy multi-host bootstrap from config — the analog of
            # CumlContext's lazy NCCL init on __enter__
            # (reference cuml_context.py:121-161).  Processes that used JAX
            # before this point should call `init_distributed()` early
            # instead.
            if init_distributed():
                self._logger.info(
                    f"jax.distributed initialized: process "
                    f"{jax.process_index()}/{jax.process_count()}"
                )
        self.mesh = get_mesh(self._num_workers)
        return self

    def __exit__(self, exc_type, exc_val, exc_tb) -> None:
        # The reference destroys/aborts the NCCL comm per fit
        # (cuml_context.py:163-180).  JAX's runtime persists across fits by
        # design (compilations are cached); nothing to tear down per-fit.
        self.mesh = None
