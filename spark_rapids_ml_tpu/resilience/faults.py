#
# Deterministic fault injection — the test harness for every recovery
# path.  Real OOM / transfer-timeout / TPU-preemption faults only occur on
# hardware under load; CI runs on a CPU mesh, so recovery code would
# otherwise ship unexercised (the reference has the same gap: its barrier
# re-schedule path is only exercised by live executor loss).  Dispatch
# sites call `maybe_inject("<site>")`; tests (or the `fault_inject_spec`
# conf for whole-process runs) arm a site with a fault kind and exact
# occurrence counts, so each injected failure is reproducible down to the
# iteration it fires on.
#
# The instrumented sites are registered in `KNOWN_SITES` below (the
# canonical list docs/resilience.md mirrors and the graft-lint
# fault-site rule enforces).  One contract worth repeating here:
# `fused_accumulate` (the fused stage-and-solve chunk loop, fused.py)
# fires per accumulated chunk; accumulators are RE-CREATABLE state, so
# the recovery contract is restart-the-pass, never resume — tests
# assert a retried pass cannot double-count chunks.
#
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, List

from ..config import get_config
from ..telemetry.locks import named_lock
from ..utils import get_logger

logger = get_logger("spark_rapids_ml_tpu.resilience")

_lock = named_lock("faults")

# The canonical fault-site registry.  Every `maybe_inject("<site>")`
# literal in the package must be registered here, every registered site
# must be instrumented by at least one dispatch-site call, and
# docs/resilience.md must list each one — all three cross-checked by the
# graft-lint `fault-site` rule (spark_rapids_ml_tpu/analysis/), so the
# site list can no longer silently diverge between code and docs.
# Tests arm ad-hoc sites freely as long as the same file instruments
# them with its own `maybe_inject` call.
KNOWN_SITES = frozenset({
    "fit_kernel",
    "transform_dispatch",
    "stage_parquet",
    "kmeans_lloyd",
    "lbfgs_iteration",
    "linreg_fista",
    "fused_accumulate",
    # the serving dispatcher's coalesced micro-batch dispatch
    # (serving/server.py): an injected OOM shrinks the coalescing batch
    # cap, a device_lost routes through elastic recovery and re-pins
    # every resident model on the shrunken mesh — no queued request is
    # lost either way
    "serving_dispatch",
    # the serving admission gate (serving/server.py submit): fires
    # BEFORE the request touches a queue, so injection drills can drive
    # the admission/shed/brownout paths deterministically — the fault
    # propagates to the submitting caller, never into the dispatcher,
    # and no half-admitted request leaks into the class deques
    "serving_admission",
    # the staged pipeline's collect/scatter phase (serving/server.py
    # collect worker): fires AFTER the batch dispatched, while earlier
    # batches may still be in flight behind it — the drill for
    # mid-pipeline failure.  Recovery requeues every in-flight batch's
    # requests in dispatch order (per-model, per-class FIFO preserved)
    # and the dispatcher re-coalesces; no request is lost or reordered
    "serving_collect",
    # the chunk cache's spill-to-host compression step
    # (parallel/device_cache.py ChunkCache._spill_chunk_locked): fires
    # while an epoch iteration is inserting/evicting chunks mid-stream.
    # The cache drops its half-recorded stream and the error propagates
    # into the consuming fit, whose retry restarts the pass with FRESH
    # accumulators — cached chunks are re-creatable state, so a retried
    # epoch can never double-count (asserted by tests/test_chunk_cache.py)
    "chunk_cache_spill",
    # the statistic-program engine's per-chunk fold (stats/engine.py):
    # same contract as `fused_accumulate` — accumulators are
    # re-creatable state, a mid-pass fault fails the WHOLE pass and the
    # retry restarts it with fresh accumulators, so a retried chunk can
    # never double-count (asserted by tests/test_stat_programs.py)
    "stat_program_step",
    # the pod layer's bounded cross-process wait (resilience/pod.py
    # `kv_wait`): every KV get/allgather/broadcast in
    # parallel/context.py enters here, so arming it drives the
    # rank-loss / reduce-timeout recovery paths at the exact wait a
    # dead peer would have wedged
    "kv_wait",
})

# Injectable fault kinds (`_Fault` validates against this; the docs and
# the `fault_inject_spec` conf comment enumerate the same set)
FAULT_KINDS = (
    "oom",
    "timeout",
    "preemption",
    "hang",
    "device_lost",
    "rank_lost",
    "kv_timeout",
)


class SimulatedPreemption(RuntimeError):
    """An injected TPU-worker preemption (the str carries 'preempted' so
    the retry classifier routes it like the real coordinator error)."""

    def __init__(self, site: str) -> None:
        super().__init__(
            f"injected fault: TPU worker preempted at dispatch site '{site}'"
        )
        self.site = site


class _Fault:
    __slots__ = ("kind", "times", "skip", "seconds")

    def __init__(self, kind: str, times: int, skip: int, seconds: float) -> None:
        if kind not in FAULT_KINDS:
            raise ValueError(f"unknown fault kind: {kind!r}")
        self.kind = kind
        self.times = int(times)
        self.skip = int(skip)
        self.seconds = float(seconds)


# context-manager-armed faults (tests) and conf-armed faults
# (`fault_inject_spec`, whole-process runs) are tracked separately so a
# config re-parse never clobbers an active `fault_inject` block
_armed: Dict[str, List[_Fault]] = {}
_armed_conf: Dict[str, List[_Fault]] = {}
_conf_spec_seen: str = ""


@contextlib.contextmanager
def fault_inject(
    site: str,
    kind: str,
    times: int = 1,
    skip: int = 0,
    seconds: float = 5.0,
) -> Iterator[None]:
    """Arm `site` to fail deterministically while the block runs.

    `skip` occurrences pass through first (inject mid-fit, e.g. after
    three Lloyd iterations), then the next `times` occurrences fire.
    Kinds: `oom` (a RESOURCE_EXHAUSTED RuntimeError), `timeout` (a typed
    DispatchTimeout), `preemption` (SimulatedPreemption), `hang` (sleeps
    `seconds` so the `guarded` watchdog fires — the only kind that needs
    a positive `dispatch_deadline_s` to become an error), `device_lost`
    (a jaxlib-shaped 'failed to execute ... device' RuntimeError that
    ALSO registers a simulated loss with resilience/elastic.py, so the
    health probe reports the device gone and the whole elastic-recovery
    state machine runs on the CPU test mesh), `rank_lost` (a typed
    `pod.RankLost` that ALSO registers a simulated dead peer with
    resilience/pod.py — single-process it installs an implicit 2-rank
    simulated topology first, so the pod detect/shrink/resume machine
    runs on one box), `kv_timeout` (a typed `pod.ReduceTimeout`, the
    bounded-wait expiry with no identifiable corpse — the straggler
    shape).
    """
    f = _Fault(kind, times, skip, seconds)
    with _lock:
        _armed.setdefault(site, []).append(f)
    try:
        yield
    finally:
        with _lock:
            faults = _armed.get(site, [])
            if f in faults:
                faults.remove(f)
            if not faults:
                _armed.pop(site, None)


def _parse_spec(spec: str) -> Dict[str, List[_Fault]]:
    """`"site:kind[:times[:skip]]"` comma list -> armed-fault table."""
    out: Dict[str, List[_Fault]] = {}
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        parts = entry.split(":")
        if len(parts) < 2:
            raise ValueError(
                f"fault_inject_spec entry {entry!r} is not "
                "'site:kind[:times[:skip]]'"
            )
        site, kind = parts[0], parts[1]
        times = int(parts[2]) if len(parts) > 2 else 1
        skip = int(parts[3]) if len(parts) > 3 else 0
        out.setdefault(site, []).append(_Fault(kind, times, skip, 5.0))
    return out


def _sync_conf_locked() -> None:
    global _conf_spec_seen, _armed_conf
    spec = str(get_config("fault_inject_spec") or "")
    if spec == _conf_spec_seen:
        return
    _armed_conf = _parse_spec(spec)
    _conf_spec_seen = spec


def maybe_inject(site: str) -> None:
    """Fire the armed fault for `site`, if any.  Called at every named
    dispatch site; unarmed sites cost one dict lookup."""
    with _lock:
        _sync_conf_locked()
        # one occurrence counts ONCE against every armed fault's skip
        # window, and the first fault that is ready (skip drained, times
        # left) fires — a fault still skipping must not suppress another
        # fault armed at the same site
        fault = None
        for table in (_armed, _armed_conf):
            for f in table.get(site, []):
                if f.skip > 0:
                    f.skip -= 1
                elif fault is None and f.times > 0:
                    f.times -= 1
                    fault = f
    if fault is None:
        return
    from ..telemetry.registry import counter
    from ..tracing import event

    counter(
        "faults_injected_total", "Deterministic fault injections by site"
    ).inc(site=site, kind=fault.kind)
    event(f"fault_injected[{site}]", detail=fault.kind, log=logger)
    if fault.kind == "oom":
        raise RuntimeError(
            f"RESOURCE_EXHAUSTED: injected OOM fault at dispatch site "
            f"'{site}'"
        )
    if fault.kind == "timeout":
        from .guard import DispatchTimeout

        raise DispatchTimeout(site, fault.seconds)
    if fault.kind == "preemption":
        raise SimulatedPreemption(site)
    if fault.kind == "device_lost":
        # mark the device gone FIRST (so the recovery probe finds it),
        # then fail the dispatch the way jaxlib does when a chip
        # vanishes mid-execution — the string shape `is_device_loss`
        # (retry.py) classifies
        from .elastic import simulate_device_loss

        dev = simulate_device_loss()
        raise RuntimeError(
            "INTERNAL: failed to execute XLA Runtime executable: device "
            f"{dev} has been lost (injected fault at dispatch site "
            f"'{site}')"
        )
    if fault.kind == "rank_lost":
        # register the simulated dead peer FIRST (so liveness and the
        # recovery probe find it), then raise the typed loss the bounded
        # wait would have raised — the `device_lost` pattern at pod scale
        from .pod import simulate_rank_loss

        raise simulate_rank_loss(site)
    if fault.kind == "kv_timeout":
        from .pod import ReduceTimeout

        raise ReduceTimeout(
            site, key=f"injected/{site}", waited_s=fault.seconds
        )
    # "hang": park inside the dispatch so the guarded watchdog fires; on
    # its own (no deadline armed) this is just a stall, never an error
    time.sleep(fault.seconds)
