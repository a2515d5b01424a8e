#
# Retry policies — declarative recovery for dispatch failures.  One
# classifier set replaces the scattered hand-rolled handlers (the inline
# `_is_oom` special case in core.py, the per-site halving loops): every
# failure maps to an ACTION, and the action — not the call site — decides
# the recovery:
#
#   oom         drop the poisoned buffers (site hook: shrink the batch /
#               gc the staged arrays) and re-dispatch
#   transient   RPC/DEADLINE errors: exponential backoff + jitter,
#               then re-dispatch
#   preemption  a TPU worker went away: re-init `jax.distributed`
#               (parallel/context.py `reinit_distributed`) and resume —
#               iterative solvers pick their checkpoint back up
#               (resilience/checkpoint.py)
#   device_loss one or more DEVICES vanished but the process lives: the
#               elastic recovery layer (resilience/elastic.py) shrinks
#               the mesh to the survivors, the caller re-stages, and
#               checkpointed solvers resume at iteration k on the
#               smaller mesh (falls back to the preemption repair when
#               elastic is off / too few survivors)
#   rank_loss   a peer PROCESS died mid-reduction (typed RankLost /
#               ReduceTimeout from the pod layer's bounded waits): with
#               `pod_elastic` on, resilience/pod.py shrinks the quorum
#               to the survivors under a bumped generation and the pass
#               restarts on the reassigned share layout; with it off the
#               typed error is FATAL — bounded timeout, then propagate
#   fatal       everything else propagates unchanged on the FIRST raise
#
from __future__ import annotations

import gc
import random
import time
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

from ..config import get_config
from ..telemetry.registry import counter as _counter
from ..utils import get_logger

logger = get_logger("spark_rapids_ml_tpu.resilience")

# one counter family for every policy-driven recovery, labeled by the
# dispatch site and the classified action — the queryable form of the
# `retry[<label>]` trace events (core.py's inline transform retry loop
# bumps the same family so the two paths never diverge in the metrics)
RETRIES = _counter(
    "retries_total", "Policy-driven dispatch retries by site and action"
)


def is_oom(e: BaseException) -> bool:
    """XLA device-memory exhaustion (moved from core.py `_is_oom`)."""
    s = str(e)
    return (
        "RESOURCE_EXHAUSTED" in s
        or "Out of memory" in s
        or "out of memory" in s
    )


def is_preemption(e: BaseException) -> bool:
    """A TPU worker/coordinator went away mid-fit (maintenance event,
    spot reclaim): the runtime must re-bootstrap before any retry.

    Beyond the obvious 'preempted' strings, the coordination service
    surfaces worker death as status-code / transport errors that never
    say "preempted": `DATA_LOSS` (a restarted worker lost its state),
    heartbeat timeouts ('... heartbeat timed out' / 'Heartbeat request
    failed'), and the coordination channel's socket closing under it.
    Each of those is pinned by a test (tests/test_resilience.py).  Plain
    user RuntimeErrors that merely mention sockets stay in the
    `transient` family, and everything unmatched stays fatal."""
    from .faults import SimulatedPreemption

    if isinstance(e, SimulatedPreemption):
        return True
    s = str(e)
    low = s.lower()
    return (
        "preempted" in s
        or "PREEMPTED" in s
        or "DATA_LOSS" in s
        or "coordinator disconnected" in s
        or "worker has been restarted" in s
        or ("heartbeat" in low and ("timed out" in low or "failed" in low))
        or ("coordination" in low and "socket closed" in low)
    )


def is_device_loss(e: BaseException) -> bool:
    """One or more DEVICES vanished mid-execution (spot reclaim of a
    worker's chips, an ICI/PCIe failure) — distinct from a whole-worker
    preemption because the surviving devices can keep working: the
    elastic recovery layer (resilience/elastic.py) shrinks the mesh and
    resumes instead of blind-retrying.  Matches the typed
    `parallel.context.DeviceLoss` (duck-typed on `lost_devices`, so this
    module never imports jax) and runtime errors that name a DEVICE as
    lost or invalid ('INTERNAL: failed to execute XLA Runtime
    executable: device N has been lost', 'device is in an invalid
    state').  Deliberately NOT a match on 'failed to execute' alone:
    that wrapper also carries deterministic internal failures (a custom
    call rejecting, a lowering bug), which must stay fatal on the first
    raise rather than burn retry rounds re-bootstrapping a healthy
    runtime.  The misclassification that remains possible (a transient
    error naming a 'lost device') is recoverable: the health probe finds
    every device answering and the recovery falls back."""
    if getattr(e, "lost_devices", None) is not None:
        return True
    low = str(e).lower()
    return "device" in low and (
        "lost" in low or "is in an invalid state" in low
    )


def is_transient(e: BaseException) -> bool:
    """Retryable without state repair: RPC deadline and availability
    errors, including the guard's typed DispatchTimeout."""
    from .guard import DispatchTimeout

    if isinstance(e, DispatchTimeout):
        return True
    s = str(e)
    return (
        "DEADLINE_EXCEEDED" in s
        or "UNAVAILABLE" in s
        or "Socket closed" in s
        or "RPC failed" in s
        or "Connection reset" in s
    )


def is_rank_loss(e: BaseException) -> bool:
    """A typed pod-layer failure: a peer PROCESS declared dead
    (`RankLost`) or a bounded cross-process wait that expired
    (`ReduceTimeout`).  Both come from resilience/pod.py's `kv_wait`
    seam — string matching is unnecessary, the types are ours."""
    from .pod import RankLost, ReduceTimeout

    return isinstance(e, (RankLost, ReduceTimeout))


def classify_error(e: BaseException) -> str:
    """Map an exception to its recovery action: 'rank_loss' |
    'device_loss' | 'preemption' | 'oom' | 'transient' | 'fatal'.
    Rank loss classifies FIRST — the exceptions are typed, and their
    messages deliberately carry DEADLINE/lost markers that the string
    classifiers below would mis-route.  With `pod_elastic` off the same
    typed errors are FATAL: the bounded timeout already did its job
    (never hang), and there is no recovery to drive."""
    if is_rank_loss(e):
        from .pod import pod_elastic_enabled

        return "rank_loss" if pod_elastic_enabled() else "fatal"
    if is_device_loss(e):
        return "device_loss"
    if is_preemption(e):
        return "preemption"
    if is_oom(e):
        return "oom"
    if is_transient(e):
        return "transient"
    return "fatal"


def _default_oom_hook() -> None:
    # free the failed dispatch's temporaries before re-dispatching; the
    # caller's staged inputs (deliberately still referenced) survive
    gc.collect()


def _default_device_loss_hook() -> None:
    # the elastic state machine (resilience/elastic.py): shrink the mesh
    # to the survivors when allowed, else fall back to the preemption
    # repair — either way the retry loop re-dispatches afterwards.
    # Callers whose inputs must move to the degraded mesh (core.py
    # _run_fit_kernel) pass their own hook that ALSO re-stages.
    from .elastic import recover_from_device_loss

    recover_from_device_loss(logger)


def _default_rank_loss_hook(exc: Optional[BaseException] = None) -> None:
    # the pod recovery state machine (resilience/pod.py): shrink the
    # quorum to the survivors under a bumped generation when a dead rank
    # is identifiable, else fall back to the preemption repair (a
    # straggler timeout or a dead coordinator — only a full re-bootstrap
    # can help).  Either way the retry loop re-dispatches afterwards and
    # the pass restarts with fresh accumulators.
    from .pod import recover_from_rank_loss

    if not recover_from_rank_loss(exc, log=logger):
        _default_preemption_hook()


def _default_preemption_hook() -> None:
    # best-effort: on a single-controller process whose XLA backend is
    # already live, re-bootstrapping jax.distributed may itself fail (the
    # runtime only accepts distributed init before backend init on some
    # versions).  The retry must then still run — a failed repair must
    # surface the ORIGINAL preemption on the next attempt, not a
    # confusing bootstrap error from inside the hook.
    from ..parallel.context import reinit_distributed

    try:
        reinit_distributed()
    except Exception as e:
        logger.warning(
            f"jax.distributed re-init after preemption failed ({e}); "
            "retrying on the existing runtime"
        )


@dataclass
class RetryPolicy:
    """Declarative retry: total attempts, exponential backoff + jitter,
    and the retryable-action set.  `classify` maps an exception to an
    action name; actions outside `retryable` (and 'fatal') propagate."""

    max_attempts: int = 3
    backoff_s: float = 0.5
    backoff_mult: float = 2.0
    jitter: float = 0.25
    classify: Callable[[BaseException], str] = classify_error
    retryable: Tuple[str, ...] = (
        "oom", "transient", "preemption", "device_loss", "rank_loss",
    )
    # OOM gets a TIGHTER budget than max_attempts: one gc'd re-dispatch
    # recovers fragmentation/injected faults, but a dataset that genuinely
    # exceeds HBM fails every attempt after minutes of device work each —
    # the caller's fallback (e.g. _stage_or_stream's streamed-statistics
    # path) must engage after a single repair attempt, not attempt N
    oom_attempts: int = 1

    @classmethod
    def from_config(cls) -> "RetryPolicy":
        return cls(
            max_attempts=int(get_config("retry_max_attempts")),
            backoff_s=float(get_config("retry_backoff_s")),
            backoff_mult=float(get_config("retry_backoff_mult")),
            jitter=float(get_config("retry_jitter")),
        )

    def backoff(self, attempt: int) -> float:
        """Delay before retry number `attempt` (1-based)."""
        delay = self.backoff_s * self.backoff_mult ** (attempt - 1)
        return delay * (1.0 + random.uniform(0.0, self.jitter))


def retry_call(
    fn: Callable[[], Any],
    label: str = "dispatch",
    policy: Optional[RetryPolicy] = None,
    log: Optional[object] = None,
    on_oom: Optional[Callable[[], None]] = None,
    on_preemption: Optional[Callable[[], None]] = None,
    on_device_loss: Optional[Callable[[], None]] = None,
    on_rank_loss: Optional[Callable[[], None]] = None,
) -> Any:
    """Run `fn` under `policy` (default: `RetryPolicy.from_config()`).

    Each recovery is surfaced as a `retry[label]` trace event.  `on_oom` /
    `on_preemption` / `on_device_loss` / `on_rank_loss` override the
    default repair hooks (gc-collect / `reinit_distributed` / the elastic
    mesh recovery / the pod quorum shrink — resilience/elastic.py and
    resilience/pod.py).  Callers whose recovery mutates loop state the
    policy cannot see (the transform chunk loop in core.py: chunk halving,
    resume-row tracking across a pipelined pending dispatch) apply the
    SAME policy — `RetryPolicy.from_config()`, `classify`, `backoff`, and
    `_default_preemption_hook` — inline instead of through this wrapper,
    so classification and attempt semantics never diverge.
    """
    if policy is None:
        policy = RetryPolicy.from_config()
    lg = log or logger
    attempt = 1
    oom_count = 0
    while True:
        action = None
        err_desc = ""
        rank_loss_exc = None
        try:
            return fn()
        except Exception as e:
            action = policy.classify(e)
            if (
                action == "fatal"
                or action not in policy.retryable
                or attempt >= policy.max_attempts
                or (action == "oom" and oom_count >= policy.oom_attempts)
            ):
                if action != "fatal" and action in policy.retryable:
                    # a RECOVERABLE failure class exhausted its attempt
                    # budget — the fit is about to die with its evidence:
                    # dump the flight-recorder black box before the raise
                    # (fatal errors propagate on the FIRST raise and are
                    # the caller's bug to read from the traceback)
                    from ..telemetry.flight_recorder import note_failure

                    note_failure(
                        "retry_exhausted",
                        detail=(
                            f"label={label} action={action} "
                            f"attempt={attempt} "
                            f"error={type(e).__name__}: {e}"
                        ),
                        log=lg,
                    )
                raise
            err_desc = f"{type(e).__name__}: {e}"
            if action == "rank_loss":
                # the recovery hook needs the typed exception (it names
                # the dead ranks); safe to carry outside the except
                # block — pod errors are host-side, their tracebacks pin
                # no device buffers
                rank_loss_exc = e
        # the retry runs OUTSIDE the except block: while handling, the
        # interpreter's exception state pins the failed dispatch's frames
        # via the traceback, whose locals reference the device buffers we
        # are trying to free (the poisoned-buffer lesson recorded at
        # core.py _stage_or_stream) — leaving the block pops
        # the exception and releases them before the repair hook runs
        from ..tracing import event

        RETRIES.inc(label=label, action=action)
        event(
            f"retry[{label}]",
            detail=f"attempt={attempt} action={action}",
            log=lg,
        )
        lg.warning(
            f"Dispatch '{label}' failed ({err_desc}); recovery={action}, "
            f"attempt {attempt + 1}/{policy.max_attempts}"
        )
        if action == "oom":
            oom_count += 1
            (on_oom or _default_oom_hook)()
        elif action == "preemption":
            (on_preemption or _default_preemption_hook)()
        elif action == "device_loss":
            (on_device_loss or _default_device_loss_hook)()
        elif action == "rank_loss":
            if on_rank_loss is not None:
                on_rank_loss()
            else:
                _default_rank_loss_hook(rank_loss_exc)
        else:  # transient
            time.sleep(policy.backoff(attempt))
        attempt += 1
