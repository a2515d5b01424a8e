#
# resilience/ — the unified failure-handling layer every fit/transform
# path routes through.  The reference stack survives executor loss because
# Spark re-schedules barrier tasks (reference core.py:742-1013); this
# single-controller JAX runtime has no scheduler above it, so the same
# guarantees live here, in four pieces:
#
#   guard.py       guarded(fn, deadline, label): blocking device work under
#                  a watchdog thread — a hang raises a typed
#                  DispatchTimeout instead of blocking the controller
#                  forever.
#   retry.py       RetryPolicy: declarative max-attempts / exponential
#                  backoff + jitter / error classifier.  One classifier
#                  set subsumes the hand-rolled special cases: OOM ->
#                  shrink batch (site-provided hook), transient
#                  RPC/DEADLINE -> backoff + retry, preemption -> re-init
#                  jax.distributed then resume.
#   faults.py      deterministic fault injection at named dispatch sites,
#                  so every recovery path is exercisable on CPU in CI.
#   checkpoint.py  the estimator-wide checkpoint contract (content-tag
#                  naming, atomic tmp + os.replace, rank-0 writer) lifted
#                  out of streaming.py and shared by every iterative
#                  solver loop.
#   elastic.py     elastic mesh recovery: a classified DEVICE LOSS
#                  shrinks the mesh to the survivors
#                  (parallel/mesh.py exclusions), invalidates resident
#                  cache entries for re-staging, and lets checkpointed
#                  solvers resume at iteration k on the smaller mesh —
#                  instead of the blind full retry.
#   pod.py         the same contract at POD scale: bounded, typed
#                  cross-process waits (`kv_wait`), per-rank liveness
#                  heartbeats, and a RANK LOSS recovery that shrinks the
#                  quorum to the survivors under a bumped reduction
#                  generation and reassigns the dead rank's row-group
#                  shares (fused.py consumes the RecoveryPlan).
#
# The layer imports neither jax nor numpy at module scope: arming faults
# or reading a policy must not pay the multi-second jax import.
#
from .checkpoint import (  # noqa: F401
    checkpoint_file_for,
    clear_checkpoint,
    load_checkpoint,
    resolve_checkpoint_dir,
    save_checkpoint,
    sweep_orphaned_tmps,
)
from .elastic import (  # noqa: F401
    RECOVERY_METRICS,
    probe_lost_devices,
    recover_from_device_loss,
    reset_elastic,
    simulate_device_loss,
)
from .faults import SimulatedPreemption, fault_inject, maybe_inject  # noqa: F401
from .guard import DispatchTimeout, guarded  # noqa: F401
from .pod import (  # noqa: F401
    POD_METRICS,
    RankLost,
    ReduceTimeout,
    recover_from_rank_loss,
    reset_pod,
    simulate_rank_loss,
)
from .retry import (  # noqa: F401
    RetryPolicy,
    classify_error,
    is_device_loss,
    is_oom,
    is_preemption,
    is_rank_loss,
    is_transient,
    retry_call,
)

__all__ = [
    "DispatchTimeout",
    "POD_METRICS",
    "RECOVERY_METRICS",
    "RankLost",
    "ReduceTimeout",
    "RetryPolicy",
    "SimulatedPreemption",
    "checkpoint_file_for",
    "classify_error",
    "clear_checkpoint",
    "fault_inject",
    "guarded",
    "is_device_loss",
    "is_oom",
    "is_preemption",
    "is_rank_loss",
    "is_transient",
    "load_checkpoint",
    "maybe_inject",
    "probe_lost_devices",
    "recover_from_device_loss",
    "recover_from_rank_loss",
    "reset_elastic",
    "reset_pod",
    "resolve_checkpoint_dir",
    "retry_call",
    "save_checkpoint",
    "simulate_device_loss",
    "simulate_rank_loss",
    "sweep_orphaned_tmps",
]
