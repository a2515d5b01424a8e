#
# Guarded dispatch — a watchdog for blocking device work.  A
# `block_until_ready` or host fetch that never returns blocks the
# controller forever with no exception to recover from.  `guarded` runs
# the blocking call on a worker thread and bounds the wait; past the
# deadline the CALLER gets a typed `DispatchTimeout` (classified transient
# by retry.py, so policy-driven re-dispatch applies) while the abandoned
# worker parks harmlessly until the runtime call returns or the process
# exits.
#
from __future__ import annotations

import threading
from typing import Any, Callable, Optional

from ..config import get_config
from ..telemetry.registry import counter as _counter
from ..utils import get_logger

logger = get_logger("spark_rapids_ml_tpu.resilience")

TIMEOUTS = _counter(
    "dispatch_timeouts_total",
    "Watchdog deadline expiries by dispatch label",
)


class DispatchTimeout(RuntimeError):
    """Blocking device work exceeded its watchdog deadline.

    Typed (instead of a bare hang or a stringly RuntimeError) so
    `retry.classify_error` can route it: transient -> backoff + re-dispatch.
    """

    def __init__(self, label: str, deadline: float) -> None:
        super().__init__(
            f"dispatch '{label}' exceeded its {deadline:.1f}s watchdog "
            "deadline (DEADLINE_EXCEEDED); the device program may still be "
            "in flight"
        )
        self.label = label
        self.deadline = deadline


def guarded(
    fn: Callable[[], Any],
    deadline: Optional[float] = None,
    label: str = "dispatch",
    log: Optional[object] = None,
) -> Any:
    """Run `fn` (blocking device work) under a watchdog deadline.

    `deadline=None` reads the `dispatch_deadline_s` conf; `<= 0` disables
    the watchdog entirely — `fn` runs inline on the calling thread with
    zero overhead (the default, and the tier-1 test configuration).

    With a positive deadline the call runs on a daemon worker thread and
    the caller waits at most `deadline` seconds: completion returns the
    value (or re-raises the worker's exception); expiry records a
    `dispatch_timeout[label]` trace event carrying the deadline and raises
    `DispatchTimeout`.  The worker is NOT killed — Python cannot interrupt
    a thread blocked inside the runtime — but the caller regains control,
    which is the property the hang ledger shows we lose today.
    """
    if deadline is None:
        deadline = float(get_config("dispatch_deadline_s") or 0.0)
    if deadline <= 0:
        return fn()

    result: list = []
    failure: list = []
    # the worker adopts the caller's trace context: tracing storage is
    # thread-local, so without this every trace()/event() recorded inside
    # the guarded dispatch (stage timings, resume/fault markers) would be
    # invisible to the caller whenever the watchdog is enabled
    from ..tracing import adopt_trace_context

    adopt = adopt_trace_context()

    def _worker() -> None:
        adopt()
        try:
            result.append(fn())
        except BaseException as e:  # surfaced on the caller below
            failure.append(e)

    t = threading.Thread(
        target=_worker, name=f"guarded[{label}]", daemon=True
    )
    t.start()
    t.join(deadline)
    if t.is_alive():
        from ..tracing import event

        TIMEOUTS.inc(label=label)
        event(
            f"dispatch_timeout[{label}]",
            detail=f"deadline={deadline:.1f}s",
            log=log or logger,
        )
        # the watchdog firing is exactly the moment evidence is about to
        # be lost (the runtime may never return): leave the black box
        from ..telemetry.flight_recorder import note_failure

        note_failure(
            "dispatch_timeout",
            detail=f"label={label} deadline={deadline:.1f}s",
            log=log or logger,
        )
        raise DispatchTimeout(label, deadline)
    if failure:
        raise failure[0]
    return result[0]
