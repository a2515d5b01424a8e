#
# Tracing / profiling — the analog of the reference's observability tier
# (cuML verbose levels 0-6 routed to executors, reference core.py:413-436;
# per-stage wall-clock logs in ANN, knn.py:1571-1687; benchmark
# `with_benchmark` wrappers).  Two mechanisms:
#
#   - `trace(stage)`: a nestable per-process stage timer recording SPANS —
#     absolute t0/t1 timestamps, the recording thread id, and the active
#     `run_id` (minted per fit/transform by core.py) — so a degraded-mesh
#     CV run can be reconstructed after the fact.  Events are recorded
#     in-process (inspect with `get_trace_events` / `summarize`); at
#     `verbose >= 1` each stage logs its wall-clock on exit.  The
#     telemetry exporters (telemetry/exporters.py) render the recorded
#     spans as Chrome trace-event JSON (one track per thread, instant
#     markers on their own track — loads in Perfetto).
#   - `profile_dir` config: when set, fits run under `jax.profiler.trace`,
#     producing a TensorBoard/XProf trace of the actual device execution —
#     the TPU-native deep-profiling path (there is no cuML logger to
#     forward to; XLA's profiler is strictly more detailed).
#
from __future__ import annotations

import contextlib
import sys
import threading
import time
import uuid
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterator, List, Optional

from .config import get_config
from .utils import get_logger

logger = get_logger("spark_rapids_ml_tpu.tracing")

_tls = threading.local()

# bounded event history per thread: long-lived serving processes transform
# repeatedly and must not grow memory without bound
MAX_EVENTS = 4096


@dataclass
class TraceEvent:
    name: str
    seconds: float
    depth: int
    # instantaneous events (retries, injected faults, dispatch timeouts —
    # resilience/) carry their context here; timed stages leave it empty
    detail: str = ""
    # -- span fields (this PR): correlation + absolute placement ----------
    t0: float = 0.0  # absolute start, epoch seconds (time.time clock)
    t1: float = 0.0  # absolute end; == t0 for instant events
    thread_id: int = 0  # threading.get_ident() of the recording thread
    run_id: str = ""  # the fit/transform run this event belongs to
    kind: str = "span"  # "span" (timed stage) | "instant" (marker)
    # the pod-global pass id active when the event was recorded
    # (telemetry/fleet.py: rank 0 mints it at begin_pass and broadcasts
    # over the KV seam, so the SAME id lands on every rank's spans) —
    # the cross-rank correlation key a merged pod trace is joined on
    pass_id: str = ""
    # a FACT's mapping (`fact()`): what a subsystem did for this run,
    # read by the fit report; None on every other event
    fields: Optional[Dict[str, Any]] = None


# every thread's record list, registered once at creation so the
# telemetry exporters can merge a PROCESS-wide view (the lists themselves
# stay thread-local for lock-free appends; list.append is atomic under
# the GIL).  Worker threads that adopt a caller's buffer share its
# already-registered list — no duplicate registration.  Entries hold a
# WEAK reference to the recording thread and are pruned (lazily, on the
# next registration) once that thread is gone: a thread-per-request
# service must not accumulate dead buffers — and their MAX_EVENTS of
# history — forever.
_buffers_lock = threading.Lock()
_buffers: List[tuple] = []  # (thread_name, weakref-to-thread, records)


def _records() -> List[TraceEvent]:
    rec = getattr(_tls, "records", None)
    if rec is None:
        import weakref

        rec = _tls.records = []
        t = threading.current_thread()
        with _buffers_lock:
            _buffers[:] = [b for b in _buffers if b[1]() is not None]
            _buffers.append((t.name, weakref.ref(t), rec))
    return rec


# process-wide observers of EVERY recorded event, regardless of which
# thread's buffer it lands in — the flight recorder's feed
# (telemetry/flight_recorder.py).  Registration is rare (guarded by
# _buffers_lock); the hot-path iteration reads the list lock-free
# (list object replaced atomically on registration, append-only reads).
_taps: List[Callable[[TraceEvent], None]] = []


def add_trace_tap(fn: Callable[[TraceEvent], None]) -> None:
    """Register `fn` to observe every TraceEvent recorded by any thread
    of this process (spans on exit, instants immediately).  Idempotent.
    A tap must be cheap and never raise — it runs inline on the
    recording thread."""
    global _taps
    with _buffers_lock:
        if fn not in _taps:
            _taps = _taps + [fn]


def remove_trace_tap(fn: Callable[[TraceEvent], None]) -> None:
    global _taps
    with _buffers_lock:
        _taps = [t for t in _taps if t is not fn]


def _append(event: TraceEvent) -> None:
    rec = _records()
    if len(rec) >= MAX_EVENTS:
        # drop the oldest half, less the running run's facts: each was
        # recorded once and the fit report is built from it.  The last of
        # each name stays, so what is kept is bounded by the names.
        half = MAX_EVENTS // 2
        run_id = getattr(_tls, "run_id", "")
        kept = {
            e.name: e
            for e in (rec[:half] if run_id else ())
            if e.fields is not None and e.run_id == run_id
        }
        rec[:half] = list(kept.values())
    rec.append(event)
    for tap in _taps:
        try:
            tap(event)
        except Exception:  # a broken observer must never fail the span
            pass


def get_trace_events() -> List[TraceEvent]:
    """Events recorded on this thread since the last `reset_trace`."""
    return list(_records())


def get_all_trace_events(run_id: Optional[str] = None) -> List[TraceEvent]:
    """Events recorded on EVERY thread of this process, in start order
    (parents sort before their children).  `run_id` filters to one
    fit/transform run.  This is the exporters' view: a guarded dispatch's
    worker thread adopts its caller's buffer, so cross-thread spans of
    one run appear exactly once."""
    with _buffers_lock:
        bufs = [rec for _, _, rec in _buffers]
    seen = set()
    events: List[TraceEvent] = []
    for rec in bufs:
        if id(rec) in seen:  # adopted buffers are shared, not duplicated
            continue
        seen.add(id(rec))
        events.extend(list(rec))
    if run_id is not None:
        events = [e for e in events if e.run_id == run_id]
    # (t0, -t1): a parent starts no later than its children and ends no
    # earlier, so ties break parent-first
    events.sort(key=lambda e: (e.t0, -e.t1))
    return events


# ---------------------------------------------------------------------------
# Run correlation — one id per fit/transform
# ---------------------------------------------------------------------------

# the pod-global pass id (telemetry/fleet.py begin_pod_pass): PROCESS-
# global, not thread-local — the producer/prefetch threads of a fused
# pass must stamp the same id as the consumer that minted it.  A str
# assignment is GIL-atomic, so readers never need the lock.
_current_pass_id = ""


def current_pass_id() -> str:
    """The pod-global pass id active in this process ('' outside any
    pod-correlated pass)."""
    return _current_pass_id


def set_current_pass_id(pass_id: str) -> None:
    """Install (or clear, with '') the process-global pass id every
    subsequently recorded span/instant is stamped with.  Called by
    telemetry/fleet.py at begin/complete of a pod-correlated pass."""
    global _current_pass_id
    _current_pass_id = str(pass_id or "")


def mint_run_id(prefix: str = "run") -> str:
    """A fresh globally-unique run id (`<prefix>-<12 hex>`); core.py
    mints one per fit/transform so retries, device-loss recoveries and
    checkpoint resumes stamp the run they interrupted."""
    return f"{prefix}-{uuid.uuid4().hex[:12]}"


def current_run_id() -> str:
    """The run id active on this thread ('' outside any run)."""
    return getattr(_tls, "run_id", "")


@contextlib.contextmanager
def run_context(
    run_id: Optional[str] = None, prefix: str = "run"
) -> Iterator[str]:
    """Scope a run id onto this thread: every span/event recorded inside
    carries it.  Nests — an inner fit's run restores the outer run on
    exit.  `run_id=None` mints a fresh id."""
    rid = run_id or mint_run_id(prefix)
    prev = getattr(_tls, "run_id", "")
    _tls.run_id = rid
    try:
        yield rid
    finally:
        _tls.run_id = prev


def adopt_trace_context() -> Callable[[], None]:
    """Capture this thread's trace buffer, depth AND run id for adoption
    by a worker thread (resilience/guard.py): the returned thunk, called
    on the worker, makes its trace()/event() calls land in the CALLER's
    record list, at the caller's depth, stamped with the caller's run —
    so a watchdog-guarded dispatch's stage timings and resilience markers
    correlate with the fit that issued it.  Without this the watchdog
    thread's thread-local storage swallows every event recorded inside a
    guarded dispatch.  list.append is atomic under the GIL, so a caller
    reading while an abandoned worker still appends is safe."""
    rec = _records()
    depth = getattr(_tls, "depth", 0)
    run_id = getattr(_tls, "run_id", "")
    # compile-event attribution rides along: a dispatch's XLA compiles
    # happen on the worker thread, but they belong to the caller's label
    # scope (telemetry/compile.py)
    from .telemetry.compile import adopt_labels, snapshot_labels

    labels = snapshot_labels()

    def _adopt() -> None:
        _tls.records = rec
        _tls.depth = depth
        _tls.run_id = run_id
        adopt_labels(labels)

    return _adopt


def reset_trace() -> None:
    _records().clear()


def summarize() -> str:
    """Indented per-stage timing table for the recorded events, rendered
    in START order (each span carries its t0): a parent prints before its
    children and siblings print in execution order.  Events used to
    append on stage EXIT, which printed nested stages before their
    parents and interleaved siblings misleadingly."""
    events = sorted(_records(), key=lambda e: (e.t0, -e.t1))
    lines = [
        f"{'  ' * e.depth}{e.name}: {e.seconds:.4f}s"
        + (f" [{e.detail}]" if e.detail else "")
        for e in events
    ]
    return "\n".join(lines)


def event(
    name: str,
    detail: str = "",
    log: Optional[object] = None,
    fields: Optional[Dict[str, Any]] = None,
) -> None:
    """Record an INSTANTANEOUS event (zero-duration TraceEvent) — failure/
    recovery markers from the resilience layer: retries, injected faults,
    dispatch timeouts, checkpoint resumes.  Stamped with the active run
    id, so a recovery marker attributes to the fit it interrupted.
    Always logged at `verbose >= 1` like timed stages."""
    depth = getattr(_tls, "depth", 0)
    now = time.time()
    _append(
        TraceEvent(
            name,
            0.0,
            depth,
            detail,
            t0=now,
            t1=now,
            thread_id=threading.get_ident(),
            run_id=getattr(_tls, "run_id", ""),
            kind="instant",
            pass_id=_current_pass_id,
            fields=fields,
        )
    )
    if int(get_config("verbose") or 0) >= 1:
        suffix = f" [{detail or fields}]" if detail or fields else ""
        (log or logger).info(f"[trace] {'  ' * depth}{name}{suffix}")


def fact(section: str, **fields: Any) -> None:
    """Record what a subsystem did for the active run, once, where it
    knows it: an instant `fact[<section>]` carrying `fields`.  This is
    the one way a number reaches a fit report (`telemetry/report.py`
    reads the run's facts by `run_id`; the last of a section stands), so
    the report holds this run's own and a run elsewhere in the process
    cannot reach it.  Outside any run the fact still lands in the
    thread's buffer (`last_fact`)."""
    event(f"fact[{section}]", fields=fields)


def last_fact(
    section: str, run_id: Optional[str] = None, all_threads: bool = False
) -> Dict[str, Any]:
    """The fields of the newest `fact[<section>]` in this thread's buffer
    (every thread's with `all_threads`), of `run_id` alone when given;
    {} when there is none."""
    name = f"fact[{section}]"
    if all_threads:
        with _buffers_lock:
            bufs = [rec for _, _, rec in _buffers]
    else:
        bufs = [_records()]
    newest: Optional[TraceEvent] = None
    for rec in bufs:
        for e in reversed(list(rec)):
            if e.name == name and e.fields is not None and (
                run_id is None or e.run_id == run_id
            ):
                if newest is None or e.t0 > newest.t0:
                    newest = e
                break
    return dict(newest.fields) if newest is not None else {}


class OpenSpan:
    """What `trace` yields: once the stage has ended, `seconds` is the
    duration its span recorded, so a caller that also decides by that
    interval (the staging engine's put rate) reads the span's clock and
    keeps none of its own."""

    __slots__ = ("seconds",)

    def __init__(self) -> None:
        self.seconds = 0.0


@contextlib.contextmanager
def trace(
    name: str, log: Optional[object] = None, detail: str = ""
) -> Iterator[OpenSpan]:
    """Time a stage.  Nested stages indent; `verbose >= 1` logs on exit.
    The recorded span carries absolute t0/t1, the recording thread id and
    the active run id (see `run_context`).  `detail` rides on the span as
    it does on an instant: the spans beneath a fit's leaf spans say there
    whether the host `wait`s (for the device, for a transfer) or `work`s
    (docs/observability.md, "Span vocabulary").  Yields the span's
    `OpenSpan`.

    Where jax is already imported the stage is also a
    `jax.profiler.TraceAnnotation`: nothing without a profiler session;
    with one (`profile_dir`, or a caller's own `start_trace`) a host event
    of the same name in the same trace as the device operations, on the
    profiler's clock."""
    depth = getattr(_tls, "depth", 0)
    _tls.depth = depth + 1
    # no jax import at module scope, and none caused here either
    jax = sys.modules.get("jax")
    annotation = jax.profiler.TraceAnnotation(name) if jax is not None else None
    span = OpenSpan()
    t0_abs = time.time()
    t0 = time.perf_counter()
    if annotation is not None:
        annotation.__enter__()
    try:
        yield span
    finally:
        if annotation is not None:
            annotation.__exit__(None, None, None)
        dt = span.seconds = time.perf_counter() - t0
        _tls.depth = depth
        _append(
            TraceEvent(
                name,
                dt,
                depth,
                detail,
                t0=t0_abs,
                t1=t0_abs + dt,
                thread_id=threading.get_ident(),
                run_id=getattr(_tls, "run_id", ""),
                kind="span",
                pass_id=_current_pass_id,
            )
        )
        if int(get_config("verbose") or 0) >= 1:
            (log or logger).info(f"[trace] {'  ' * depth}{name}: {dt:.4f}s")


def record_span(
    name: str, t0_abs: float, t1_abs: float, detail: str = ""
) -> None:
    """Record an already-timed span from absolute epoch endpoints — for
    producers that measured a window themselves (the pod layer's bounded
    cross-process waits) and only want it on the trace after the fact.
    Stamped with the active run id and the pod-global pass id exactly
    like `trace()`."""
    _append(
        TraceEvent(
            name,
            max(t1_abs - t0_abs, 0.0),
            getattr(_tls, "depth", 0),
            detail,
            t0=float(t0_abs),
            t1=float(t1_abs),
            thread_id=threading.get_ident(),
            run_id=getattr(_tls, "run_id", ""),
            kind="span",
            pass_id=_current_pass_id,
        )
    )


_profile_lock = threading.Lock()
_profile_active = False


@contextlib.contextmanager
def device_profile() -> Iterator[None]:
    """Wrap a region in `jax.profiler.trace` when `profile_dir` is set —
    the XLA/TPU execution profile (TensorBoard `xprof` format).  The jax
    profiler is process-global, so concurrent fits (fitMultiple consumers)
    share one trace: only the first caller starts/stops it."""
    global _profile_active
    profile_dir = get_config("profile_dir")
    if not profile_dir:
        yield
        return
    with _profile_lock:
        owner = not _profile_active
        if owner:
            import jax

            jax.profiler.start_trace(str(profile_dir))
            _profile_active = True
    try:
        yield
    finally:
        if owner:
            with _profile_lock:
                # only stop a trace that actually started: if start_trace
                # raised, _profile_active never became True and calling
                # stop_trace would mask the original error
                if _profile_active:
                    import jax

                    jax.profiler.stop_trace()
                    _profile_active = False
                    logger.info(f"Wrote device profile to {profile_dir}")
