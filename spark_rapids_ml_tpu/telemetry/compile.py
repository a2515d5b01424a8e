#
# Compile observability — where the non-execute time goes.  XLA
# compilation is this repo's second currency after HBM: a cold fit pays
# tens of seconds of lowering+compile (the 87.8 s round-1 finding that
# motivated shape bucketing), an elastic mesh shrink re-lowers every
# donated staging program for the surviving device set, and a precision
# flip drops every compiled kernel — none of which was measurable
# before this module.  Two mechanisms, used together:
#
#   jax.monitoring   a process-global listener
#                    (`register_event_duration_secs_listener`) turns
#                    jax's own compile events
#                    (`/jax/core/compile/jaxpr_trace_duration`,
#                    `.../jaxpr_to_mlir_module_duration`,
#                    `.../backend_compile_duration`) into the
#                    `compile_seconds{fn=,phase=}` histogram and the
#                    `compiles_total{fn=}` counter.  The `fn` label is
#                    the innermost `compile_label(...)` scope active on
#                    the compiling thread (FitTelemetry labels the whole
#                    fit with its estimator name; the staging engine
#                    labels its program builds), so compile time
#                    attributes to the work that paid it.
#                    The same events land in the active run's trace as
#                    `compile[trace|lower|backend_compile|cache_read]`
#                    spans, placed where they happened.
#   explicit spans   `compile_span(fn)` wraps our OWN lowering seams
#                    (the staging-program builders in parallel/mesh.py)
#                    in a timed trace span + the same histogram.
#
# Recompiles are always EXPLICIT: `note_recompile(fn, reason)` bumps
# `recompiles_total{fn=,reason=}` and drops a `recompile[fn]` instant
# marker into the active run's trace buffer — so an elastic recovery's
# re-lowering storm (`mesh.drop_staging_programs`) is visible inside the
# span tree of the fit it interrupted, next to the retry and recovery
# markers.
#
# No jax import at module scope (telemetry/ rule); the listener installs
# lazily on the first fit, by which point jax is loaded anyway.
#
from __future__ import annotations

import contextlib
import threading
import time

from .locks import named_lock
from typing import Iterator

from .registry import counter, histogram

# compile durations cluster far below the fit-duration buckets: a
# recompiled staging program is ~10 ms, a cold solver lowering ~1-100 s
_COMPILE_BUCKETS = (
    0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 30.0,
    60.0, 120.0, 300.0,
)

compile_seconds = histogram(
    "compile_seconds",
    "Seconds spent in jax tracing/lowering/XLA compilation, by label "
    "and phase",
    buckets=_COMPILE_BUCKETS,
)
compiles_total = counter(
    "compiles_total", "XLA backend compilations observed, by label"
)
recompiles_total = counter(
    "recompiles_total",
    "Compiled programs dropped and re-lowered, by label and reason",
)

# jax.monitoring event key -> phase label; events outside this map are
# not compile-related and stay unrecorded
_PHASE_BY_KEY = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    # a persistent-cache hit's read lies INSIDE the backend_compile
    # duration jax reports for the same program: a span, and no further
    # sample of the histogram
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
}

_tls = threading.local()
_install_lock = named_lock("compile_install")
_installed = False


def current_label() -> str:
    """The innermost compile-label scope on this thread ("unlabeled"
    outside any scope)."""
    stack = getattr(_tls, "labels", None)
    return stack[-1] if stack else "unlabeled"


def snapshot_labels() -> tuple:
    """This thread's label stack, for adoption by a worker thread
    (tracing.adopt_trace_context carries it together with the trace
    buffer/run id, so compiles inside a watchdog-guarded dispatch
    attribute to the fit that issued it)."""
    return tuple(getattr(_tls, "labels", ()) or ())


def adopt_labels(stack) -> None:
    """Install a snapshot taken by `snapshot_labels` on this thread."""
    _tls.labels = list(stack)


@contextlib.contextmanager
def compile_label(name: str) -> Iterator[None]:
    """Attribute every compile event recorded on this thread inside the
    scope to `name` (nests; innermost wins).  FitTelemetry scopes the
    whole fit with the estimator name, so `compile_seconds{fn="KMeans"}`
    answers "what did KMeans fits spend compiling"."""
    stack = getattr(_tls, "labels", None)
    if stack is None:
        stack = _tls.labels = []
    stack.append(str(name))
    try:
        yield
    finally:
        stack.pop()


def _at_top_level() -> bool:
    """Whether no jax trace is in progress on this thread."""
    import jax

    try:
        return bool(jax.core.trace_ctx.is_top_level())
    except AttributeError:  # another jax: every trace event gets a span
        return True


def _on_duration(key: str, duration_s: float, **_kw) -> None:
    from ..tracing import record_span

    phase = _PHASE_BY_KEY.get(key)
    if phase is None:
        return
    # jax reports a phase as it ends, on the thread that ran it: the span
    # `compile[<phase>]` lands in the active run at (now - duration, now),
    # so a fit that re-jits shows it and an idle gap spent compiling is
    # named so.  A jitted function traced inside another's trace (every
    # jnp call of a solver body: hundreds in a cold fit) lies inside its
    # caller's span and gets none of its own
    if phase != "trace" or _at_top_level():
        now = time.time()
        record_span(f"compile[{phase}]", now - float(duration_s), now)
    if phase == "cache_read":
        return
    label = current_label()
    compile_seconds.observe(float(duration_s), fn=label, phase=phase)
    if phase == "backend_compile":
        compiles_total.inc(fn=label)


def install_jax_listener() -> bool:
    """Register the jax.monitoring duration listener (idempotent; jax
    offers no per-listener removal, so it installs once per process).
    Returns whether the listener is active."""
    global _installed
    with _install_lock:
        if not _installed:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(_on_duration)
            _installed = True
        return True


def listener_installed() -> bool:
    return _installed


@contextlib.contextmanager
def compile_span(fn: str) -> Iterator[None]:
    """Time one of OUR lowering seams (a staging-program build, an
    explicit re-lower) as a trace span + a `compile_seconds{fn=,
    phase="explicit"}` observation.  The monitoring listener also
    records the inner jax phases under the same `fn` via the label
    scope."""
    from ..tracing import trace

    t0 = time.perf_counter()
    with compile_label(fn):
        with trace(f"compile[{fn}]"):
            yield
    compile_seconds.observe(
        time.perf_counter() - t0, fn=fn, phase="explicit"
    )


def note_recompile(fn: str, reason: str, count: int = 1) -> None:
    """Record that compiled program(s) under `fn` were dropped and must
    re-lower (`reason`: elastic_shrink, precision_change, ...).  Bumps
    `recompiles_total{fn=,reason=}` and drops a `recompile[fn]` instant
    marker stamped with the active run id — the elastic-recovery caller
    runs on the interrupted fit's (adopted) thread, so the marker lands
    inside that fit's span tree."""
    recompiles_total.inc(int(count), fn=fn, reason=reason)
    try:
        from ..tracing import event

        event(f"recompile[{fn}]", detail=f"reason={reason} n={int(count)}")
    except Exception:
        pass


__all__ = [
    "adopt_labels",
    "compile_label",
    "compile_seconds",
    "compile_span",
    "compiles_total",
    "current_label",
    "install_jax_listener",
    "listener_installed",
    "note_recompile",
    "recompiles_total",
    "snapshot_labels",
]
