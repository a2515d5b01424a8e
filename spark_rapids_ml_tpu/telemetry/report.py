#
# Per-fit telemetry reports — one JSON artifact per fit answering "what
# did this fit actually do": the stage timing tree (from the run's
# spans), bytes staged and staging throughput, cache hits/evictions,
# retries and recoveries (with iterations salvaged), and the solver's
# iteration count / loss curve.  `core.Estimator.fit` opens a
# `FitTelemetry` around every fit: it mints the run id (tracing.py
# `run_context`), snapshots the registry before/after, and — when the
# `telemetry_dir` conf is set — writes `<dir>/fit_<Est>_<run_id>.json`.
# The same dict is reachable in-process as `model.fit_report()`.
#
from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Dict, List, Optional

from .locks import named_lock
from .registry import REGISTRY, delta, histogram

_fit_seconds = histogram(
    "fit_duration_seconds", "Wall-clock seconds per estimator fit"
)

# model attribute names the solver summary scans, in preference order
_N_ITER_KEYS = ("n_iter_", "num_iters", "n_iter")
_LOSS_CURVE_KEYS = ("objective_history", "loss_curve", "hist")
_FINAL_LOSS_KEYS = ("objective", "inertia_", "cost", "loss")


# slack when asking whether one span lies inside another: a span's end is
# its epoch start plus a perf_counter duration, two clocks that agree to
# well under this
_INSIDE_SLACK_S = 1e-3


def span_tree(events: List[Any]) -> List[Dict[str, Any]]:
    """Nest the run's events into a start-ordered tree (instant markers
    attach as zero-duration leaves).  An event's parent is the deepest
    span recorded at a lesser depth that holds it IN TIME, on its own
    thread before any other: a worker thread that adopted the caller's
    trace context (the staging prefetch thread, a guarded dispatch)
    records at the caller's depth, concurrently with the caller's own
    spans, so depth alone would hang a span under a neighbour that merely
    started earlier.  A child lies inside its parent, or it is a sibling.
    Events arrive start-sorted from `tracing.get_all_trace_events`."""
    root: List[Dict[str, Any]] = []
    open_spans: List[tuple] = []  # (event, node) that may still hold a later one
    for e in sorted(events, key=lambda e: (e.t0, -e.t1)):
        node: Dict[str, Any] = {
            "name": e.name,
            "t0": round(e.t0, 6),
            "seconds": round(e.seconds, 6),
        }
        if e.detail:
            node["detail"] = e.detail
        if getattr(e, "fields", None) is not None:
            node["fields"] = dict(e.fields)
        if getattr(e, "kind", "span") == "instant":
            node["instant"] = True
        node["children"] = []
        open_spans = [
            (p, n) for p, n in open_spans if p.t1 + _INSIDE_SLACK_S >= e.t0
        ]
        holders = [
            (p.depth, p.thread_id == e.thread_id, p.t0, n)
            for p, n in open_spans
            if p.depth < e.depth and e.t1 <= p.t1 + _INSIDE_SLACK_S
        ]
        parent = max(holders, key=lambda h: h[:3])[3] if holders else None
        (parent["children"] if parent else root).append(node)
        if "instant" not in node:
            open_spans.append((e, node))
    # drop empty children arrays for a compact artifact
    def _prune(nodes: List[Dict[str, Any]]) -> None:
        for n in nodes:
            if n["children"]:
                _prune(n["children"])
            else:
                del n["children"]

    _prune(root)
    return root


def solver_summary(model: Any) -> Dict[str, Any]:
    """Iteration count / loss curve from a fitted model's attributes —
    generic over the solver families (KMeans `n_iter_`, LogReg
    `num_iters` + `objective_history`, LinReg diag `n_iter`)."""
    attrs: Dict[str, Any] = {}
    getter = getattr(model, "_get_model_attributes", None)
    if callable(getter):
        try:
            attrs = dict(getter() or {})
        except Exception:
            attrs = {}
    out: Dict[str, Any] = {}
    for k in _N_ITER_KEYS:
        v = attrs.get(k, getattr(model, k, None))
        if v is not None:
            try:
                out["n_iter"] = int(v)
                break
            except (TypeError, ValueError):
                continue
    for k in _LOSS_CURVE_KEYS:
        v = attrs.get(k)
        if v is not None:
            try:
                out["loss_curve"] = [float(x) for x in list(v)]
                break
            except (TypeError, ValueError):
                continue
    for k in _FINAL_LOSS_KEYS:
        v = attrs.get(k, getattr(model, k, None))
        if isinstance(v, (int, float)):
            out["final_loss"] = float(v)
            break
    return out


def _view_delta(d: Dict[str, Dict[str, Any]], family: str) -> Dict[str, Any]:
    """One dict-view family's changed keys from a registry `delta`:
    {'key=hits': 3} -> {'hits': 3}."""
    out = {}
    for ls, v in d.get(family, {}).items():
        k = ls.split("=", 1)[1] if ls.startswith("key=") else ls
        out[k] = v
    return out


class FitTelemetry:
    """The per-fit observability scope `core.Estimator.fit` wraps every
    fit in: mints the run id, opens the root `fit[<Est>]` span, and after
    the fit builds the report dict from the run's own events (spans,
    resilience markers, and the facts its subsystems recorded with
    `tracing.fact`) plus registry deltas.

    The run's events are exact: two fits that overlap each report their
    own `staging`, `fused`, `stats`, `solver_decision`, `pass_report`.
    The registry deltas are process-global: when fits OVERLAP (a caller
    pulling `fitMultiple` from several threads), each report's
    staging counts and cache/recovery/lock/compile-seconds sections
    include the concurrent fits' activity too — the report then carries
    `"concurrent_fits": true` so those numbers are read as
    process-level, not per-fit."""

    # fits currently inside span(): a fit that starts while others are
    # open marks them and itself, so the first starter finishing last
    # still knows its registry deltas span more than this fit
    _live: List["FitTelemetry"] = []
    _active_lock = named_lock("fit_telemetry_active")

    def __init__(self, estimator_name: str) -> None:
        self.estimator = estimator_name
        self.run_id: str = ""
        self.report: Optional[Dict[str, Any]] = None
        self._before: Dict[str, Dict[str, Any]] = {}
        self._t0 = 0.0
        self._t1 = 0.0
        self._overlapped = False
        self._watermark = None

    @contextlib.contextmanager
    def span(self):
        from ..tracing import mint_run_id, run_context, trace
        from .compile import compile_label, install_jax_listener
        from .exporters import maybe_start_http_server
        from .memory import FitMemoryWatermark

        maybe_start_http_server()
        install_jax_listener()
        self.run_id = mint_run_id("fit")
        # fold the named locks' pending accounting in BEFORE the
        # baseline snapshot, so this fit's registry delta reflects only
        # the lock traffic of its own window
        from .locks import publish_lock_metrics

        publish_lock_metrics()
        self._before = REGISTRY.snapshot()
        self._t0 = time.time()
        cls = FitTelemetry
        with cls._active_lock:
            for other in cls._live:
                other._overlapped = self._overlapped = True
            cls._live.append(self)
        self._watermark = FitMemoryWatermark(self.run_id, self.estimator)
        self._watermark.open()
        try:
            with run_context(self.run_id):
                # compile events on this thread (and adopted workers)
                # attribute to this estimator
                with compile_label(self.estimator):
                    with trace(f"fit[{self.estimator}]"):
                        yield self
        finally:
            with cls._active_lock:
                cls._live.remove(self)
            self._watermark.close()
        self._t1 = time.time()

    def _resilience_section(
        self, events: List[Any], deltas: Dict[str, Dict[str, Any]]
    ) -> Dict[str, Any]:
        instants = [e for e in events if getattr(e, "kind", "") == "instant"]
        sec = {
            "retries": sum(
                1 for e in instants if e.name.startswith("retry[")
            ),
            "faults_injected": sum(
                1 for e in instants if e.name.startswith("fault_injected[")
            ),
            "dispatch_timeouts": sum(
                1 for e in instants if e.name.startswith("dispatch_timeout[")
            ),
            "checkpoint_resumes": sum(
                1 for e in instants
                if e.name.endswith("_resume") or e.name == "elastic_recovery[resumed]"
            ),
            # a resident fit that ran out of device memory and was refit
            # by epoch streaming (core._stage_or_stream)
            "oom_streaming_refits": sum(
                1 for e in instants if e.name == "oom_streaming_refit"
            ),
        }
        rec = _view_delta(deltas, "recovery")
        if rec:
            sec["recoveries"] = rec
            if "iterations_salvaged" in rec:
                sec["iterations_salvaged"] = rec["iterations_salvaged"]
        return sec

    def _compile_section(
        self, events: List[Any], deltas: Dict[str, Dict[str, Any]]
    ) -> Dict[str, Any]:
        """Compile time + recompile count for this fit.  The recompile
        count is RUN-EXACT (the `recompile[...]` instant markers carry
        this run's id); the seconds come from the registry delta of
        `compile_seconds`, filtered to this estimator's label where the
        jax.monitoring listener attributed them (process-global samples
        under other labels are excluded, so a concurrent fit's compiles
        don't leak in)."""
        sec: Dict[str, Any] = {}
        recompiles = [
            e
            for e in events
            if getattr(e, "kind", "") == "instant"
            and e.name.startswith("recompile[")
        ]
        if recompiles:
            sec["recompiles"] = len(recompiles)
            sec["recompiled"] = sorted(
                {e.name[len("recompile["):-1] for e in recompiles}
            )
        seconds = 0.0
        count = 0
        for ls, v in deltas.get("compile_seconds", {}).items():
            if f"fn={self.estimator}" not in ls.split(","):
                continue
            if isinstance(v, dict):
                seconds += float(v.get("sum", 0.0))
                count += int(v.get("count", 0))
        if count:
            sec["seconds"] = round(seconds, 4)
            sec["events"] = count
        return sec

    def _profile_section(self) -> Dict[str, Any]:
        """Cross-reference the XProf capture (`profile_dir` conf) so the
        device profile and this report's run_id stop being orphaned from
        each other: the report names the profile directory plus any
        artifact entries written during this fit's window."""
        from ..config import get_config

        pdir = str(get_config("profile_dir") or "")
        if not pdir:
            return {}
        sec: Dict[str, Any] = {"dir": pdir}
        try:
            arts = []
            # top level: trace FILES only (the 'plugins' container dir's
            # mtime refreshes on every child write and is not itself an
            # artifact); under plugins/profile the per-capture TIMESTAMP
            # DIRECTORIES are the artifacts XProf consumes
            for root, dirs_ok in (
                (pdir, False),
                (os.path.join(pdir, "plugins", "profile"), True),
            ):
                if not os.path.isdir(root):
                    continue
                upper = (self._t1 if self._t1 > 0 else time.time()) + 1.0
                for name in os.listdir(root):
                    p = os.path.join(root, name)
                    if not dirs_ok and not os.path.isfile(p):
                        continue
                    # written during (± 1 s of) THIS fit's window: a
                    # later fit sharing the profile_dir must not have
                    # its capture attributed here
                    if self._t0 - 1.0 <= os.path.getmtime(p) <= upper:
                        arts.append(os.path.relpath(p, pdir))
            if arts:
                sec["artifacts"] = sorted(arts)
        except OSError:
            pass
        return sec

    def build(self, model: Any = None) -> Dict[str, Any]:
        """Assemble the report from the run's events + registry deltas.
        Called once, after `span()` exits.  Reads only the CALLING
        thread's trace buffer: every event of this run lands there by
        construction (watchdog workers adopt it; concurrent fits on
        other threads carry other run ids), so the per-fit cost stays a
        single bounded-buffer scan, not a cross-thread merge."""
        from ..tracing import get_trace_events

        events = [
            e for e in get_trace_events() if e.run_id == self.run_id
        ]
        from .locks import publish_lock_metrics

        publish_lock_metrics()
        deltas = delta(self._before, REGISTRY.snapshot())
        wall = max(self._t1 - self._t0, 0.0)
        _fit_seconds.observe(wall, estimator=self.estimator)

        # what the subsystems recorded for THIS run (`tracing.fact`): the
        # last fact of a section stands
        facts: Dict[str, Dict[str, Any]] = {
            e.name[len("fact["):-1]: e.fields
            for e in events
            if getattr(e, "fields", None) is not None
        }
        staging: Dict[str, Any] = {
            **_view_delta(deltas, "staging_counts"),
            **facts.get("staging", {}),
        }
        # the three decisions a fit can take on the way: the PCA solver,
        # the parquet reader count, and a serving dispatch's padding
        # class (prefixed, so its keys never collide with the others')
        solver_decision: Dict[str, Any] = {
            **facts.get("pca_solver", {}),
            **facts.get("parquet_readers", {}),
            **{
                f"serving_{k}": v
                for k, v in facts.get("serving_bucket", {}).items()
            },
        }

        report: Dict[str, Any] = {
            "run_id": self.run_id,
            "estimator": self.estimator,
            # set when another fit overlapped this one: the registry
            # deltas below then include the concurrent fits' activity
            # (what is built from the run's events stays run-exact)
            **({"concurrent_fits": True} if self._overlapped else {}),
            "t0": round(self._t0, 6),
            "t1": round(self._t1, 6),
            "wall_s": round(wall, 4),
            "spans": span_tree(events),
            "staging": staging,
            "cache": _view_delta(deltas, "device_cache"),
            "resilience": self._resilience_section(events, deltas),
        }
        # per-fit lock profile: this window's acquisitions / contended
        # acquires / wait seconds per lock (registry counter deltas,
        # process-global like the other delta sections — `concurrent_
        # fits` marks the overlap caveat above)
        lock_sec: Dict[str, Any] = {}
        for fam, short in (
            ("lock_wait_seconds_total", "wait_s"),
            ("lock_contended_total", "contended"),
            ("lock_acquisitions_total", "acquisitions"),
        ):
            for ls, v in deltas.get(fam, {}).items():
                name = ls.split("=", 1)[1] if ls.startswith("lock=") else ls
                lock_sec.setdefault(name, {})[short] = (
                    round(v, 6) if isinstance(v, float) else v
                )
        if any(e.get("wait_s") for e in lock_sec.values()):
            report["locks"] = {
                k: v for k, v in sorted(
                    lock_sec.items(),
                    key=lambda kv: -(kv[1].get("wait_s", 0) or 0),
                )
                if v.get("wait_s")
            }
        # the run's utilization timeline (telemetry/utilization.py):
        # device-busy fraction + ranked idle-gap attribution
        from . import utilization as _utilization

        util = _utilization.summarize(run_id=self.run_id, scope="fit")
        if util:
            report["utilization"] = util
        chunk_cache = _view_delta(deltas, "chunk_cache")
        if any(chunk_cache.values()):
            report["chunk_cache"] = chunk_cache
        for section in ("fused", "stats", "pass_report", "forest", "lbfgs"):
            if facts.get(section):
                report[section] = dict(facts[section])
        if solver_decision:
            report["solver_decision"] = solver_decision
        if self._watermark is not None:
            memory = self._watermark.section()
            if memory:
                report["memory"] = memory
        comp = self._compile_section(events, deltas)
        if comp:
            report["compile"] = comp
        prof = self._profile_section()
        if prof:
            report["profile"] = prof
        solver = solver_summary(model) if model is not None else {}
        if solver:
            report["solver"] = solver
        # drift baseline (monitor/): a fit that captured a fingerprint
        # records what it holds — the serving-side comparison is live
        # state (server.report()), but "did THIS fit capture a
        # baseline, from how many rows" belongs in the fit artifact
        fp = getattr(model, "_drift_baseline", None)
        if fp is not None:
            report["drift"] = {
                "baseline_rows": int(fp.n),
                "columns": int(fp.d),
            }
        self.report = report
        return report

    def attach(self, model: Any, log: Optional[object] = None) -> None:
        """Build the report, expose it as `model.fit_report()`, and write
        the JSON artifact when `telemetry_dir` is set.  Never raises —
        observability must not fail the fit it observed."""
        # the report's own assembly is a span of the report, a root beside
        # `fit[<Est>]`: what the instrumentation costs each fit, and the
        # name an idle device's time between two fits goes under
        t0, c0 = time.time(), time.perf_counter()
        try:
            report = self.build(model)
        except Exception as e:  # pragma: no cover - defensive
            _warn(log, f"fit report build failed ({type(e).__name__}: {e})")
            return
        report["spans"].append({
            "name": "fit_report", "t0": round(t0, 6),
            "seconds": round(time.perf_counter() - c0, 6), "detail": "work",
        })
        try:
            model._fit_report = report
        except Exception:
            pass  # models without assignable attributes keep the artifact
        from ..config import get_config

        tdir = str(get_config("telemetry_dir") or "")
        if not tdir:
            return
        try:
            os.makedirs(tdir, exist_ok=True)
            path = os.path.join(
                tdir, f"fit_{self.estimator}_{self.run_id}.json"
            )
            tmp = f"{path}.tmp"
            with open(tmp, "w") as f:
                json.dump(report, f, indent=1)
            os.replace(tmp, path)
        except OSError as e:
            _warn(log, f"fit report write to {tdir} failed ({e})")


def _warn(log: Optional[object], msg: str) -> None:
    if log is None:
        from ..utils import get_logger

        log = get_logger("spark_rapids_ml_tpu.telemetry")
    log.warning(msg)


__all__ = ["FitTelemetry", "solver_summary", "span_tree"]
