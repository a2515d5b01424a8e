#
# The serving-side drift monitor — fit-time baselines vs sliding
# serving-window sketches, scored continuously.  One process-global
# `MONITOR` tracks every served model that carries a baseline
# fingerprint (registered by `ServingServer.register` alongside the
# model pin):
#
#   observe(model, X)          the dispatcher's already-decoded host
#                              batches fold into the model's current
#                              tumbling window (host tier only — the
#                              device hot path pays nothing; the fold is
#                              buffered-amortized)
#   observe_output(model, outs) prediction-side drift: output columns
#                              (predicted classes, regression outputs)
#                              fold into per-column windows whose
#                              REFERENCE is the first closed window
#                              (the fit produces no output distribution,
#                              so serving's own early traffic is the
#                              anchor)
#
# Windows tumble every `drift_window_s`; scoring always sees the last
# closed window MERGED with the current partial one (mergeable
# sketches), so the view slides with bounded memory — two builders per
# model, the flight-recorder-ring discipline.  Divergences
# (monitor/compare.py) export as `drift_score{model,column,stat}`
# gauges bounded to the `drift_top_k` highest-scoring columns (stale
# column series are removed, so the family stays within its
# METRIC_CATALOG cardinality), plus the per-model `_overall` series the
# alert watches: overall above `drift_alert_threshold` SUSTAINED for
# `drift_alert_sustain_s` fires ONE flight-recorder post-mortem
# (`postmortems_total{reason="drift"}`, the recorder's per-reason
# cooldown absorbing storms) whose bundle carries BOTH fingerprints and
# the divergence table — evidence even when nobody was watching the
# gauges, the PR-12 contract.
#
from __future__ import annotations


from ..telemetry.locks import named_lock
import time
from typing import Any, Dict, List, Optional, Set, Tuple

import numpy as np

from ..config import get_config
from ..telemetry.registry import counter, gauge
from ..utils import get_logger
from .compare import STAT_NAMES, divergence_table
from .fingerprint import (
    BaselineBuilder,
    Fingerprint,
    builder_from_bytes,
    builder_to_bytes,
)

logger = get_logger("spark_rapids_ml_tpu.monitor")

DRIFT_SCORE = gauge(
    "drift_score",
    "Data/model drift divergence per model, column and statistic "
    "(top-k drifting columns; column=_overall is the alert score)",
)
DRIFT_SCORE_PARTIAL = gauge(
    "drift_score_partial",
    "This process's LOCAL window drift score per model, next to the "
    "pod-merged drift_score (fleet merge on, multi-process only)",
)
DRIFT_ROWS = counter(
    "drift_rows_observed_total",
    "Serving rows folded into the drift monitor's windows, by model",
)

# divergence recomputation is rate-limited per model (the fold itself
# runs on every observe; scoring walks the sketches)
_REFRESH_S = 1.0
# output columns tracked per model (prediction-side drift stays bounded
# no matter how wide a model's output dict is)
_MAX_OUTPUT_COLS = 4


class _Window:
    """One tumbling-window pair: the current building window and the
    last closed one.  `view()` merges them — the bounded sliding view
    the comparator scores."""

    __slots__ = ("d", "cur", "t0", "last", "columns")

    def __init__(self, d: int, columns=()) -> None:
        self.d = int(d)
        self.cur = BaselineBuilder(d)
        self.t0 = time.monotonic()
        self.last: Optional[BaselineBuilder] = None
        self.columns = list(columns or ())

    def maybe_roll(self, window_s: float) -> Optional[BaselineBuilder]:
        """Tumble when the current window aged past `window_s`; returns
        the closed builder (the caller may freeze it as a reference)."""
        now = time.monotonic()
        if now - self.t0 < window_s or self.cur.n == 0:
            return None
        closed = self.cur
        self.last = closed
        self.cur = BaselineBuilder(self.d)
        self.t0 = now
        return closed

    def fold(self, X: np.ndarray) -> None:
        self.cur.update(X)

    def view_builder(self) -> Optional[BaselineBuilder]:
        """The merged last+current BUILDER behind `view()` — the pod
        drift merge (telemetry/fleet.py) folds peers' window blobs into
        this before finalizing."""
        if self.last is not None and (
            (self.last.k, self.last.cap, self.last.bits)
            != (self.cur.k, self.cur.cap, self.cur.bits)
        ):
            # a summarizer_* sketch conf changed between tumbles: the
            # closed window's geometry no longer merges with the
            # current builder's — discard the stale window rather than
            # stall scoring until it ages out (the stats engine makes
            # conf-geometry changes safe; so must this path)
            self.last = None
        if self.last is not None and self.last.n > 0:
            return (
                self.last.merge(self.cur) if self.cur.n > 0 else self.last
            )
        if self.cur.n == 0:
            return None
        return self.cur

    def view(self) -> Optional[Fingerprint]:
        b = self.view_builder()
        return None if b is None else b.finalize(self.columns)


class _ModelState:
    __slots__ = (
        "baseline", "window", "outputs", "out_refs", "rows",
        "last_refresh", "above_since", "last_table", "last_out",
        "alerts", "exported",
    )

    def __init__(self, baseline: Fingerprint) -> None:
        self.baseline = baseline
        self.window = _Window(baseline.d, baseline.columns)
        # output column key -> _Window(d=1); reference fingerprints are
        # frozen from each key's FIRST closed window
        self.outputs: Dict[str, _Window] = {}
        self.out_refs: Dict[str, Fingerprint] = {}
        self.rows = 0
        self.last_refresh = 0.0
        self.above_since: Optional[float] = None
        self.last_table: Optional[Dict[str, Any]] = None
        self.last_out: Dict[str, float] = {}
        self.alerts = 0
        # (column, stat) label pairs currently exported, for pruning
        self.exported: Set[Tuple[str, str]] = set()


class DriftMonitor:
    """Process-global drift state over every baseline-carrying served
    model.  All entry points are cheap, never raise into the serving
    path, and hold only this monitor's lock."""

    def __init__(self) -> None:
        self._mu = named_lock("drift_monitor", kind="rlock")
        self._models: Dict[str, _ModelState] = {}

    # -- registration --------------------------------------------------------

    def register(self, name: str, baseline: Fingerprint) -> None:
        """Track `name` against `baseline` (called by
        `ServingServer.register` when the pinned model carries a
        fit-time fingerprint).  Re-registering replaces the state — a
        hot-swapped model restarts its windows against the new
        baseline."""
        with self._mu:
            old = self._models.pop(name, None)
            self._models[name] = _ModelState(baseline)
        if old is not None:
            self._prune(name, old.exported, set())

    def drop(self, name: str) -> None:
        with self._mu:
            st = self._models.pop(name, None)
        if st is not None:
            self._prune(name, st.exported, set())
            DRIFT_SCORE.remove(model=name, column="_overall", stat="score")
            try:
                from ..parallel.context import process_topology

                DRIFT_SCORE_PARTIAL.remove(
                    model=name, process=str(process_topology()[1])
                )
            except Exception:
                pass

    def tracks(self, name: str) -> bool:
        with self._mu:
            return name in self._models

    def names(self) -> List[str]:
        with self._mu:
            return sorted(self._models)

    def clear(self) -> None:
        for name in self.names():
            self.drop(name)

    # -- folding (the serving hot path, host tier) ---------------------------

    def observe(self, name: str, X: Any) -> None:
        """Fold one decoded host batch into the model's current window
        (feature side).  Never raises — a malformed block is dropped
        with a debug log, not a failed request."""
        with self._mu:
            st = self._models.get(name)
            if st is None:
                return
            try:
                X = np.asarray(X)
                if X.ndim == 1:
                    X = X[None, :]
                st.window.fold(X)
                rows = int(X.shape[0])
                st.rows += rows
            except Exception as e:
                logger.debug(f"drift fold dropped a block ({e})")
                return
        DRIFT_ROWS.inc(rows, model=name)
        self._maybe_refresh(name)

    def observe_output(self, name: str, outs: Dict[str, Any]) -> None:
        """Fold a batch's output columns (prediction side).  1-D numeric
        outputs fold as themselves; 2-D outputs (class probabilities)
        fold their leading columns, bounded at `_MAX_OUTPUT_COLS` keys
        per model."""
        with self._mu:
            st = self._models.get(name)
            if st is None:
                return
            try:
                for col in sorted(outs):
                    arr = np.asarray(outs[col])
                    if arr.dtype.kind not in "fiu" or arr.size == 0:
                        continue
                    mat = arr.reshape(arr.shape[0], -1)
                    for i in range(mat.shape[1]):
                        key = col if mat.shape[1] == 1 else f"{col}[{i}]"
                        w = st.outputs.get(key)
                        if w is None:
                            if len(st.outputs) >= _MAX_OUTPUT_COLS:
                                continue
                            w = st.outputs[key] = _Window(1, [key])
                        w.fold(mat[:, i:i + 1].astype(np.float64))
            except Exception as e:
                logger.debug(f"drift output fold dropped a block ({e})")

    # -- scoring -------------------------------------------------------------

    def _maybe_refresh(self, name: str) -> None:
        now = time.monotonic()
        with self._mu:
            st = self._models.get(name)
            if st is None or now - st.last_refresh < _REFRESH_S:
                return
            st.last_refresh = now
        try:
            self.refresh(name)
        except Exception as e:  # scoring must never fail a request
            logger.warning(f"drift refresh for {name!r} failed ({e})")

    def refresh(self, name: str) -> Optional[Dict[str, Any]]:
        """Recompute divergences for `name`, update the gauges, and run
        the alert state machine.  Returns the divergence table (None
        when the window is still below `drift_min_window_rows`)."""
        window_s = max(float(get_config("drift_window_s")), 1e-3)
        min_rows = max(int(get_config("drift_min_window_rows")), 1)
        top_k = max(int(get_config("drift_top_k")), 1)
        fleet_on = self._fleet_active()
        with self._mu:
            st = self._models.get(name)
            if st is None:
                return None
            closed = st.window.maybe_roll(window_s)
            for key, w in st.outputs.items():
                oclosed = w.maybe_roll(window_s)
                if oclosed is not None and key not in st.out_refs:
                    # the first closed window freezes as the output
                    # reference distribution
                    ref = oclosed.finalize([key])
                    if ref is not None:
                        st.out_refs[key] = ref
            view = st.window.view()
            pod_vb = None
            if fleet_on:
                pod_vb = st.window.view_builder()
                if pod_vb is st.window.cur and pod_vb.n > 0:
                    # the live builder keeps folding once the lock
                    # drops; the pod merge below runs unlocked (it
                    # probes the KV seam), so it works on a wire-
                    # round-trip SNAPSHOT instead
                    pod_vb = builder_from_bytes(builder_to_bytes(pod_vb))
            columns = list(st.window.columns)
            baseline = st.baseline
            out_views = {
                key: (st.out_refs.get(key), w.view())
                for key, w in st.outputs.items()
            }
        partial: Optional[Fingerprint] = None
        if fleet_on:
            pod_view = self._pod_view(name, closed, pod_vb, columns)
            if pod_view is not None:
                view, partial = pod_view, view
        if view is None or view.n < min_rows:
            return None
        table = divergence_table(baseline, view, top_k)
        if partial is not None and partial.n >= min_rows:
            # the local window's score stays visible next to the
            # pod-merged one, keyed by this process's rank
            try:
                from ..parallel.context import process_topology

                pt = divergence_table(baseline, partial, 1)
                DRIFT_SCORE_PARTIAL.set(
                    pt["overall"], model=name,
                    process=str(process_topology()[1]),
                )
            except Exception:
                pass
        out_scores: Dict[str, float] = {}
        for key, (ref, wv) in out_views.items():
            if ref is None or wv is None or wv.n < min_rows:
                continue
            t = divergence_table(ref, wv, 1)
            out_scores[key] = t["overall"]
            if t["top_columns"]:
                table.setdefault("outputs", {})[key] = t["top_columns"][0]
        overall = max(
            [table["overall"]] + list(out_scores.values())
        )
        table["overall"] = round(float(overall), 4)
        self._export(name, table, out_scores)
        self._check_alert(name, table, view)
        with self._mu:
            st = self._models.get(name)
            if st is not None:
                st.last_table = table
                st.last_out = out_scores
        return table

    @staticmethod
    def _fleet_active() -> bool:
        """Whether the pod drift merge applies right now: multi-process
        topology, `drift_fleet_merge` on, seam importable."""
        try:
            from ..parallel.context import process_topology
            from ..telemetry import fleet

            return (
                process_topology()[0] > 1 and fleet.fleet_drift_enabled()
            )
        except Exception:
            return False

    def _pod_view(
        self,
        name: str,
        closed: Optional[BaselineBuilder],
        vb: Optional[BaselineBuilder],
        columns: List[str],
    ) -> Optional[Fingerprint]:
        """The pod-wide scoring view: publish this rank's just-closed
        window blob (non-collective — idle peers owe nothing), drain
        peers' latest blobs, and merge local + peers in ASCENDING rank
        order (the deterministic fold every reduction here uses; the
        SRSK wire merge is exact, so the pod view over split traffic
        equals one process folding the combined rows).  Returns None
        when nothing merged — the caller keeps the local view.  Never
        raises into the serving path."""
        try:
            from ..parallel.context import process_topology
            from ..telemetry import fleet

            if closed is not None and closed.n > 0:
                fleet.publish_drift_window(
                    name, builder_to_bytes(closed)
                )
            me = process_topology()[1]
            ranked: Dict[int, Optional[BaselineBuilder]] = {me: vb}
            for r, blob in fleet.fetch_peer_drift_windows(name).items():
                try:
                    ranked[int(r)] = builder_from_bytes(blob)
                except Exception:
                    continue  # one bad blob must not drop the rest
            merged: Optional[BaselineBuilder] = None
            for r in sorted(ranked):
                b = ranked[r]
                if b is None or b.n == 0:
                    continue
                try:
                    merged = b if merged is None else merged.merge(b)
                except Exception:
                    continue  # geometry drift on one peer: keep the rest
            if merged is None:
                return None
            return merged.finalize(columns)
        except Exception:
            return None

    def _export(
        self, name: str, table: Dict[str, Any],
        out_scores: Dict[str, float],
    ) -> None:
        """Publish `drift_score{model,column,stat}` for the top-k
        columns (+ per-output overalls + the `_overall` alert score) and
        REMOVE series for columns that left the top-k — the family's
        live cardinality stays bounded by k x stats per model."""
        fresh: Set[Tuple[str, str]] = set()
        for entry in table["top_columns"]:
            col = str(entry["column"])
            for stat in STAT_NAMES:
                DRIFT_SCORE.set(
                    entry[stat], model=name, column=col, stat=stat
                )
                fresh.add((col, stat))
        for key, score in out_scores.items():
            DRIFT_SCORE.set(
                score, model=name, column=f"out:{key}", stat="score"
            )
            fresh.add((f"out:{key}", "score"))
        DRIFT_SCORE.set(
            table["overall"], model=name, column="_overall", stat="score"
        )
        fresh.add(("_overall", "score"))
        with self._mu:
            st = self._models.get(name)
            if st is None:
                stale = fresh = set()
            else:
                stale, st.exported = st.exported, fresh
        self._prune(name, stale, fresh)

    def _prune(
        self, name: str, stale: Set[Tuple[str, str]],
        fresh: Set[Tuple[str, str]],
    ) -> None:
        for col, stat in stale - fresh:
            DRIFT_SCORE.remove(model=name, column=col, stat=stat)

    def _check_alert(
        self, name: str, table: Dict[str, Any], view: Fingerprint
    ) -> None:
        threshold = float(get_config("drift_alert_threshold"))
        if threshold <= 0:
            return
        sustain = max(float(get_config("drift_alert_sustain_s")), 0.0)
        now = time.monotonic()
        fire = False
        with self._mu:
            st = self._models.get(name)
            if st is None:
                return
            if table["overall"] < threshold:
                st.above_since = None
                return
            if st.above_since is None:
                st.above_since = now
            if now - st.above_since >= sustain:
                fire = True
                st.above_since = None  # re-arm; the recorder cooldown
                st.alerts += 1         # absorbs a persisting breach
            baseline = st.baseline
            alerts = st.alerts
        if not fire:
            return
        from ..telemetry.flight_recorder import note_failure
        from ..tracing import event

        detail = (
            f"model={name} overall={table['overall']} "
            f"threshold={threshold} sustain_s={sustain} "
            f"window_rows={table['window_rows']}"
        )
        event(f"drift_alert[{name}]", detail=detail, log=logger)
        # pod mode: ONE bundle per pod incident, not one per rank — the
        # merged view crossed the threshold everywhere, so only rank 0
        # dumps, under a deterministic incident id any rank could mint
        incident_id = ""
        if self._fleet_active():
            try:
                from ..parallel.context import process_topology
                from ..resilience.pod import generation
                from ..telemetry import fleet

                if process_topology()[1] != 0:
                    return
                incident_id = fleet.mint_incident_id(
                    "drift", f"{name}/{alerts}", generation=generation()
                )
                detail += f" incident={incident_id}"
            except Exception:
                incident_id = ""
        note_failure(
            "drift",
            detail=detail,
            log=logger,
            incident_id=incident_id,
            attachments={
                "drift": {
                    "model": name,
                    "threshold": threshold,
                    "sustain_s": sustain,
                    "divergence": table,
                    "baseline": baseline.summary(),
                    "window": view.summary(),
                },
                "baseline_fingerprint.bin": baseline.to_bytes(),
                "window_fingerprint.bin": view.to_bytes(),
            },
        )

    # -- reporting -----------------------------------------------------------

    def summary(self, name: str) -> Optional[Dict[str, Any]]:
        """The per-model drift summary `server.report()` and the
        `GET /v1/models/<name>` detail embed (last computed table +
        observation counters; None for untracked models)."""
        with self._mu:
            st = self._models.get(name)
            if st is None:
                return None
            out: Dict[str, Any] = {
                "baseline_rows": st.baseline.n,
                "rows_observed": st.rows,
                "alerts": st.alerts,
            }
            if st.last_table is not None:
                out["overall"] = st.last_table["overall"]
                out["window_rows"] = st.last_table["window_rows"]
                out["top_columns"] = st.last_table["top_columns"]
                if st.last_out:
                    out["output_scores"] = {
                        k: round(float(v), 4)
                        for k, v in st.last_out.items()
                    }
            return out


# the process-global monitor the serving layer feeds
MONITOR = DriftMonitor()

__all__ = [
    "DriftMonitor",
    "MONITOR",
    "DRIFT_ROWS",
    "DRIFT_SCORE",
    "DRIFT_SCORE_PARTIAL",
]
