#
# Failure flight recorder — the always-on black box.  Per-fit reports
# (`telemetry_dir`) only exist for fits the operator instrumented ahead
# of time; when an UN-instrumented fit dies, the evidence dies with it.
# The recorder closes that gap: a bounded ring of recent trace events
# (fed by a tracing tap — every span and instant marker, regardless of
# thread), plus rate-limited metric deltas, all O(1) memory.  The typed
# failure paths the resilience layer can classify —
#
#   retry exhaustion      resilience/retry.py `retry_call` (and the
#                         serving dispatcher's inline per-request
#                         budget, serving/server.py)
#   DispatchTimeout       resilience/guard.py watchdog expiry
#   device-loss recovery  resilience/elastic.py `recover_from_device_loss`
#   sustained overload    serving/server.py admission control
#
# — call `note_failure(reason, ...)`, which writes a post-mortem BUNDLE
# (rate-limited per reason) to `flight_recorder_dir` (default:
# `telemetry_dir`):
#
#   manifest.json   reason/detail/time/pid, the run ids seen in the
#                   window, the live solver gauges (which iteration each
#                   in-flight solver had reached), recent metric deltas
#   trace.json      Chrome trace of the last `flight_recorder_window_s`
#                   seconds of ring events — loads in Perfetto next to
#                   any per-fit trace (absolute timestamps align)
#   metrics.prom    full Prometheus snapshot, exemplars included
#   config.json     the effective value of every conf key
#
# Recording must stay cheap enough to leave on under serving traffic:
# one deque append per event plus a 5-second-rate-limited registry
# snapshot; `measure_overhead()` reports the per-event cost.
#
from __future__ import annotations

import collections
import json
import os

from .locks import named_lock
import time
from typing import Any, Deque, Dict, List, Optional

from .registry import REGISTRY, counter, delta

POSTMORTEMS = counter(
    "postmortems_total", "Flight-recorder post-mortem bundles by reason"
)

# seconds between metric-delta snapshots appended to the delta ring
_DELTA_INTERVAL_S = 5.0
# retained metric-delta entries (bounded like the event ring)
_MAX_DELTAS = 64
# conf re-read cadence: the enabled flag / capacity are re-checked every
# this many record() calls so toggling `flight_recorder` takes effect
# without a per-event config-lock acquisition
_CONF_REFRESH_EVENTS = 256
# per-reason dump cooldown: a failure storm (every queued request timing
# out at once) writes ONE bundle, not hundreds
_DUMP_COOLDOWN_S = 30.0


class FlightRecorder:
    """The process-global ring + dump machinery.  Thread-safe; installed
    onto the tracing tap at telemetry import (`install()`)."""

    def __init__(self) -> None:
        # REENTRANT: the tracing tap re-enters record() when the
        # slow-wait instrumentation (telemetry/locks.py) emits an
        # event while this very lock is held — a plain Lock here
        # self-deadlocks the whole trace-emission path
        self._lock = named_lock("flight_recorder", kind="rlock")
        self._ring: Optional[Deque[Any]] = None  # built lazily from conf
        self._deltas: Deque[Dict[str, Any]] = collections.deque(
            maxlen=_MAX_DELTAS
        )
        self._last_snap: Dict[str, Dict[str, Any]] = {}
        self._last_snap_t = 0.0
        self._enabled = True
        self._conf_countdown = 0
        self._last_dump: Dict[str, float] = {}  # reason -> monotonic t
        # pod incidents already dumped by THIS process: one pod-scale
        # event (rank loss detected, then its reduce timing out, then
        # the retry failing) must write one bundle here, not one per
        # typed failure path it cascades through
        self._seen_incidents: Dict[str, float] = {}
        self.cooldown_s = _DUMP_COOLDOWN_S

    # -- recording (the hot path) -------------------------------------------

    def _refresh_conf_locked(self) -> None:
        from ..config import get_config

        self._enabled = str(get_config("flight_recorder")).lower() != "off"
        cap = max(64, int(get_config("flight_recorder_events")))
        if self._ring is None or self._ring.maxlen != cap:
            self._ring = collections.deque(
                self._ring or (), maxlen=cap
            )
        self._conf_countdown = _CONF_REFRESH_EVENTS

    def record(self, event: Any) -> None:
        """Tracing-tap entry point: retain one TraceEvent.  O(1) — a
        deque append; every `_DELTA_INTERVAL_S` it also snapshots the
        registry and keeps the delta (what moved since the last one)."""
        with self._lock:
            if self._conf_countdown <= 0:
                self._refresh_conf_locked()
            self._conf_countdown -= 1
            if not self._enabled:
                return
            self._ring.append(event)
            now = time.time()
            take_snap = now - self._last_snap_t >= _DELTA_INTERVAL_S
            if take_snap:
                self._last_snap_t = now
        if not take_snap:
            return
        # the snapshot walks every registry family: done OUTSIDE the
        # recorder lock so concurrent record() calls never queue on it
        snap = REGISTRY.snapshot()
        with self._lock:
            if self._last_snap:
                d = delta(self._last_snap, snap)
                if d:
                    self._deltas.append({"t": round(now, 3), "delta": d})
            self._last_snap = snap

    # -- queries -------------------------------------------------------------

    def events(self, window_s: Optional[float] = None) -> List[Any]:
        """The retained events, oldest first; `window_s` keeps only the
        last that-many seconds (by span END time, so a long span still
        in its window survives)."""
        with self._lock:
            evs = list(self._ring or ())
        if window_s is not None:
            cutoff = time.time() - float(window_s)
            evs = [e for e in evs if max(e.t0, e.t1) >= cutoff]
        return evs

    def metric_deltas(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(d) for d in self._deltas]

    def clear(self) -> None:
        """Tests / operator reset: drop the retained history (the
        registry itself is untouched)."""
        with self._lock:
            if self._ring is not None:
                self._ring.clear()
            self._deltas.clear()
            self._last_snap = {}
            self._last_snap_t = 0.0
            self._last_dump.clear()
            self._seen_incidents.clear()

    # -- dumping -------------------------------------------------------------

    def _bundle_dir(self) -> str:
        from ..config import get_config

        return str(
            get_config("flight_recorder_dir")
            or get_config("telemetry_dir")
            or ""
        )

    def note_failure(
        self, reason: str, detail: str = "",
        log: Optional[object] = None,
        attachments: Optional[Dict[str, Any]] = None,
        incident_id: str = "",
    ) -> Optional[str]:
        """A typed failure path fired: write a post-mortem bundle
        (rate-limited — one per `reason` per cooldown window) and return
        its directory, or None when skipped (cooldown, recorder off, no
        destination configured).  `attachments` adds caller evidence to
        the bundle (the drift monitor ships both distribution
        fingerprints + the divergence table): `bytes` values write
        verbatim under their key, anything else as `<key>.json`.
        `incident_id` marks a pod-scale event (telemetry/fleet.py mints
        one deterministic id per incident): it lands in the manifest so
        fleet aggregation can group the pod's bundles per incident, and
        this process dedupes on it — the same incident cascading
        through several typed failure paths writes ONE bundle.  NEVER
        raises: the black box must not add a second failure to the one
        being recorded."""
        prev = None
        claimed = False
        inc_claimed = False
        try:
            with self._lock:
                if self._conf_countdown <= 0:
                    self._refresh_conf_locked()
                if not self._enabled:
                    return None
                now = time.monotonic()
                if incident_id and incident_id in self._seen_incidents:
                    return None
                prev = self._last_dump.get(reason)
                if prev is not None and now - prev < self.cooldown_s:
                    return None
                # claim the cooldown slot BEFORE the (unlocked) dump so
                # a concurrent storm writes one bundle, not N...
                self._last_dump[reason] = now
                claimed = True
                if incident_id:
                    self._seen_incidents[incident_id] = now
                    inc_claimed = True
            bdir = self.dump(reason, detail, log=log,
                             attachments=attachments,
                             incident_id=incident_id)
            if bdir is None:
                # ...but a dump that wrote NOTHING (no destination
                # configured yet) must not burn the slot: the operator
                # who sets flight_recorder_dir after the first failure
                # still gets a bundle from the next one
                with self._lock:
                    if claimed:
                        if prev is None:
                            self._last_dump.pop(reason, None)
                        else:
                            self._last_dump[reason] = prev
                    if inc_claimed:
                        self._seen_incidents.pop(incident_id, None)
            return bdir
        except Exception as e:  # pragma: no cover - defensive
            with self._lock:
                if claimed:
                    if prev is None:
                        self._last_dump.pop(reason, None)
                    else:
                        self._last_dump[reason] = prev
                if inc_claimed:
                    self._seen_incidents.pop(incident_id, None)
            _warn(log, f"flight-recorder dump failed "
                       f"({type(e).__name__}: {e})")
            return None

    def dump(
        self, reason: str, detail: str = "",
        log: Optional[object] = None,
        attachments: Optional[Dict[str, Any]] = None,
        incident_id: str = "",
    ) -> Optional[str]:
        """Write the bundle unconditionally (no cooldown — operator/test
        entry point).  Returns the bundle directory, or None when no
        destination is configured."""
        from ..config import config_snapshot, get_config

        base = self._bundle_dir()
        if not base:
            _warn(
                log,
                f"flight recorder has a '{reason}' post-mortem to write "
                "but neither flight_recorder_dir nor telemetry_dir is "
                "set; the in-memory ring stays queryable",
            )
            return None
        window_s = float(get_config("flight_recorder_window_s"))
        evs = self.events(window_s=window_s)
        stamp = time.strftime("%Y%m%d_%H%M%S")
        bdir = os.path.join(
            base, f"postmortem_{reason}_{stamp}_{os.getpid()}"
        )
        n = 0
        while os.path.exists(bdir):  # same reason+second: suffix
            n += 1
            bdir = os.path.join(
                base, f"postmortem_{reason}_{stamp}_{os.getpid()}.{n}"
            )
        os.makedirs(bdir)
        from .exporters import chrome_trace, dump_prometheus

        with open(os.path.join(bdir, "trace.json"), "w") as f:
            json.dump(chrome_trace(events=evs), f)
        with open(os.path.join(bdir, "metrics.prom"), "w") as f:
            f.write(dump_prometheus(exemplars=True))
        with open(os.path.join(bdir, "config.json"), "w") as f:
            json.dump(config_snapshot(), f, indent=1, default=str)
        attached = []
        for key in sorted(attachments or {}):
            val = (attachments or {})[key]
            if isinstance(val, (bytes, bytearray)):
                fname = key
                with open(os.path.join(bdir, fname), "wb") as f:
                    f.write(val)
            else:
                fname = f"{key}.json"
                with open(os.path.join(bdir, fname), "w") as f:
                    json.dump(val, f, indent=1, default=str)
            attached.append(fname)
        manifest = {
            "reason": reason,
            "detail": detail,
            "t": round(time.time(), 3),
            "pid": os.getpid(),
            "window_s": window_s,
            "n_events": len(evs),
            "run_ids": sorted({e.run_id for e in evs if e.run_id}),
            "solver_state": _solver_state(),
            "metric_deltas": self.metric_deltas(),
            **({"incident_id": incident_id} if incident_id else {}),
            **({"attachments": attached} if attached else {}),
        }
        with open(os.path.join(bdir, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=1)
        POSTMORTEMS.inc(reason=reason)
        _warn(
            log,
            f"flight recorder: '{reason}' post-mortem bundle written to "
            f"{bdir} ({len(evs)} event(s), "
            f"{len(manifest['run_ids'])} run(s))",
        )
        return bdir


def _solver_state() -> Dict[str, Any]:
    """The live solver-progress gauges at dump time: which iteration
    each still-open solver loop had reached (a COMPLETED fit's heartbeat
    closed and removed its series — see Heartbeat.close)."""
    out: Dict[str, Any] = {}
    for fam in ("solver_iteration", "solver_loss"):
        m = REGISTRY.get(fam)
        if m is None:
            continue
        out[fam] = {
            ",".join(f"{k}={v}" for k, v in lk): val
            for lk, val in m.samples().items()
        }
    return out


def _warn(log: Optional[object], msg: str) -> None:
    if log is None:
        from ..utils import get_logger

        log = get_logger("spark_rapids_ml_tpu.telemetry")
    log.warning(msg)


# the process-global recorder every failure hook talks to
RECORDER = FlightRecorder()

_installed = False
_install_lock = named_lock("flight_recorder_install")


def install() -> FlightRecorder:
    """Hook the recorder onto the tracing tap (idempotent).  Called at
    telemetry import, so the ring is recording before the first fit."""
    global _installed
    with _install_lock:
        if not _installed:
            from ..tracing import add_trace_tap

            add_trace_tap(RECORDER.record)
            _installed = True
    return RECORDER


def note_failure(
    reason: str, detail: str = "", log: Optional[object] = None,
    attachments: Optional[Dict[str, Any]] = None,
    incident_id: str = "",
) -> Optional[str]:
    """Module-level convenience over `RECORDER.note_failure` — the one
    call the failure hooks (retry exhaustion, DispatchTimeout,
    device-loss recovery, sustained overload, sustained drift, pod rank
    loss) make."""
    return RECORDER.note_failure(reason, detail, log=log,
                                 attachments=attachments,
                                 incident_id=incident_id)


def measure_overhead(n: int = 2000) -> float:
    """Measured per-event recording cost in MICROSECONDS: pushes `n`
    synthetic events through a THROWAWAY FlightRecorder (same code
    path, same conf reads) and returns the mean, so 'request tracing
    ON' stays an accounted cost, not an article of faith.  The live
    RECORDER ring is untouched — flooding the real black box with 2000
    probe events would evict exactly the recent history a post-mortem
    exists to keep."""
    from ..tracing import TraceEvent

    now = time.time()
    ev = TraceEvent(
        "flight_recorder_probe", 0.0, 0, t0=now, t1=now, kind="instant"
    )
    probe = FlightRecorder()
    t0 = time.perf_counter()
    for _ in range(n):
        probe.record(ev)
    return (time.perf_counter() - t0) / n * 1e6


__all__ = [
    "FlightRecorder",
    "RECORDER",
    "install",
    "measure_overhead",
    "note_failure",
]
