#
# HBM / device-memory telemetry — the measurement half of the byte model.
# Every staging decision in this repo runs on PREDICTED bytes (the
# `_over_device_budget` formula in core.py, the device cache's n_dev+2
# gather reservations, the streaming chunk sizing), and until this module
# nothing ever checked the predictions against the chips: the gather
# factors and reservation math were faith-based.  Snap ML's wins are
# attributed through exact per-phase accounting of accelerator memory and
# the DuHL out-of-core scheme only holds together because HBM occupancy
# is measured, not assumed (PAPERS.md) — this is that layer:
#
#   providers   where the bytes come from.  `RealMemoryProvider` reads
#               `device.memory_stats()` (TPU/GPU runtimes report
#               bytes_in_use / peak_bytes_in_use); backends without it
#               (this CPU container) degrade to the DETERMINISTIC
#               `SimulatedMemoryProvider`, which censuses
#               `jax.live_arrays()` per device — so tests and
#               fault-injection runs exercise the full sampling path
#               with real numbers instead of a stubbed no-op.
#   gauges      `device_bytes_in_use{device=}` / `device_bytes_peak{device=}`
#               in the metrics registry on every sample.
#   watermarks  `FitMemoryWatermark` — opened per fit by
#               `FitTelemetry` (report.py): tracks the per-device PEAK
#               over the fit's samples and collects the byte-model
#               predictions recorded during the fit.
#   drift       `budget_drift_ratio{est=}` = measured GROWTH (peak minus
#               the fit-start baseline — residency predating the fit is
#               subtracted out) / predicted bytes, per prediction label
#               — in the registry and the per-fit report, so a
#               reservation factor that overshoots (ratio << 1) or a
#               byte model that lies (ratio >> 1) is a number on a
#               dashboard, not an OOM postmortem.
#
# Sampling points: watermark open/close, after every `RowStager.stage`,
# each solver heartbeat (rate-limited), and — when the
# `memory_sample_interval_s` conf is > 0 — a background daemon thread
# while at least one fit is active.
#
# Like the rest of telemetry/, no jax import at module scope: reading a
# gauge must not pay the accelerator import.  jax loads lazily on the
# first sample (by which point the caller has imported it anyway).
#
from __future__ import annotations

import threading

from .locks import named_lock
import time
from typing import Any, Dict, Optional

from .registry import counter, gauge

_in_use_g = gauge(
    "device_bytes_in_use", "Last sampled live bytes per device"
)
_peak_g = gauge(
    "device_bytes_peak", "Process-lifetime peak sampled bytes per device"
)
_drift_g = gauge(
    "budget_drift_ratio",
    "Measured peak bytes / predicted bytes per estimate label",
)
_pred_g = gauge(
    "budget_predicted_bytes", "Last predicted bytes per estimate label"
)
_decisions_c = counter(
    "budget_decisions_total",
    "Byte-model budget decisions by label and outcome",
)
_samples_c = counter(
    "memory_samples_total", "Device memory samples taken, by provider"
)

_lock = named_lock("memory_telemetry")
# run_id -> FitMemoryWatermark for every fit currently inside its span
_active: Dict[str, "FitMemoryWatermark"] = {}
# process-lifetime peaks the _peak_g gauge mirrors (provider peaks reset
# with the provider; these survive a provider swap)
_process_peak: Dict[str, int] = {}
_last_sample_t = 0.0

_provider: Optional["MemoryProvider"] = None
_sampler_thread: Optional[threading.Thread] = None


# ---------------------------------------------------------------------------
# Providers
# ---------------------------------------------------------------------------


class MemoryProvider:
    """One way of answering "how many bytes does each device hold".
    `sample()` returns {device_id: {"bytes_in_use": int,
    "peak_bytes_in_use": int}} for every active device it can answer
    for (missing devices simply don't appear)."""

    name = "none"

    def sample(self) -> Dict[int, Dict[str, int]]:  # pragma: no cover
        raise NotImplementedError


class RealMemoryProvider(MemoryProvider):
    """`device.memory_stats()` — the TPU/GPU runtime's own allocator
    counters.  Devices whose backend lacks the call (CPU) are skipped;
    `available()` says whether ANY active device reports stats."""

    name = "real"

    @staticmethod
    def available() -> bool:
        from ..parallel.mesh import active_devices

        for d in active_devices():
            try:
                if d.memory_stats() is not None:
                    return True
            except Exception:
                continue
        return False

    def sample(self) -> Dict[int, Dict[str, int]]:
        from ..parallel.mesh import active_devices

        out: Dict[int, Dict[str, int]] = {}
        for d in active_devices():
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            out[int(d.id)] = {
                "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(
                    stats.get("peak_bytes_in_use",
                              stats.get("bytes_in_use", 0))
                ),
            }
        return out


class SimulatedMemoryProvider(MemoryProvider):
    """Deterministic provider for backends without allocator counters
    (the CPU test mesh): live bytes are censused from
    `jax.live_arrays()` — each array's addressable shards attribute
    their exact nbytes to the device holding them — and the peak is the
    running max this provider has observed.  Deterministic given the
    same program, so tests can assert exact byte accounting, and the
    whole sampling/watermark/drift path runs in CPU CI instead of
    no-oping."""

    name = "simulated"

    def __init__(self) -> None:
        self._peaks: Dict[int, int] = {}

    def sample(self) -> Dict[int, Dict[str, int]]:
        import jax

        from ..parallel.mesh import active_devices

        # every active device answers, at 0 when nothing lives on it —
        # otherwise a device whose arrays all freed would keep its stale
        # last gauge value forever
        live: Dict[int, int] = {int(d.id): 0 for d in active_devices()}
        # a buffer counts once: `addressable_shards` caches, on the array,
        # single-device arrays over the SAME buffers, and those are live
        # arrays themselves from the next census on
        seen = set()
        for arr in jax.live_arrays():
            try:
                if getattr(arr, "is_deleted", None) and arr.is_deleted():
                    continue
                for sh in arr.addressable_shards:
                    did = int(sh.device.id)
                    buf = (did, sh.data.unsafe_buffer_pointer())
                    if buf in seen:
                        continue
                    seen.add(buf)
                    live[did] = live.get(did, 0) + int(sh.data.nbytes)
            except Exception:
                continue  # a mid-donation array can vanish underneath us
        out: Dict[int, Dict[str, int]] = {}
        for did, b in live.items():
            peak = max(self._peaks.get(did, 0), b)
            self._peaks[did] = peak
            out[did] = {"bytes_in_use": b, "peak_bytes_in_use": peak}
        return out


def get_provider() -> Optional[MemoryProvider]:
    """The provider the `memory_provider` conf selects — resolved once
    and cached (`reset_memory_telemetry()` re-resolves):
    "auto" = real where any device reports `memory_stats()`, else
    simulated — except on a TPU backend, where a device without
    `memory_stats()` raises; "real" / "simulated" force one; "off"
    disables sampling entirely."""
    global _provider
    with _lock:
        if _provider is not None:
            return _provider if _provider.name != "none" else None
    from ..config import get_config

    mode = str(get_config("memory_provider") or "auto").lower()
    if mode == "off":
        prov: MemoryProvider = MemoryProvider()  # name="none" sentinel
    elif mode == "real":
        prov = RealMemoryProvider()
    elif mode == "simulated":
        prov = SimulatedMemoryProvider()
    elif RealMemoryProvider.available():
        prov = RealMemoryProvider()
    else:
        from ..parallel.mesh import active_devices

        if any(d.platform == "tpu" for d in active_devices()):
            # the census under the same gauge names would pass for the
            # allocator's numbers exactly where they matter
            raise RuntimeError(
                "memory_provider=auto: a TPU device reports no "
                "memory_stats(); set memory_provider=simulated or off "
                "to run without the allocator's counters"
            )
        prov = SimulatedMemoryProvider()
    with _lock:
        _provider = prov
    return prov if prov.name != "none" else None


def reset_memory_telemetry() -> None:
    """Drop the cached provider and process peaks (tests; after flipping
    the `memory_provider` conf)."""
    global _provider, _last_sample_t
    with _lock:
        _provider = None
        _process_peak.clear()
        _last_sample_t = 0.0


# ---------------------------------------------------------------------------
# Sampling
# ---------------------------------------------------------------------------


def sample_devices() -> Dict[int, int]:
    """Take one sample: update the registry gauges, feed every active
    fit watermark, and return {device_id: bytes_in_use}.  Returns {} (and
    touches nothing) when the provider is off.  A failed sample never
    raises — memory observability must not fail the work it observes."""
    global _last_sample_t
    # resolved outside the guard: a provider that cannot be resolved (a
    # TPU without allocator counters under "auto") is a configuration
    # error the fit must see, not a failed sample
    prov = get_provider()
    if prov is None:
        return {}
    try:
        stats = prov.sample()
    except Exception:
        return {}
    now = time.time()
    with _lock:
        _last_sample_t = now
        watermarks = list(_active.values())
    out: Dict[int, int] = {}
    for did, s in stats.items():
        key = str(did)
        out[did] = s["bytes_in_use"]
        _in_use_g.set(s["bytes_in_use"], device=key)
        # read-max-write under the lock: the daemon sampler and explicit
        # sample points race here, and a lost update would let a peak
        # regress.  The gauge set stays inside too — otherwise a stale
        # peak computed before losing the race could overwrite a newer
        # one on the exported family
        with _lock:
            peak = max(
                _process_peak.get(key, 0),
                s["peak_bytes_in_use"],
                s["bytes_in_use"],
            )
            _process_peak[key] = peak
            _peak_g.set(peak, device=key)
    _samples_c.inc(provider=prov.name)
    for wm in watermarks:
        wm._observe(stats)
    return out


def maybe_sample(min_interval_s: float = 1.0) -> None:
    """Rate-limited `sample_devices` for hot callers (solver heartbeats):
    samples only when the last sample is older than `min_interval_s`
    (or the `memory_sample_interval_s` conf when larger)."""
    from ..config import get_config

    try:
        conf = float(get_config("memory_sample_interval_s") or 0.0)
    except Exception:
        conf = 0.0
    spacing = max(min_interval_s, conf)
    with _lock:
        due = (time.time() - _last_sample_t) >= spacing
    if due:
        sample_devices()


def _sampler_loop() -> None:
    """Background sampling while >= 1 fit is active
    (`memory_sample_interval_s` > 0).  Exits when the last watermark
    closes; the next fit starts a fresh thread."""
    from ..config import get_config

    while True:
        try:
            interval = float(get_config("memory_sample_interval_s") or 0.0)
        except Exception:
            interval = 0.0
        with _lock:
            if interval <= 0 or not _active:
                global _sampler_thread
                _sampler_thread = None
                return
        sample_devices()
        time.sleep(interval)


def _maybe_start_sampler() -> None:
    global _sampler_thread
    from ..config import get_config

    try:
        interval = float(get_config("memory_sample_interval_s") or 0.0)
    except Exception:
        interval = 0.0
    if interval <= 0:
        return
    with _lock:
        if _sampler_thread is not None and _sampler_thread.is_alive():
            return
        t = threading.Thread(
            target=_sampler_loop, name="memory-sampler", daemon=True
        )
        _sampler_thread = t
    t.start()


# ---------------------------------------------------------------------------
# Predictions (the byte model's side of the drift ratio)
# ---------------------------------------------------------------------------


def record_prediction(label: str, nbytes: float) -> None:
    """Record one byte-model prediction (a staging's padded-byte
    estimate, a cache reservation, a budget-decision operand).  Lands on
    the `budget_predicted_bytes{est=}` gauge and on every watermark whose
    run is active on this thread (workers adopt the caller's run id), so
    the fit that made the prediction owns its drift ratio."""
    nbytes = float(nbytes)
    if nbytes <= 0:
        return
    _pred_g.set(nbytes, est=label)
    for wm in _watermarks_of_this_run():
        wm._predict(label, nbytes)


def _watermarks_of_this_run() -> list:
    from ..tracing import current_run_id

    rid = current_run_id()
    if not rid:
        # no run on this thread -> no watermark owns the event; a
        # broadcast to every active fit would cross-contaminate reports
        return []
    with _lock:
        return [w for r, w in _active.items() if r == rid]


def note_host_staging() -> None:
    """A staging from host memory begins in this thread's run: its fit
    reads the host's `MemAvailable` now, if it has not yet, and once
    more when it ends.  Fits that stage nothing from the host read
    nothing: one read of /proc/meminfo costs 0.3 ms on a one-chip v5e
    host and 3 ms on a four-chip one (PERF.md, PR 30)."""
    for wm in _watermarks_of_this_run():
        if "start" not in wm.host_available:
            wm.host_available["start"] = host_available_bytes()


def record_budget_decision(label: str, need_bytes: float, over: bool) -> None:
    """One `_over_device_budget`-style decision: the predicted bytes it
    ran on plus the outcome, counted per label so the streamed-stats
    routing rate is visible next to the drift its estimates carry."""
    _decisions_c.inc(label=label, over=str(bool(over)).lower())
    record_prediction(label, need_bytes)


def note_measured_drift(
    label: str, predicted_bytes: float, baseline_bytes: float = 0.0
) -> Optional[float]:
    """Immediate point-in-time drift for a prediction that just became
    real (a device-cache insert: reservation vs the bytes the staging
    actually added): samples now, sets `budget_drift_ratio{est=label}`
    to (measured total - `baseline_bytes`) / predicted, and returns the
    ratio (None when the provider is off or the prediction is empty).
    Pass the PRE-action total as `baseline_bytes` so unrelated residency
    (other cache entries, a concurrent fit's arrays) doesn't inflate the
    ratio into measuring occupancy instead of model error."""
    predicted_bytes = float(predicted_bytes)
    if predicted_bytes <= 0:
        return None
    measured = sample_devices()
    if not measured:
        return None
    grew = max(sum(measured.values()) - float(baseline_bytes), 0.0)
    ratio = round(grew / predicted_bytes, 4)
    _drift_g.set(ratio, est=label)
    return ratio


# ---------------------------------------------------------------------------
# Per-fit watermark
# ---------------------------------------------------------------------------


def host_available_bytes() -> Optional[int]:
    """The host's `MemAvailable` (/proc/meminfo), None where the kernel
    gives none.  Memory a fit leaves in use OUTSIDE the process (pages a
    driver keeps) shows here and not in the process's own counters."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError):
        pass
    return None


class FitMemoryWatermark:
    """Peak-byte watermark for one fit: opened/closed by `FitTelemetry`
    around the fit span.  Collects the per-device peak over every sample
    taken during the fit plus the byte-model predictions recorded inside
    it, and renders the report's `memory` section — per-device peaks and
    one `budget_drift_ratio` per prediction label (measured peak total /
    predicted bytes), also set on the registry's
    `budget_drift_ratio{est=}` gauge."""

    def __init__(self, run_id: str, estimator: str = "") -> None:
        self.run_id = run_id
        self.estimator = estimator
        self.peaks: Dict[int, int] = {}
        # per-device bytes at this fit's FIRST sample: the drift ratio
        # measures the fit's GROWTH over this baseline, so residency that
        # predates the fit (cache entries, another fit's arrays) doesn't
        # inflate it into an occupancy number
        self.start: Dict[int, int] = {}
        # label -> LARGEST prediction recorded under it during this fit
        # (a re-staging after device loss predicts again; max — not sum —
        # keeps the ratio comparable to a peak)
        self.predictions: Dict[str, float] = {}
        self._samples = 0
        # the host's MemAvailable when the fit's first staging from host
        # memory began (`note_host_staging`) and when the fit ended
        self.host_available: Dict[str, Optional[int]] = {}

    # -- lifecycle (FitTelemetry) -------------------------------------------

    def open(self) -> None:
        with _lock:
            _active[self.run_id] = self
        sample_devices()
        _maybe_start_sampler()

    def close(self) -> None:
        sample_devices()
        if "start" in self.host_available:
            self.host_available["end"] = host_available_bytes()
        with _lock:
            _active.pop(self.run_id, None)

    # -- feed ---------------------------------------------------------------

    def _observe(self, stats: Dict[int, Dict[str, int]]) -> None:
        self._samples += 1
        for did, s in stats.items():
            b = max(s["bytes_in_use"], 0)
            self.start.setdefault(did, b)
            if b > self.peaks.get(did, 0):
                self.peaks[did] = b

    def _predict(self, label: str, nbytes: float) -> None:
        if nbytes > self.predictions.get(label, 0.0):
            self.predictions[label] = nbytes

    # -- output -------------------------------------------------------------

    def grew_bytes(self) -> int:
        """How many bytes this fit ADDED at its peak: peak total minus
        the fit-start baseline (floored at 0 — frees during the fit can
        push the total below where it started)."""
        peak_total = sum(self.peaks.values())
        start_total = sum(self.start.get(d, 0) for d in self.peaks)
        return max(peak_total - start_total, 0)

    def drift_ratios(self) -> Dict[str, float]:
        """Measured growth / predicted bytes, per prediction label — the
        byte-model error, not process occupancy: residency that predates
        the fit is subtracted out via the start baseline."""
        grew = float(self.grew_bytes())
        out: Dict[str, float] = {}
        if self._samples == 0:
            return out
        for label, pred in self.predictions.items():
            if pred > 0:
                out[label] = round(grew / pred, 4)
        return out

    def section(self) -> Dict[str, Any]:
        """The fit report's `memory` section ({} when sampling is off —
        the report then simply omits it)."""
        if not self.peaks and not self.predictions:
            return {}
        prov = None
        with _lock:
            if _provider is not None and _provider.name != "none":
                prov = _provider.name
        sec: Dict[str, Any] = {
            "provider": prov,
            "samples": self._samples,
            "per_device_peak_bytes": {
                str(d): int(b) for d, b in sorted(self.peaks.items())
            },
            "peak_total_bytes": int(sum(self.peaks.values())),
            "start_total_bytes": int(sum(self.start.values())),
            "grew_bytes": int(self.grew_bytes()),
        }
        if self.host_available and None not in self.host_available.values():
            sec["host_available_bytes"] = dict(self.host_available)
        if self.predictions:
            sec["predicted_bytes"] = {
                k: int(v) for k, v in sorted(self.predictions.items())
            }
        drift = self.drift_ratios()
        if drift:
            sec["budget_drift_ratio"] = drift
            label = self.estimator or "fit"
            for est, r in drift.items():
                _drift_g.set(r, est=f"{label}:{est}")
        return sec


__all__ = [
    "FitMemoryWatermark",
    "MemoryProvider",
    "RealMemoryProvider",
    "SimulatedMemoryProvider",
    "get_provider",
    "host_available_bytes",
    "maybe_sample",
    "note_host_staging",
    "note_measured_drift",
    "record_budget_decision",
    "record_prediction",
    "reset_memory_telemetry",
    "sample_devices",
]
