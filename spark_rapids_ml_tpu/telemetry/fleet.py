#
# Pod observatory — the cross-rank half of the telemetry stack.  Every
# observability surface below this module (span trees, the flight
# recorder, drift windows, the utilization timeline) is per-process;
# this module correlates them across the pod:
#
#   pass correlation    rank 0 mints one `pass_id` per accumulate pass
#                       and broadcasts it over the coordination-service
#                       seam (`begin_pod_pass`); every rank's spans,
#                       reduce-wait intervals and pod_recovery events
#                       carry it, so N per-rank traces of one pass can
#                       be joined on a single key
#
#   clock alignment     heartbeat KV values carry the sender's wall
#                       clock; `note_clock_sample` collects
#                       (ts_send, t_recv) pairs and `clock_offsets`
#                       estimates per-peer skew as min(t_recv - ts_send)
#                       — an upper bound on (skew + delivery delay), so
#                       the estimate errs by at most the minimum
#                       delivery delay observed, itself bounded by the
#                       heartbeat probe cadence.  `merge_chrome_traces`
#                       folds per-rank trace dumps into ONE
#                       Perfetto-loadable trace, one track group per
#                       rank, peer timestamps shifted by the estimated
#                       offset (uniform per rank — order within a track
#                       is preserved, so merged tracks stay monotone)
#
#   straggler ledger    at pass complete each rank rides a tiny
#                       per-phase wall-clock blob (decode /
#                       device-accumulate / reduce-wait, from the
#                       utilization timeline clipped to the pass
#                       window) on a `reduce_blob_list` exchange; every
#                       rank computes the SAME critical-path table and
#                       publishes `pod_straggler_seconds{rank,phase}`,
#                       plus a `pass_report` naming the slowest rank
#                       per phase for the fit report
#
#   incident bundles    a pod-scale failure (rank loss, reduce timeout)
#                       mints one DETERMINISTIC incident id per event —
#                       a hash of (reason, generation, token), so every
#                       survivor computes it without communicating —
#                       and `exchange_incident_rings` best-effort pulls
#                       peers' recent flight-recorder rings over the
#                       bounded `pod.kv_wait` (a dead rank's ring is
#                       simply absent, and named as such) into one
#                       merged `pod_trace.json` attachment
#
#   fleet drift         serve-time drift windows publish their closed
#                       builder blobs to per-rank monotonic KV keys
#                       (non-collective — serving traffic is
#                       asymmetric, a blind allgather would hang the
#                       busy rank on the idle one); peers drain each
#                       other's keys with tiny bounded probes and merge
#                       rank-ordered, so `drift_score` reflects
#                       pod-wide traffic while per-host partials stay
#                       visible as `drift_score_partial{model,process}`
#
# Everything here is best-effort observability: no call may take down
# the pass or the recovery path it instruments, so cross-process
# failures degrade to the local view, never raise past this module.
#
from __future__ import annotations

import collections
import hashlib
import json
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

from .locks import named_lock
from .registry import counter, gauge

# one lock for every piece of fleet state below: clock samples, pass
# bookkeeping, drift-window caches.  Never held across a KV wait.
_fleet_lock = named_lock("fleet_state")

# retained (ts_send, t_recv) pairs per peer; minutes of heartbeat
# history at the default 2 s cadence — enough for a stable min
_MAX_CLOCK_SAMPLES = 64

# heartbeat values below this are not wall-clock timestamps (the
# pre-observatory protocol wrote the literal "1"); rejecting them keeps
# a mixed-version pod from poisoning the offset estimate
_MIN_PLAUSIBLE_TS = 1e9

_clock_samples: Dict[int, Deque[Tuple[float, float]]] = {}

# current pass bookkeeping: id + perf_counter/wall start of the window
_pass_state: Dict[str, Any] = {}

# fleet drift exchange state, all under _fleet_lock:
#   _drift_pub_seq[model]        next seq this rank publishes
#   _drift_next_seq[(model, r)]  next seq to probe from peer r
#   _drift_latest[model][r]      latest blob seen from peer r
_drift_pub_seq: Dict[str, int] = {}
_drift_next_seq: Dict[Tuple[str, int], int] = {}
_drift_latest: Dict[str, Dict[int, bytes]] = {}

# bounded per-key probe for peer drift blobs — same "is it there right
# now" shape as the liveness probe, never a real wait
_DRIFT_PROBE_MS = 50

STRAGGLER_SECONDS = gauge(
    "pod_straggler_seconds",
    "Per-rank wall seconds by pass phase from the last pod pass report",
)

POD_INCIDENTS = counter(
    "pod_incidents_total",
    "Pod-scale incidents minted, by reason",
)

# utilization-timeline kinds -> the pass-report phase names the
# straggler table speaks (the ISSUE's decode / device-accumulate /
# reduce-wait vocabulary)
_PHASE_KINDS = {
    "decode": "host_prep",
    "device_accumulate": "device",
    "reduce_wait": "reduce_wait",
}


# ---------------------------------------------------------------------------
# Clock-offset estimation
# ---------------------------------------------------------------------------


def note_clock_sample(rank: int, ts_send: float, t_recv: float) -> None:
    """Record one heartbeat clock observation from `rank`: the wall
    clock the peer wrote into its beat value (`ts_send`) and our wall
    clock when the probe read it (`t_recv`).  Implausible senders
    (legacy beats, zeroed clocks) are dropped.  Cheap; never raises."""
    try:
        ts_send = float(ts_send)
        t_recv = float(t_recv)
    except (TypeError, ValueError):
        return
    if ts_send < _MIN_PLAUSIBLE_TS or t_recv < _MIN_PLAUSIBLE_TS:
        return
    with _fleet_lock:
        dq = _clock_samples.get(int(rank))
        if dq is None:
            dq = _clock_samples[int(rank)] = collections.deque(
                maxlen=_MAX_CLOCK_SAMPLES
            )
        dq.append((ts_send, t_recv))


def clock_offsets() -> Dict[int, Tuple[float, float]]:
    """Per-peer clock offset estimates: rank -> (offset_s, err_s).

    Each sample observes `t_recv - ts_send = skew + delay` where
    `skew = local_clock - peer_clock` and `delay >= 0` is the
    beat-to-probe delivery lag; the minimum over retained samples is
    therefore an UPPER bound on the skew, off by at most the smallest
    delay that occurred.  Delivery lag is bounded by one heartbeat
    probe cadence, so the documented error bar is
    `min(observed spread, heartbeat interval)`.  Adding `offset_s` to a
    peer timestamp maps it onto this process's clock."""
    from ..resilience.pod import heartbeat_interval_s

    hb = heartbeat_interval_s()
    out: Dict[int, Tuple[float, float]] = {}
    with _fleet_lock:
        items = {r: list(dq) for r, dq in _clock_samples.items() if dq}
    for r, samples in items.items():
        diffs = [t_recv - ts_send for ts_send, t_recv in samples]
        lo = min(diffs)
        spread = max(diffs) - lo
        out[r] = (lo, min(spread, hb) if len(diffs) > 1 else hb)
    return out


def merge_chrome_traces(
    traces_by_rank: Dict[int, Dict[str, Any]],
    offsets: Optional[Dict[int, Tuple[float, float]]] = None,
) -> Dict[str, Any]:
    """Fold per-rank Chrome-trace dicts into ONE Perfetto-loadable
    trace: each rank becomes its own track group (`pid` = rank, a
    `process_name` metadata row labels it), and every event from a
    non-reference rank is shifted by that rank's estimated clock
    offset.  The shift is uniform per rank, so event order within a
    track is preserved — merged tracks are monotone wherever the
    per-rank dumps were.  `offsets` defaults to `clock_offsets()`
    (ranks without an estimate merge unshifted); the offsets and their
    error bars land in `otherData` so a reader knows how far to trust
    cross-track alignment."""
    if offsets is None:
        offsets = clock_offsets()
    events: List[Dict[str, Any]] = []
    applied: Dict[str, List[float]] = {}
    for rank in sorted(traces_by_rank):
        trace = traces_by_rank[rank] or {}
        off_s, err_s = offsets.get(rank, (0.0, 0.0))
        shift_us = off_s * 1e6
        applied[str(rank)] = [round(off_s, 6), round(err_s, 6)]
        events.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": rank,
                "tid": 0,
                "args": {"name": f"rank{rank}"},
            }
        )
        for e in trace.get("traceEvents", []):
            if e.get("ph") == "M":
                e = dict(e)
                e["pid"] = rank
                events.append(e)
                continue
            e = dict(e)
            e["pid"] = rank
            if "ts" in e:
                e["ts"] = float(e["ts"]) + shift_us
            events.append(e)
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "clock_offsets_s": applied,
            "offset_note": (
                "peer ts shifted by min(t_recv-ts_send) over heartbeat "
                "samples; error bounded by the heartbeat interval"
            ),
        },
    }


# ---------------------------------------------------------------------------
# Pod-correlated passes + straggler attribution
# ---------------------------------------------------------------------------


def begin_pod_pass() -> str:
    """Start one pod-correlated accumulate pass: rank 0 mints the
    `pass_id`, every other rank receives it over the generation-
    namespaced broadcast seam, and every rank stamps it onto its trace
    events (`tracing.set_current_pass_id`) until `complete_pod_pass`.
    MUST be called from an SPMD site (every rank, same order) — the
    broadcast is a collective.  Falls back to a locally minted id when
    the pod seam is down; never raises."""
    from ..tracing import event, mint_run_id, set_current_pass_id

    pass_id = mint_run_id("pass")
    try:
        from ..parallel.context import (
            broadcast_bytes,
            cross_process_reduce_ready,
            process_topology,
        )

        nranks, rank = process_topology()
        if nranks > 1 and cross_process_reduce_ready():
            payload = pass_id.encode("ascii") if rank == 0 else None
            pass_id = broadcast_bytes("pass_id", payload).decode("ascii")
    except Exception:
        pass  # local id still correlates this rank's own spans
    with _fleet_lock:
        _pass_state.clear()
        _pass_state.update(
            {
                "pass_id": pass_id,
                "t0_pc": time.perf_counter(),
                "t0_wall": time.time(),
            }
        )
    set_current_pass_id(pass_id)
    event(f"pod_pass_begin[{pass_id}]")
    return pass_id


def _local_phase_seconds(t0_pc: float, t1_pc: float) -> Dict[str, float]:
    """This rank's per-phase wall seconds over the pass window, from
    the utilization timeline: intervals are merged per kind and clipped
    to [t0_pc, t1_pc], so a long-lived producer can't charge time from
    a previous pass to this one."""
    from .utilization import merge_intervals, timeline

    evs = timeline()
    out: Dict[str, float] = {}
    for phase, kind in _PHASE_KINDS.items():
        iv = [
            (max(e[3], t0_pc), min(e[4], t1_pc))
            for e in evs
            if e[1] == kind and e[4] > t0_pc and e[3] < t1_pc
        ]
        out[phase] = round(
            sum(hi - lo for lo, hi in merge_intervals(iv) if hi > lo), 6
        )
    return out


def complete_pod_pass(run_id: str = "") -> Optional[Dict[str, Any]]:
    """Close the current pod pass: compute this rank's per-phase
    seconds, ride them on a `reduce_blob_list` exchange (SPMD — every
    rank reaches this site after the pass reduction), and fold every
    rank's blob into the straggler table all ranks agree on.  Publishes
    `pod_straggler_seconds{rank,phase}` and records the report as the
    run's `pass_report` fact (`tracing.fact`) for the fit report.  A
    failed exchange (peer died after the main reduce) degrades to a
    local-only report; never raises."""
    from ..tracing import fact, set_current_pass_id

    with _fleet_lock:
        state = dict(_pass_state)
        _pass_state.clear()
    if not state:
        return None
    pass_id = state["pass_id"]
    t1_pc = time.perf_counter()
    phases = _local_phase_seconds(state["t0_pc"], t1_pc)
    try:
        from ..parallel.context import process_topology, reduce_blob_list

        nranks, rank = process_topology()
        blob = json.dumps(
            {"rank": rank, "pass_id": pass_id, "phases": phases}
        ).encode("ascii")
        if nranks > 1:
            blobs = reduce_blob_list("pass_report", blob)
        else:
            blobs = [blob]
        per_rank: Dict[int, Dict[str, float]] = {}
        for b in blobs:
            try:
                d = json.loads(b.decode("ascii"))
                per_rank[int(d["rank"])] = {
                    p: float(v) for p, v in d.get("phases", {}).items()
                }
            except Exception:
                continue
    except Exception:
        # recovery owns the failure; the local view still reports
        try:
            from ..parallel.context import process_topology

            rank = process_topology()[1]
        except Exception:
            rank = 0
        per_rank = {rank: phases}
    slowest: Dict[str, Any] = {}
    for phase in _PHASE_KINDS:
        rows = {r: p.get(phase, 0.0) for r, p in per_rank.items()}
        if not rows:
            continue
        worst = max(rows, key=lambda r: rows[r])
        slowest[phase] = {
            "rank": worst,
            "seconds": rows[worst],
            "spread_s": round(rows[worst] - min(rows.values()), 6),
        }
        for r, s in rows.items():
            STRAGGLER_SECONDS.set(s, rank=str(r), phase=phase)
    report = {
        "pass_id": pass_id,
        "wall_s": round(t1_pc - state["t0_pc"], 6),
        "ranks": {str(r): per_rank[r] for r in sorted(per_rank)},
        "slowest": slowest,
        "run_id": run_id,
        "stamp": round(time.time(), 3),
    }
    fact("pass_report", **report)
    set_current_pass_id("")
    return report


def pass_report() -> Dict[str, Any]:
    """The last completed pass report any thread of this process still
    holds in its trace buffer, or {}."""
    from ..tracing import last_fact

    return last_fact("pass_report", all_threads=True)


# ---------------------------------------------------------------------------
# Pod incident bundles
# ---------------------------------------------------------------------------


def mint_incident_id(
    reason: str, token: str, generation: int = 0
) -> str:
    """One DETERMINISTIC incident id per pod-scale event: a hash of
    (reason, detection generation, caller token — e.g. the sorted dead
    set).  Every survivor of the same event computes the same id
    without a round of communication, so their bundles share it and
    fleet aggregation can group per incident instead of per rank."""
    h = hashlib.blake2b(digest_size=6)
    h.update(f"{reason}|g{int(generation)}|{token}".encode())
    incident_id = f"inc-{h.hexdigest()}"
    POD_INCIDENTS.inc(reason=reason)
    return incident_id


def _own_ring_trace() -> Dict[str, Any]:
    from ..config import get_config
    from .exporters import chrome_trace
    from .flight_recorder import RECORDER

    window_s = float(get_config("flight_recorder_window_s"))
    return chrome_trace(events=RECORDER.events(window_s=window_s))


def exchange_incident_rings(
    incident_id: str, dead=(),
) -> Dict[str, Any]:
    """Best-effort cross-rank evidence collection for one incident:
    publish this rank's recent flight-recorder ring (as a Chrome trace)
    to an incident-scoped KV key, then pull every live peer's ring
    under one shared deadline (`pod_incident_ring_deadline_s`).  A
    dead or slow peer's ring is simply ABSENT — named in the returned
    `pod_incident.json`, never waited on past the deadline.  Returns
    flight-recorder attachments: the merged `pod_trace.json` (every
    collected ring on the common corrected timeline) plus the incident
    manifest.  Single-process or seam-down: {}.  Never raises."""
    try:
        from ..config import get_config
        from ..parallel.context import (
            coordination_client,
            kv_fetch,
            kv_publish,
        )
        from ..resilience.pod import _current_boot_ranks, _my_boot_rank

        client = coordination_client()
        if client is None:
            return {}
        me = _my_boot_rank()
        ranks = _current_boot_ranks()
        dead = {int(d) for d in (dead or ())}
        own = _own_ring_trace()
        try:
            kv_publish(
                f"inc/{incident_id}/{me}",
                json.dumps(own).encode("ascii"),
            )
        except Exception:
            pass  # publishing is for the peers; the pull still runs
        deadline_s = float(get_config("pod_incident_ring_deadline_s"))
        t_end = time.monotonic() + max(0.1, deadline_s)
        traces: Dict[int, Dict[str, Any]] = {me: own}
        absent: Dict[str, str] = {}
        for r in sorted(dead):
            absent[str(r)] = "rank dead at detection; ring lost with it"
        for r in sorted(set(ranks) - dead - {me}):
            left_ms = int(max(50, (t_end - time.monotonic()) * 1000))
            if t_end - time.monotonic() <= 0:
                absent[str(r)] = "incident ring deadline exhausted"
                continue
            try:
                payload = kv_fetch(
                    f"inc/{incident_id}/{r}",
                    timeout_ms=left_ms,
                    tag=f"incident/{incident_id}",
                    peer=r,
                )
                traces[r] = json.loads(payload.decode("ascii"))
            except Exception as e:
                absent[str(r)] = f"{type(e).__name__}: {e}"
        merged = merge_chrome_traces(traces)
        return {
            "pod_trace.json": json.dumps(merged).encode("ascii"),
            "pod_incident": {
                "incident_id": incident_id,
                "dumping_rank": me,
                "ranks_present": sorted(traces),
                "ranks_absent": absent,
                "clock_offsets_s": merged["otherData"][
                    "clock_offsets_s"
                ],
            },
        }
    except Exception:
        return {}


# ---------------------------------------------------------------------------
# Fleet-merged drift windows
# ---------------------------------------------------------------------------


def _drift_key(model: str) -> str:
    # model names may hold characters the KV store treats as
    # separators; a short digest keeps the key flat and collision-free
    return hashlib.blake2b(model.encode(), digest_size=6).hexdigest()


def fleet_drift_enabled() -> bool:
    from ..config import get_config

    return str(get_config("drift_fleet_merge")).lower() != "off"


def publish_drift_window(model: str, payload: bytes) -> None:
    """Publish one closed drift-window builder blob to this rank's next
    monotonic incident-free KV key for `model`.  NON-collective: the
    busy rank publishes whenever its window closes; idle peers owe
    nothing.  No-op single-process or seam-down; never raises."""
    try:
        from ..parallel.context import (
            coordination_client,
            kv_publish,
            process_topology,
        )
        from ..resilience.pod import _my_boot_rank

        if process_topology()[0] == 1 or not fleet_drift_enabled():
            return
        if coordination_client() is None:
            return
        me = _my_boot_rank()
        mk = _drift_key(model)
        with _fleet_lock:
            seq = _drift_pub_seq.get(model, 0)
            _drift_pub_seq[model] = seq + 1
        kv_publish(f"drift/{mk}/{me}/{seq}", payload)
    except Exception:
        pass


def fetch_peer_drift_windows(model: str) -> Dict[int, bytes]:
    """Drain peers' newly published drift blobs with tiny bounded
    probes (the liveness-probe shape: present-now or skip, never a
    real wait) and return the LATEST blob per peer rank seen so far.
    Pull-based and non-collective — a rank that never serves traffic
    never publishes, and that's fine: its absence just means the pod
    view equals the publishers' merge.  Never raises."""
    out: Dict[int, bytes] = {}
    try:
        from ..parallel.context import (
            coordination_client,
            kv_fetch,
            process_topology,
        )
        from ..resilience.pod import _current_boot_ranks, _my_boot_rank

        if process_topology()[0] == 1 or not fleet_drift_enabled():
            return {}
        client = coordination_client()
        if client is None:
            return {}
        me = _my_boot_rank()
        mk = _drift_key(model)
        for r in sorted(set(_current_boot_ranks()) - {me}):
            while True:
                with _fleet_lock:
                    seq = _drift_next_seq.get((model, r), 0)
                try:
                    payload = kv_fetch(
                        f"drift/{mk}/{r}/{seq}",
                        timeout_ms=_DRIFT_PROBE_MS,
                        tag=f"drift/{model}",
                        peer=r,
                    )
                except Exception:
                    break  # nothing new from this peer right now
                with _fleet_lock:
                    _drift_next_seq[(model, r)] = seq + 1
                    _drift_latest.setdefault(model, {})[r] = bytes(
                        payload
                    )
        with _fleet_lock:
            out = dict(_drift_latest.get(model, {}))
    except Exception:
        pass
    return out


# ---------------------------------------------------------------------------
# Summaries / lifecycle
# ---------------------------------------------------------------------------


def fleet_summary() -> Dict[str, Any]:
    """Small pod-observatory block for serving `_totals` / reports:
    the last pass report, the live clock-offset table, incident count
    families are on the registry already."""
    out: Dict[str, Any] = {}
    rep = pass_report()
    if rep:
        out["pass_report"] = rep
    offs = clock_offsets()
    if offs:
        out["clock_offsets_s"] = {
            str(r): [round(o, 6), round(e, 6)]
            for r, (o, e) in sorted(offs.items())
        }
    return out


def reset_fleet() -> None:
    """Tests / operator reset: drop every piece of fleet state."""
    from ..tracing import set_current_pass_id

    with _fleet_lock:
        _clock_samples.clear()
        _pass_state.clear()
        _drift_pub_seq.clear()
        _drift_next_seq.clear()
        _drift_latest.clear()
    set_current_pass_id("")


def on_reinit() -> None:
    """Pod re-bootstrap (resilience/pod.on_reinit): peer clocks and
    drift seq counters belong to the OLD runtime — a re-bootstrapped
    peer restarts its heartbeat numbering and its drift keys live
    under a new generation prefix.  The last pass report survives (it
    describes a completed pass, not live state)."""
    with _fleet_lock:
        _clock_samples.clear()
        _pass_state.clear()
        _drift_pub_seq.clear()
        _drift_next_seq.clear()
        _drift_latest.clear()


__all__ = [
    "begin_pod_pass",
    "clock_offsets",
    "complete_pod_pass",
    "exchange_incident_rings",
    "fetch_peer_drift_windows",
    "fleet_drift_enabled",
    "fleet_summary",
    "merge_chrome_traces",
    "mint_incident_id",
    "note_clock_sample",
    "on_reinit",
    "pass_report",
    "publish_drift_window",
    "reset_fleet",
]
