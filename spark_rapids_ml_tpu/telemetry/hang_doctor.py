#
# Automatic hang doctor — the stall half of the progress observatory.
# PR 14's two-thread `describe()` deadlock wedged three tier-1 runs at
# zero CPU and was root-caused BY HAND with faulthandler; the doctor
# makes that diagnosis automatic and always-on.  A daemon thread
# (spawned on the first trace event; `hang_doctor` conf, default on)
# watches forward progress through signals the telemetry stack already
# emits:
#
#   trace-event flow      every span/instant bumps a tap counter (the
#                         same tap feed the flight recorder rides)
#   heartbeat advance     the `solver_iteration`/`solver_loss` gauges
#   serving collects      completed-request counts on the serving
#                         latency family
#
# A STALL is either (a) a thread stuck waiting on a named lock for
# `hang_doctor_stall_s` (telemetry/locks.py waiter table), or (b) work
# visibly in progress — live solver gauges, queued serving requests,
# held/waited named locks — with NO progress signal advancing for
# `hang_doctor_stall_s`.  On a stall the doctor:
#
#   1. captures ALL thread stacks (`sys._current_frames`),
#   2. builds the lock wait-for graph from the holder/waiter table and
#      detects cycles (naming the deadlocked threads and locks),
#   3. dumps a `reason="stall"` flight-recorder bundle — the stacks,
#      wait-for graph and lock table ride as attachments next to the
#      bundle's usual trace.json of the newest spans — under the
#      recorder's existing per-reason cooldown, counted by
#      `postmortems_total{reason="stall"}`.
#
# One stall EPISODE dumps once: the doctor re-arms only after a progress
# signal moves again, so a wedged run leaves one bundle, not one per
# tick.  Tick cost is microseconds; the default 120 s stall threshold
# keeps long XLA compiles — which emit no trace events while they run —
# from reading as stalls in CI.
#
from __future__ import annotations

import sys
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

from . import locks
from .registry import REGISTRY, counter

TICKS = counter(
    "hang_doctor_ticks_total", "Hang-doctor watchdog evaluations"
)
STALLS = counter(
    "hang_doctor_stalls_total",
    "Stall episodes the hang doctor detected, by kind",
)

_DEFAULT_STALL_S = 120.0
# how long _diagnose waits for the flight-recorder dump thread before
# falling back to a stderr diagnosis (the dump path takes locks and
# writes files — in a badly wedged process those can hang too)
_DUMP_JOIN_S = 15.0
# poll cadence: fast enough to catch a stall within ~stall_s * 1.25,
# bounded so tiny test thresholds don't spin
_MIN_POLL_S = 0.05
_MAX_POLL_S = 2.0
_DISABLED_POLL_S = 0.5


def all_thread_stacks() -> str:
    """Every live thread's current stack, faulthandler-style, with
    thread names resolved — the evidence the PR-14 wedge had to be
    root-caused with by hand."""
    names = {t.ident: t.name for t in threading.enumerate()}
    parts: List[str] = []
    for tid, frame in sorted(sys._current_frames().items()):
        parts.append(
            f"--- thread {tid} ({names.get(tid, '?')}) ---\n"
            + "".join(traceback.format_stack(frame))
        )
    return "\n".join(parts)


def build_wait_graph(table: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Edges of the thread wait-for graph: one edge per (waiter, lock,
    holder) triple in the live lock table — thread W waits for lock L
    held by thread H."""
    edges: List[Dict[str, Any]] = []
    for row in table:
        holder = row.get("holder")
        if not holder:
            continue
        for w in row.get("waiters", ()):
            edges.append({
                "waiter_id": w["thread_id"],
                "waiter": w["thread"],
                "lock": row["name"],
                "holder_id": holder["thread_id"],
                "holder": holder["thread"],
                "waited_s": w.get("waited_s", 0.0),
            })
    return edges


def find_cycles(edges: List[Dict[str, Any]]) -> List[List[Dict[str, Any]]]:
    """Cycles in the wait-for graph, each as its edge list — a cycle IS
    a deadlock (every thread on it waits for a lock another one holds).
    A thread waits on at most one lock at a time, so successor-chasing
    with a visited set finds every cycle exactly once."""
    succ: Dict[int, Dict[str, Any]] = {}
    for e in edges:
        succ.setdefault(e["waiter_id"], e)
    cycles: List[List[Dict[str, Any]]] = []
    done: set = set()
    for start in succ:
        if start in done:
            continue
        path: List[int] = []
        seen_at: Dict[int, int] = {}
        node = start
        while node in succ and node not in done:
            if node in seen_at:
                cyc = path[seen_at[node]:]
                cycles.append([succ[t] for t in cyc])
                break
            seen_at[node] = len(path)
            path.append(node)
            node = succ[node]["holder_id"]
        done.update(path)
    return cycles


def describe_cycle(cycle: List[Dict[str, Any]]) -> str:
    """Human line naming the deadlocked threads and locks:
    `A -(lock1)-> B -(lock2)-> A`."""
    if not cycle:
        return ""
    hops = [f"{e['waiter']} -({e['lock']})-> " for e in cycle]
    return "".join(hops) + cycle[0]["waiter"]


class HangDoctor:
    """The process-global stall watchdog.  `install()` hooks it onto the
    tracing tap; the daemon spawns on the first observed event and then
    re-reads the `hang_doctor`/`hang_doctor_stall_s` confs every tick,
    so tests (and operators) retune it live."""

    def __init__(self, force_enabled: bool = False) -> None:
        # reentrant for the same reason as the flight recorder's
        # lock: on_event (a trace tap) takes it on the first event,
        # and the slow-wait instrumentation may emit a trace event
        # while it is held
        self._mu = locks.named_lock("hang_doctor", kind="rlock")
        # tests drive PRIVATE doctors tick-by-tick with the global
        # daemon conf'd off; force_enabled makes such an instance ignore
        # the `hang_doctor` conf (stall_s still reads from conf)
        self._force = force_enabled
        self._started = False
        self._thread: Optional[threading.Thread] = None
        self._events = 0  # tap counter; lone-writer += races lose only a tick
        self._last_fp: Any = None
        self._last_progress = time.monotonic()
        # the last diagnosed stall EPISODE: for a lock stall, the frozen
        # set of (lock, waiter) pairs — stable while other threads keep
        # making progress, so one stuck waiter in an otherwise-active
        # process dumps ONCE, not once per tick; for a no-progress
        # stall, the progress fingerprint (any advance re-arms)
        self._dumped_episode: Any = None

    # -- feed ----------------------------------------------------------------

    def on_event(self, _event: Any) -> None:
        """Tracing-tap entry point: count the event (progress signal)
        and make sure the watchdog thread exists."""
        self._events += 1
        if not self._started:
            self._ensure_thread()

    def _ensure_thread(self) -> None:
        with self._mu:
            if self._started:
                return
            self._started = True
            t = threading.Thread(
                target=self._loop, name="hang-doctor", daemon=True
            )
            self._thread = t
        t.start()

    # -- configuration -------------------------------------------------------

    def _conf(self) -> tuple:
        try:
            from ..config import get_config

            enabled = str(get_config("hang_doctor")).lower() != "off"
            stall_s = float(get_config("hang_doctor_stall_s"))
        except Exception:
            enabled, stall_s = True, _DEFAULT_STALL_S
        return enabled or self._force, max(stall_s, 0.1)

    # -- progress signals ----------------------------------------------------

    def _fingerprint(self) -> tuple:
        """A cheap hash of every forward-progress signal: trace-event
        count, the live solver gauges, completed serving requests.  Any
        change = the process moved."""
        solver: tuple = ()
        m = REGISTRY.get("solver_iteration")
        if m is not None:
            solver = tuple(sorted(m.samples().items()))
        collects = 0
        lat = REGISTRY.get("serving_request_latency_seconds")
        if lat is not None:
            collects = sum(
                h.get("count", 0)
                for h in lat.samples().values()
                if isinstance(h, dict)
            )
        return (self._events, solver, collects)

    def _reduce_waits(self) -> List[Dict[str, Any]]:
        """In-flight cross-process waits (resilience/pod.py kv_wait):
        thread, reduce tag, peer rank, waited seconds — the pod-scale
        analog of the lock waiter table."""
        try:
            from ..resilience.pod import live_reduce_waits

            return live_reduce_waits()
        except Exception:  # pragma: no cover - import-order defensive
            return []

    def _work_pending(self, table: List[Dict[str, Any]]) -> List[str]:
        """Evidence something SHOULD be making progress: live solver
        gauges (a fit mid-loop), queued serving requests, held or
        awaited named locks, in-flight cross-process reduce waits.
        Returns the evidence labels (empty = the process is
        legitimately idle)."""
        evidence: List[str] = []
        m = REGISTRY.get("solver_iteration")
        if m is not None and m.samples():
            evidence.append("live_solver_gauges")
        q = REGISTRY.get("serving_queue_depth")
        if q is not None and any(
            isinstance(v, (int, float)) and v > 0
            for v in q.samples().values()
        ):
            evidence.append("queued_serving_requests")
        if any(r.get("holder") or r.get("waiters") for r in table):
            evidence.append("held_locks")
        if self._reduce_waits():
            evidence.append("reduce_wait")
        return evidence

    # -- the tick ------------------------------------------------------------

    def tick(self) -> Optional[str]:
        """One watchdog evaluation (the daemon calls this every poll;
        tests call it directly).  Returns the bundle directory when a
        stall was diagnosed and dumped, else None."""
        TICKS.inc()
        locks.publish_lock_metrics()
        enabled, stall_s = self._conf()
        if not enabled:
            return None
        now = time.monotonic()
        fp = self._fingerprint()
        if fp != self._last_fp:
            self._last_fp = fp
            self._last_progress = now
        table = locks.lock_table()
        stuck = [
            (row, w)
            for row in table
            for w in row.get("waiters", ())
            if w.get("waited_s", 0.0) >= stall_s
        ]
        reduce_stuck = [
            w
            for w in self._reduce_waits()
            if w.get("waited_s", 0.0) >= stall_s
        ]
        kind = None
        episode: Any = None
        if stuck:
            kind = "lock_wait"
            episode = (
                "lock_wait",
                frozenset(
                    (row["name"], w["thread_id"]) for row, w in stuck
                ),
            )
        elif reduce_stuck:
            # a thread parked in a cross-process wait past the stall
            # window: name the blocked reduce tag and peer rank — the
            # pod-scale analog of the lock_wait diagnosis.  kv_wait
            # itself bounds the wait (ReduceTimeout at the deadline);
            # the doctor's job is ATTRIBUTION while it is still stuck
            kind = "reduce_wait"
            episode = (
                "reduce_wait",
                frozenset(
                    (w["tag"], w["thread_id"]) for w in reduce_stuck
                ),
            )
        else:
            pending = self._work_pending(table)
            if pending and (now - self._last_progress) >= stall_s:
                kind = "no_progress"
                episode = ("no_progress", fp)
        if kind is None:
            self._dumped_episode = None  # healthy tick re-arms
            return None
        if self._dumped_episode == episode:
            return None  # same episode, already diagnosed
        self._dumped_episode = episode
        STALLS.inc(kind=kind)
        return self._diagnose(kind, stall_s, table, stuck, reduce_stuck)

    def _diagnose(
        self,
        kind: str,
        stall_s: float,
        table: List[Dict[str, Any]],
        stuck: List[tuple],
        reduce_stuck: Optional[List[Dict[str, Any]]] = None,
    ) -> Optional[str]:
        from .flight_recorder import note_failure

        reduce_stuck = reduce_stuck or []
        edges = build_wait_graph(table)
        cycles = find_cycles(edges)
        if cycles:
            detail = "deadlock: " + "; ".join(
                describe_cycle(c) for c in cycles
            )
        elif kind == "reduce_wait" and reduce_stuck:
            worst = max(reduce_stuck, key=lambda w: w.get("waited_s", 0.0))
            peer = worst.get("peer")
            detail = (
                f"thread {worst['thread']} has waited "
                f"{worst.get('waited_s', 0.0):.1f}s in cross-process "
                f"reduce {worst['tag']!r}"
                + (f" on rank {peer}" if peer is not None else "")
            )
        elif stuck:
            worst_row, worst_w = max(
                stuck, key=lambda rw: rw[1].get("waited_s", 0.0)
            )
            holder = worst_row.get("holder") or {}
            detail = (
                f"thread {worst_w['thread']} has waited "
                f"{worst_w.get('waited_s', 0.0):.1f}s for lock "
                f"{worst_row['name']!r}"
                + (
                    f" held by {holder.get('thread')} for "
                    f"{holder.get('held_s', 0.0):.1f}s"
                    if holder
                    else ""
                )
            )
        else:
            detail = (
                f"no forward progress for {stall_s:.0f}s with work "
                "in flight"
            )
        waitfor = {
            "kind": kind,
            "stall_s": stall_s,
            "edges": edges,
            "reduce_waits": [
                {k: v for k, v in w.items() if k != "since"}
                for w in reduce_stuck
            ],
            "cycles": [
                {
                    "threads": [e["waiter"] for e in c],
                    "locks": [e["lock"] for e in c],
                    "description": describe_cycle(c),
                }
                for c in cycles
            ],
        }
        stacks = all_thread_stacks()

        # The dump path takes the flight recorder's lock and writes
        # files — in a badly wedged process THOSE can hang too, and the
        # watchdog must never die of its patient.  Dump on a short-lived
        # side thread with a join timeout; if even the dump wedges, the
        # diagnosis still escapes via stderr (the same channel the
        # WEDGE_GUARD faulthandler backstop uses).
        result: Dict[str, Any] = {}

        def _dump() -> None:
            result["bdir"] = note_failure(
                "stall",
                detail=detail,
                attachments={
                    # bytes write verbatim; dicts land as `<key>.json`
                    "stacks.txt": stacks.encode(),
                    "waitfor": waitfor,
                    "locks": table,
                },
            )

        t = threading.Thread(
            target=_dump, name="hang-doctor-dump", daemon=True
        )
        t.start()
        t.join(timeout=_DUMP_JOIN_S)
        if t.is_alive():
            sys.stderr.write(
                f"hang doctor: stall diagnosed ({detail}) but the "
                "flight-recorder dump itself wedged; stacks follow\n"
                + stacks + "\n"
            )
            return None
        return result.get("bdir")

    # -- the daemon ----------------------------------------------------------

    def _loop(self) -> None:
        while True:
            enabled, stall_s = self._conf()
            if not enabled:
                time.sleep(_DISABLED_POLL_S)
                continue
            try:
                self.tick()
            except Exception:  # the watchdog must never die of its patient
                pass
            time.sleep(
                min(_MAX_POLL_S, max(_MIN_POLL_S, stall_s / 4.0))
            )


# the process-global doctor every trace event feeds
DOCTOR = HangDoctor()

_installed = False


def install() -> HangDoctor:
    """Hook the doctor onto the tracing tap (idempotent; called at
    telemetry import, like the flight recorder).  The watchdog thread
    itself spawns lazily on the first recorded event, so merely
    importing the package starts no threads."""
    global _installed
    with DOCTOR._mu:
        if not _installed:
            from ..tracing import add_trace_tap

            add_trace_tap(DOCTOR.on_event)
            _installed = True
    return DOCTOR


__all__ = [
    "DOCTOR",
    "HangDoctor",
    "all_thread_stacks",
    "build_wait_graph",
    "describe_cycle",
    "find_cycles",
    "install",
]
