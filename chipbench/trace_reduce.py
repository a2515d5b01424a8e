#
# chipbench/trace_reduce.py: from a profiler trace to the numbers the
# per-layer metrics read.  Pure functions over a list of events, so the
# same reduction runs over a live trace and over the small recorded one
# the tests keep (chipbench/tests/data/).
#
# An event is a dict: plane, line, name, start (seconds from the trace's
# start), dur (seconds), and `module` where the profiler names the XLA
# program an operation belongs to.
#
# What a TPU trace looks like (jax 0.9, libtpu 0.0.34, v5e): one plane per
# chip, "/device:TPU:<n>", whose line "XLA Ops" holds one event per executed
# HLO operation and whose line "XLA Modules" holds one event per run of a
# compiled program, named after the jitted function ("jit_vg_fn(...)").
# Host threads are lines of the plane "/host:CPU".
#
from __future__ import annotations

import glob
import os
import re
from typing import Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast", re.I)
# A `while`, a `conditional` or a `call` is one event that spans the leaf
# operations inside it (the fused L-BFGS solve is one `while` program): it is
# no work of its own, and counted as busy time it would cover every gap and
# every collective inside the loop.  Only leaf operations count.
CONTAINER = re.compile(r"^(while|conditional|call)\b")
SYNC_NAME = "chipbench_sync"

Interval = Tuple[float, float]


def load_events(trace_dir: str) -> List[dict]:
    """Every event of the newest .xplane.pb under `trace_dir`."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            if not device and not plane.name.startswith("/host:"):
                continue
            for ev in line.events:
                if not device and ev.name != SYNC_NAME:
                    continue  # of the host we need the clock mark only
                out.append({
                    "plane": plane.name, "line": line.name, "name": ev.name,
                    "start": ev.start_ns / 1e9, "dur": ev.duration_ns / 1e9,
                })
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the intervals."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of the disjoint sorted cover `a` that `b`, another such
    cover, leaves bare.  One pass over both: a fused solve has 10^5 of each."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        at, k = lo, j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < hi:
            out.append((at, hi))
    return out


def device_planes(events: List[dict]) -> List[str]:
    names = {e["plane"] for e in events if DEVICE_PLANE.match(e["plane"])}
    return sorted(names, key=lambda p: int(DEVICE_PLANE.match(p).group(1)))


def sync_start(events: List[dict]) -> float:
    """Trace time at which the harness entered its clock mark."""
    marks = [e["start"] for e in events if e["name"] == SYNC_NAME]
    if not marks:
        raise ValueError(f"the trace holds no {SYNC_NAME!r} mark")
    return min(marks)


def reduce(events: List[dict], window: Interval) -> Optional[dict]:
    """Per-chip busy time, program and operation times, collectives and idle
    gaps inside `window` (trace seconds).  None when no device operation
    ran there: a reader then has nothing to read."""
    planes = device_planes(events)
    per_chip = []
    for plane in planes:
        spans = {True: [], False: []}  # collective or not -> intervals
        for e in events:
            if e["plane"] != plane or e["line"] != OPS_LINE:
                continue
            op = e["name"].partition(" = ")[0].lstrip("%")
            if CONTAINER.match(op):
                continue  # it spans the operations inside it, gaps and all
            spans[bool(COLLECTIVE.search(op))].append((e["start"], e["start"] + e["dur"]))
        busy = clip(union(spans[True] + spans[False]), window)
        if not busy:
            continue
        coll = clip(union(spans[True]), window)
        compute = clip(union(spans[False]), window)
        per_chip.append({
            "plane": plane, "busy": busy, "busy_s": total(busy),
            "collective_s": total(coll),
            "collective_exposed_s": total(subtract(coll, compute)),
            "gaps": subtract([window], busy),
        })
    if not per_chip:
        return None
    n = len(per_chip)
    ops: dict = {}
    modules: dict = {}
    for e in events:
        if e["plane"] not in planes or not clip([(e["start"], e["start"] + e["dur"])], window):
            continue
        table = ops if e["line"] == OPS_LINE else modules
        name = op_label(e["name"]) if e["line"] == OPS_LINE else program_name(e["name"])
        seconds, count = table.get(name, (0.0, 0))
        table[name] = (seconds + e["dur"] / n, count + 1)
    return {
        "chips": n, "window_s": window[1] - window[0],
        "busy_s": sum(c["busy_s"] for c in per_chip) / n,
        "collective_s": sum(c["collective_s"] for c in per_chip) / n,
        "collective_exposed_s": sum(c["collective_exposed_s"] for c in per_chip) / n,
        "ops": ops,          # name -> (seconds averaged over chips, events)
        "modules": modules,  # program -> (seconds averaged over chips, runs)
        "gaps": per_chip[0]["gaps"],  # of the first chip, for attribution
    }


def op_label(event_name: str) -> str:
    """An operation's name and result shape out of the HLO line the
    profiler gives: '%fusion = f32[3000,3000]{1,0:T(8,128)} fusion(...)' ->
    'fusion f32[3000,3000]'."""
    name, _, rest = event_name.partition(" = ")
    shape = rest.split("{", 1)[0].strip() if rest else ""
    return (name.lstrip("%") + (" " + shape if shape else ""))[:80]


def program_name(event_name: str) -> str:
    """'jit_vg_fn(1234567)' -> 'jit_vg_fn'."""
    return event_name.split("(", 1)[0].strip()


def program_seconds(summary: dict, patterns: Iterable[str]) -> Tuple[float, int]:
    """Device seconds (averaged over chips) and runs of the programs whose
    name holds one of `patterns`."""
    seconds, runs = 0.0, 0
    for name, (s, c) in summary["modules"].items():
        if any(p in name for p in patterns):
            seconds, runs = seconds + s, runs + c
    return seconds, runs


def top_ops(summary: dict, k: int = 10) -> list:
    """The operations that took most device time; a `while` or a
    `conditional` only holds the operations inside it and is left out."""
    ranked = sorted(((n, v) for n, v in summary["ops"].items() if not CONTAINER.match(n)),
                    key=lambda kv: -kv[1][0])
    return [[name, seconds] for name, (seconds, _) in ranked[:k]]


def attribute_gaps(gaps: List[Interval], host_spans: List[Tuple[str, float, float]],
                   parents: List[int], k: int = 10) -> list:
    """Idle seconds by what the host was doing.  Every instant of a gap goes
    to the innermost host spans that cover it: a span (name, start, end;
    trace seconds) owns its time less its children's (`parents[i]` is the
    index of span i's parent in the fit report's tree, -1 for none), and
    spans of two threads that own one instant (the staging prefetch thread
    beside its caller) share it equally.  So a gap longer than the spans
    around it is split over them in proportion to the overlap, where giving
    it whole to the span that held its middle named the wrong one; what no
    span covers is 'between_fits'.  The gaps are arrays throughout: a fused
    solve leaves some 10^5 of them between its leaf operations."""
    import numpy as np

    if not gaps:
        return []
    glo, ghi = np.asarray(gaps, dtype=np.float64).T
    children: dict = {}
    for i, p in enumerate(parents):
        if p >= 0:
            children.setdefault(p, []).append(host_spans[i][1:])
    owner, lo, hi = [], [], []
    for i, (_, s0, s1) in enumerate(host_spans):
        for a, b in subtract(union([(s0, s1)]), union(children.get(i, []))):
            # the gaps that this stretch of the span's own time meets
            j0, j1 = np.searchsorted(ghi, a, "right"), np.searchsorted(glo, b, "left")
            if j1 > j0:
                owner.append(np.full(j1 - j0, i))
                lo.append(np.maximum(glo[j0:j1], a))
                hi.append(np.minimum(ghi[j0:j1], b))
    by_name: dict = {}
    if owner:
        owner, lo, hi = (np.concatenate(x) for x in (owner, lo, hi))
        # between two neighbouring edges the owners do not change: their
        # number there, and the seconds up to each edge with every stretch
        # divided by it
        edges = np.unique(np.concatenate([lo, hi]))
        at_lo, at_hi = np.searchsorted(edges, lo), np.searchsorted(edges, hi)
        owners = np.cumsum(np.bincount(at_lo, minlength=len(edges))
                           - np.bincount(at_hi, minlength=len(edges)))[:-1]
        upto = np.concatenate([[0.0], np.cumsum(np.diff(edges) / np.maximum(owners, 1))])
        per_span = np.bincount(owner, weights=upto[at_hi] - upto[at_lo],
                               minlength=len(host_spans))
        for i in np.flatnonzero(per_span):
            name = host_spans[i][0]
            by_name[name] = by_name.get(name, 0.0) + float(per_span[i])
    idle = float((ghi - glo).sum())
    uncovered = idle - sum(by_name.values())
    if uncovered > 1e-9 * idle:
        by_name["between_fits"] = uncovered
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
    return [[name, seconds] for name, seconds in ranked[:k]]
