#
# chipbench/span_reads.py: further ways to read a window's program spans,
# beside chipbench/spans.py (whose `named` and `seconds_per_fit` these build
# on): a span's count per fit, every duration of a name, and a span's self
# time by the fit report's tree (`span_parents`).  None, or an empty list,
# where the program records no such span.
#
from __future__ import annotations

from typing import List, Optional

from chipbench import spans, trace_reduce


def count_per_fit(ctx: dict, name: str) -> Optional[float]:
    """Spans called `name` per fit, averaged over the window's fits that
    recorded one.  None where none did."""
    counts = [len(spans.named(f, name)) for f in ctx["fits"]]
    counts = [c for c in counts if c]
    return sum(counts) / len(counts) if counts else None


def durations(ctx: dict, name: str, skip_first: bool = False) -> List[float]:
    """Seconds of every span of the window called `name`, fit by fit;
    `skip_first` leaves each fit's first out."""
    out: List[float] = []
    for f in ctx["fits"]:
        mine = [t1 - t0 for _, t0, t1 in spans.named(f, name)]
        out += mine[1:] if skip_first else mine
    return out


def self_seconds(fit: dict, name: str) -> Optional[float]:
    """Seconds of the fit's spans called `name` that none of their own
    children covers: duration less the union of the children's intervals
    (children overlap where a second thread records under the same
    parent), by the fit's `span_parents`.  None where the fit has no such
    span or no record of parents."""
    parents = fit.get("span_parents")
    if parents is None:
        return None
    total = None
    for i, (n, t0, t1) in enumerate(fit["spans"]):
        if n != name:
            continue
        children = [(a, b) for (_, a, b), p in zip(fit["spans"], parents) if p == i]
        covered = trace_reduce.total(trace_reduce.union(trace_reduce.clip(children, (t0, t1))))
        total = (total or 0.0) + (t1 - t0) - covered
    return total
