#
# chipbench/spans.py: what the per-layer metrics of `source: program_span`
# read.  run.py flattens every span of every window fit's report into
# ctx["fits"][i]["spans"] as (name, start, end) in host epoch seconds; the
# names are the program's contract (docs/observability.md, "Span
# vocabulary").  A program that records no such span gives None: the line
# then leaves the metric out.
#
from __future__ import annotations

from typing import List, Optional, Tuple

Span = Tuple[str, float, float]


def named(fit: dict, name: str) -> List[Span]:
    """The fit's spans called `name`, in the order recorded."""
    return [s for s in fit["spans"] if s[0] == name]


def seconds_per_fit(ctx: dict, name: str) -> Optional[float]:
    """Seconds inside the spans called `name`, summed per fit and averaged
    over the window's fits that recorded one.  None where none did."""
    sums = [sum(t1 - t0 for _, t0, t1 in spans)
            for spans in (named(f, name) for f in ctx["fits"]) if spans]
    return sum(sums) / len(sums) if sums else None
