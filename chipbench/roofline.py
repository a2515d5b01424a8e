#
# chipbench/roofline.py: the least time a chip could take for a piece of
# work, from chipbench/peaks.json.  A device that is not in the table is an
# error, never a default.
#
from __future__ import annotations

import json
import os
from typing import Tuple

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in chipbench/peaks.json "
            f"(known: {sorted(table)}); add it with its source")
    return table[device_kind]


def least_seconds(piece: dict, peaks: dict) -> Tuple[float, str]:
    """(seconds, which peak bounds it) for {"flops": .., "bytes": ..}."""
    by_flops = piece["flops"] / peaks["flops_per_s"]
    by_bytes = piece["bytes"] / peaks["bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bytes")


def fit_least_seconds(work: dict, peaks: dict) -> float:
    """The least time one chip could take for its share of one fit."""
    return sum(least_seconds(p, peaks)[0] * p["count"] for p in work["fit"])


def kernel_share(ctx: dict, kernel: str, calls_per_fit: float):
    """Percent of its roofline that `kernel` reached: least time for
    `calls_per_fit` calls over the device time per fit of the programs that
    hold it.  None where the trace has no such program."""
    from . import trace_reduce

    if ctx.get("trace") is None or kernel not in ctx["work"]["kernels"]:
        return None
    seconds, runs = trace_reduce.program_seconds(
        ctx["trace"], ctx["adapter"].PROGRAMS[kernel])
    if not runs or seconds <= 0 or not ctx["traced_fits"]:
        return None
    least, _ = least_seconds(ctx["work"]["kernels"][kernel], ctx["peaks"])
    return 100.0 * least * calls_per_fit / (seconds / ctx["traced_fits"])
