# L1 compute: a tree level's share of its roofline.  Least time for the
# levels a fit grows (the chip's trees x maxDepth, `rfc.work`; per level the
# larger of rows x (K + 12) bytes at the peak bytes/s and rows x K adds at
# the peak FLOP/s) over the device time per fit of the programs that grow
# the trees (trace, "XLA Modules").  The counts are the shapes', never the
# implementation's, so the share cannot pass 100.
from chipbench import roofline, trace_reduce


def read(ctx):
    kernels = ctx["work"]["kernels"]
    patterns = getattr(ctx["adapter"], "PROGRAMS", {}).get("forest_grow")
    if not ctx.get("trace") or not patterns or "forest_level" not in kernels:
        return None
    seconds, runs = trace_reduce.program_seconds(ctx["trace"], patterns)
    if not runs or seconds <= 0 or not ctx["traced_fits"]:
        return None
    least = ctx["work"]["levels"] * roofline.least_seconds(kernels["forest_level"], ctx["peaks"])[0]
    return 100.0 * least / (seconds / ctx["traced_fits"])
