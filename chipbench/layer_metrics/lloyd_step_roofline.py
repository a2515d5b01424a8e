# L1 compute: the Lloyd step's share of its roofline.  Least time for the
# steps the algorithm made (the reference's own count of iterations; per
# step the larger of 2 rows cols k + rows cols FLOP at the bf16 peak and one
# read of the chip's rows) plus the final cost pass's assignment, over the
# device time per fit of the programs a step runs in (trace, "XLA Modules").
# The counts are the shapes' (`kmeans.work`), never the implementation's.
from chipbench import roofline, trace_reduce


def read(ctx):
    kernels, iters = ctx["work"]["kernels"], ctx["reference"].get("n_iter")
    patterns = getattr(ctx["adapter"], "PROGRAMS", {}).get("lloyd_step")
    if not ctx.get("trace") or not patterns or not iters or "lloyd_step" not in kernels:
        return None
    seconds, runs = trace_reduce.program_seconds(ctx["trace"], patterns)
    if not runs or seconds <= 0 or not ctx["traced_fits"]:
        return None
    least = (iters * roofline.least_seconds(kernels["lloyd_step"], ctx["peaks"])[0]
             + roofline.least_seconds(kernels["lloyd_assign"], ctx["peaks"])[0])
    return 100.0 * least / (seconds / ctx["traced_fits"])
