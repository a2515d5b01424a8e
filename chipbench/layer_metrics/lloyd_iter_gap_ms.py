# L1 compute: the host's share of one host-dispatched Lloyd iteration: the
# mean `kmeans_lloyd_iter` span (first block dispatched to the shift on the
# host) less the device seconds per iteration of the programs it waits for
# (trace, "XLA Modules", through the adapter's PROGRAMS): the dispatches the
# device does not hide and the shift's round trip.
from chipbench import spans, trace_reduce


def read(ctx):
    patterns = getattr(ctx["adapter"], "PROGRAMS", {}).get("lloyd_iter")
    if not ctx.get("trace") or not patterns:
        return None
    seconds, runs = trace_reduce.program_seconds(ctx["trace"], patterns)
    iters = [t1 - t0 for f in ctx["fits"]
             for _, t0, t1 in spans.named(f, "kmeans_lloyd_iter")]
    if not runs or not iters:
        return None
    return 1e3 * (sum(iters) - seconds) / len(iters)
