# L1 compute: the host round trip one evaluation pays: the mean `lbfgs_eval`
# span (call of the evaluating program to its value and gradient on the host;
# each fit's first left out, it holds the re-jit) less the device seconds per
# run of that program (trace, "XLA Modules", through the adapter's PROGRAMS).
from chipbench import spans, trace_reduce


def read(ctx):
    patterns = getattr(ctx["adapter"], "PROGRAMS", {}).get("lbfgs_eval")
    if not ctx.get("trace") or not patterns:
        return None
    seconds, runs = trace_reduce.program_seconds(ctx["trace"], patterns)
    later = [t1 - t0 for f in ctx["fits"]
             for _, t0, t1 in spans.named(f, "lbfgs_eval")[1:]]
    if not runs or not later:
        return None
    return 1e3 * (sum(later) / len(later) - seconds / runs)
