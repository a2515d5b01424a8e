# L1 compute: the multinomial evaluation's own device time, in ms: the
# device time per fit of the programs that evaluate (trace, "XLA Modules",
# through the adapter's PROGRAMS["lbfgs_eval"]) over the evaluations the
# solver ran a fit (`mlogreg_evals_per_fit`), so it does not move with how
# many the line search asked for.  None without a trace or the count.
from chipbench import manifest as mf
from chipbench import trace_reduce


def read(ctx):
    patterns = getattr(ctx["adapter"], "PROGRAMS", {}).get("lbfgs_eval")
    evals = mf.reader("mlogreg_evals_per_fit")(ctx)
    if not ctx.get("trace") or not patterns or not evals or not ctx["traced_fits"]:
        return None
    seconds, runs = trace_reduce.program_seconds(ctx["trace"], patterns)
    if not runs or seconds <= 0:
        return None
    return 1e3 * seconds / ctx["traced_fits"] / evals
