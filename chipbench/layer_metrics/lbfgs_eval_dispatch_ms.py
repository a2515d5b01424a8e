# L1 compute: the mean `lbfgs_eval_dispatch` span in ms, each fit's first
# left out (it holds the re-jit): the call of the evaluating program until it
# is enqueued, the host at WORK.  The rest of `lbfgs_eval_gap_ms` is the wait
# and the fetch.
from chipbench import span_reads


def read(ctx):
    later = span_reads.durations(ctx, "lbfgs_eval_dispatch", skip_first=True)
    return 1e3 * sum(later) / len(later) if later else None
