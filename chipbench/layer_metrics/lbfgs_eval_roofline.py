# L1 compute: the value+gradient evaluation's share of its roofline.  Least
# time for the evaluations the algorithm made (the reference's own count,
# one read of the chip's rows each) over the device time per fit of the
# programs that evaluate (trace, "XLA Modules").
from chipbench import roofline


def read(ctx):
    evals = ctx["reference"].get("n_evals")
    return roofline.kernel_share(ctx, "lbfgs_eval", evals) if evals else None
