# L1 compute: seconds per fit inside the program's `lbfgs_host_step` spans:
# the host's own work between evaluations of the host-dispatched L-BFGS
# (two-loop recursion, line-search bookkeeping, checkpoint write).
from chipbench import spans


def read(ctx):
    return spans.seconds_per_fit(ctx, "lbfgs_host_step")
