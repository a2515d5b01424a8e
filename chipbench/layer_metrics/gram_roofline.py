# L1 compute: linreg_sufficient_stats' share of its roofline: the larger of
# 2 rows cols^2 FLOP at the bf16 peak and one read of the rows at the peak
# bytes/s (FLOP bounds it at 3000 columns), over its device time per fit.
from chipbench import roofline


def read(ctx):
    return roofline.kernel_share(ctx, "gram", 1)
