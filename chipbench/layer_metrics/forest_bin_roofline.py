# L1 compute: the bin phase's share of its roofline: one read of the chip's
# f32 rows and one write of their 8-bit bin ids at the peak bytes/s, over the
# device time per fit of the programs that sample, sort and digitize.
from chipbench import roofline


def read(ctx):
    return roofline.kernel_share(ctx, "forest_bin", 1)
