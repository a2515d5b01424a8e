# L1 compute: seconds per fit inside the program's `forest_bin` span: the
# edge sample read out of the resident rows, the edges, and the rows
# digitized into packed 8-bit bin ids, ended when the bins are written.
from chipbench import spans


def read(ctx):
    return spans.seconds_per_fit(ctx, "forest_bin")
