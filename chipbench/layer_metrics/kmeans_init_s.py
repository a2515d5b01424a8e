# L1 compute: seconds per fit inside the program's `kmeans_init` span: the
# initial centres drawn and fetched out of the resident rows, ready on the
# device.
from chipbench import spans


def read(ctx):
    return spans.seconds_per_fit(ctx, "kmeans_init")
