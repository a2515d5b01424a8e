# L1 compute: seconds per fit inside the program's `pca_covariance` span: the
# pass that makes the shift and the Gram's row-block programs over the
# resident rows, the device's, waited for.
from chipbench import spans


def read(ctx):
    return spans.seconds_per_fit(ctx, "pca_covariance")
