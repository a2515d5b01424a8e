# L1 compute: seconds per fit inside the program's `pca_fetch` span: the
# (cols, cols) second moments, the column sums and the shift copied to the
# host, after the wait for the programs that made them (`pca_covariance`).
from chipbench import spans


def read(ctx):
    return spans.seconds_per_fit(ctx, "pca_fetch")
