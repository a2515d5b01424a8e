# L1 compute: seconds per fit inside the program's `pca_eigensolve` span: the
# centring and the top-k eigenpairs of the full covariance.  On the host
# (LAPACK in float64) the device idles for all of it.
from chipbench import spans


def read(ctx):
    return spans.seconds_per_fit(ctx, "pca_eigensolve")
