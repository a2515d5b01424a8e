# L1 compute: seconds per fit inside the program's `pca_subspace_device`
# span: dispatch of the block iteration on the device to the end of the fetch
# of its block, inside `pca_eigensolve`.  A WAIT.
from chipbench import spans


def read(ctx):
    return spans.seconds_per_fit(ctx, "pca_subspace_device")
