# L1 compute: `pca_polish_sweep` spans per fit: the float64 sweeps the host's
# polish made, counted at the loop.  The mean over the window's fits; whole
# where every fit made as many (a sweep more is ~14 ms of `fit_s`).
from chipbench import span_reads


def read(ctx):
    return span_reads.count_per_fit(ctx, "pca_polish_sweep")
