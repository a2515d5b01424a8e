# L1 compute: `kmeans_lloyd_iter` spans per fit: the Lloyd iterations the
# program made, counted at the loop.  The mean over the window's fits; whole,
# and the configuration's maxIter, where every fit ran every iteration (the
# whole-fit count of `fit_mfu` is exact only then).
from chipbench import spans


def read(ctx):
    counts = [len(spans.named(f, "kmeans_lloyd_iter")) for f in ctx["fits"]]
    counts = [c for c in counts if c]
    return sum(counts) / len(counts) if counts else None
