# L1 compute: `lbfgs_eval` spans per fit: the evaluations the program made,
# counted at the call (the roofline takes the reference's count on trust).
# The mean over the window's fits: whole where every fit made as many.
from chipbench import spans


def read(ctx):
    counts = [len(spans.named(f, "lbfgs_eval")) for f in ctx["fits"]]
    counts = [c for c in counts if c]
    return sum(counts) / len(counts) if counts else None
