# L1 compute: seconds per fit inside the program's `forest_grow` spans, one
# per dispatched chunk of trees, each ended when its trees are built.
from chipbench import spans


def read(ctx):
    return spans.seconds_per_fit(ctx, "forest_grow")
