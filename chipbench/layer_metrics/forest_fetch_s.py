# L1 compute: seconds per fit inside the program's `forest_fetch` span: the
# built trees' node tables brought to the host.
from chipbench import spans


def read(ctx):
    return spans.seconds_per_fit(ctx, "forest_fetch")
