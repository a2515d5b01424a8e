# L3 ingest: seconds per fit inside the program's `stage_put` spans, one per
# staged piece: device lock taken to update dispatched, the wait for an
# older piece's update (two may be in flight per device) included.
from chipbench import spans


def read(ctx):
    return spans.seconds_per_fit(ctx, "stage_put")
