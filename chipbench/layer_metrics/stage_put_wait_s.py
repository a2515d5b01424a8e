# L3 ingest: seconds per fit inside the program's `stage_put_wait` spans: the
# host parked until an older piece's update has run (two may be in flight per
# device), inside a `stage_put` and in the drain of `stage_finish`.  A WAIT:
# with `stage_put_call_s` it says whether the put is held by the wire or by
# the runtime's own call.
from chipbench import spans


def read(ctx):
    return spans.seconds_per_fit(ctx, "stage_put_wait")
