# L3 ingest: seconds per fit inside the program's `stage_prep` spans, one per
# staged piece: the producer's slice/cast/copy of the host rows, on the
# prefetch thread.  It overlaps `stage_put`; read the two beside `stage_s`.
from chipbench import spans


def read(ctx):
    return spans.seconds_per_fit(ctx, "stage_prep")
