# L3 ingest: seconds per fit inside the program's `stage_put_call` spans: the
# two `jax.device_put` calls of every staged piece, the host at WORK in the
# runtime (a copy out of pageable memory made synchronously shows here, a
# transfer the runtime makes on its own thread in `stage_put_wait_s`).
from chipbench import spans


def read(ctx):
    return spans.seconds_per_fit(ctx, "stage_put_call")
