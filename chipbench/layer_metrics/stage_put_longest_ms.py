# L3 ingest: the longest single `stage_put` span of the window, in ms.  Every
# other staging metric is a mean over the window's fits; a piece that stalled
# for seconds on the host shows here, and its children say where.
from chipbench import span_reads


def read(ctx):
    puts = span_reads.durations(ctx, "stage_put")
    return 1e3 * max(puts) if puts else None
