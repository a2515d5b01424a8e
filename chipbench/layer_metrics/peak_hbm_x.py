# L2 device memory: the fullest chip's peak_bytes_in_use over that chip's
# share of the rows (rows x cols x 4 / chips).  Nothing to read where the
# backend has no allocator statistics.


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    if not peak:
        return None
    return peak / (ctx["rows"] * ctx["cols"] * 4 / ctx["chips"])
