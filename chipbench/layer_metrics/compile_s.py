# compile: seconds per fit inside the program's `compile[trace|lower|
# backend_compile|cache_read]` spans, an overlap counted once (a cache read
# lies inside its backend_compile).  0.0 where the window compiled nothing;
# None where the counter says it compiled and no fit has such a span: the
# program does not record them.
from chipbench import trace_reduce


def read(ctx):
    fits = ctx["fits"]
    per_fit = [trace_reduce.total(trace_reduce.union(
        (t0, t1) for name, t0, t1 in f["spans"] if name.startswith("compile[")))
        for f in fits]
    if not fits or (ctx["compiles_in_window"] and not any(per_fit)):
        return None
    return sum(per_fit) / len(fits)
