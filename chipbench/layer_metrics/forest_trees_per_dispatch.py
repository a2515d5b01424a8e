# L1 compute: trees to one dispatch of the grow program, `chunk_trees` of the
# run's `fact[forest]` as the adapter's answer carries it (the mean over the
# window's fits): what `ops/forest.chunk_trees_for` made of the shapes and
# the memory the device had left, so it moves when the memory rule or a
# tree's bytes do.  None from a program without the fact.


def read(ctx):
    sizes = [((f.get("answer") or {}).get("fact") or {}).get("chunk_trees") for f in ctx["fits"]]
    sizes = [s for s in sizes if s is not None]
    return sum(sizes) / len(sizes) if sizes else None
