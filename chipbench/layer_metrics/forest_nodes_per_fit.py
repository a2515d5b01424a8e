# L1 compute: internal (split) nodes per fit, from the run's `fact[forest]`
# as the adapter's answer carries it.  The mean over the window's fits: it
# moves only if the trees change.  None from a program without the fact.


def read(ctx):
    counts = [((f.get("answer") or {}).get("fact") or {}).get("internal_nodes") for f in ctx["fits"]]
    counts = [c for c in counts if c is not None]
    return sum(counts) / len(counts) if counts else None
