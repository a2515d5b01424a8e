# L1 compute: value+gradient evaluations the multinomial fit's solver ran,
# per fit: `evaluations` of the run's `fact[lbfgs]` as the adapter's answer
# carries it (the fused program counts them itself; the line search's
# retries included).  The mean over the window's fits.  None from a program
# without the fact, or whose solver does not count.


def evals(fit: dict):
    return ((fit.get("answer") or {}).get("fact") or {}).get("evaluations")


def read(ctx):
    counts = [c for c in (evals(f) for f in ctx["fits"]) if c is not None]
    return sum(counts) / len(counts) if counts else None
