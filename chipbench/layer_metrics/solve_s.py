# L1 compute: a fit's wall time less its staging, per fit, by the host clock
# (each fit ends with its coefficients on the host).


def read(ctx):
    fits = ctx["fits"]
    return sum(f["wall_s"] - f["stage_s"] for f in fits) / len(fits) if fits else None
