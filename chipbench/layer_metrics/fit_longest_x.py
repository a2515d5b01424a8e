# L6 API whole fit: the longest `fit[<Est>]` span of the window over the
# median one.  Every other metric is a mean over the window's fits: one fit
# that stalled among many reads well over 1 here and moves a mean by its
# share.
import statistics


def read(ctx):
    fits = [t1 - t0 for f in ctx["fits"] for name, t0, t1 in f["spans"]
            if name.startswith("fit[")]
    return max(fits) / statistics.median(fits) if fits and min(fits) > 0 else None
