# L6 API, the whole fit: the least time one chip could take for its share of
# a fit (chipbench/estimators/<family>.py `work`, against peaks.json) over
# the fit's wall time.  It bounds every kernel's gain and cannot pass 100.


def read(ctx):
    from chipbench import roofline

    if ctx["peaks"] is None or not ctx["fits"]:
        return None
    least = roofline.fit_least_seconds(ctx["work"], ctx["peaks"])
    return 100.0 * least / (ctx["window_s"] / len(ctx["fits"]))
