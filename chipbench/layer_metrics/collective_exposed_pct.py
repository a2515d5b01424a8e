# L3 communication: collective time during which no other operation runs on
# that chip, over the traced window, averaged over the chips.  Nothing to
# read where the trace holds no collective.


def read(ctx):
    t = ctx["trace"]
    if not t or t["collective_s"] <= 0:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
