# device: 1 - (union of device-operation intervals over the traced window),
# averaged over the chips.


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None
