# L1 compute: seconds per fit inside the program's `linreg_fetch` span: the
# Gram, the cross term and the moments copied to the host, after the wait
# for the program that made them (`linreg_gram`).
from chipbench import spans


def read(ctx):
    return spans.seconds_per_fit(ctx, "linreg_fetch")
