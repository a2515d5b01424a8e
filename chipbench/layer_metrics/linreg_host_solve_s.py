# L1 compute: seconds per fit inside the program's `linreg_host_solve` span:
# float64 centring, scaling and the solve of the cols x cols system on the host.
from chipbench import spans


def read(ctx):
    return spans.seconds_per_fit(ctx, "linreg_host_solve")
