# L1 compute: seconds per fit inside the program's `linreg_solve_assemble`
# span: the float64 system built from the fetched Gram (the widening copy and
# first touch of its one (d,d) buffer), inside `linreg_host_solve`.  WORK.
from chipbench import spans


def read(ctx):
    return spans.seconds_per_fit(ctx, "linreg_solve_assemble")
