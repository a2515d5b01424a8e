# L1 compute: seconds per fit inside the program's `linreg_solve_factor`
# span: the factorisation and solve of the assembled system (Cholesky, or
# the LU that takes a system it refuses), inside `linreg_host_solve`.  WORK.
from chipbench import spans


def read(ctx):
    return spans.seconds_per_fit(ctx, "linreg_solve_factor")
