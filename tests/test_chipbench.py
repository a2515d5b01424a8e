# The benchmark's own tests (chipbench/tests: the manifest's contract, a CPU
# rehearsal of every cell with --trace 0 and 1, the control, the trace
# reduction) run in tier-1 from here, under this directory's eight devices.
# The traced rehearsals share one trace directory (`.chipbench_trace`), so
# under xdist they need `--dist loadfile`, as the tier-1 command has it.
from chipbench.tests.test_chipbench import *  # noqa: F401,F403
