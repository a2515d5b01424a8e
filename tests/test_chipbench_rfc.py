# The forest family's benchmark tests (chipbench/tests/test_rfc.py: its work
# counted by hand, its limits beside the audit's own tolerance), run with the
# tier-1 suite like the rest of the benchmark's tests (tests/test_chipbench.py).
from chipbench.tests.test_rfc import *  # noqa: F401,F403
