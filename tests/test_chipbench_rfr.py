# The regression forest family's benchmark tests (chipbench/tests/test_rfr.py:
# its work by hand, the features a node, its configuration against its audit,
# the programs it names) run in tier-1 from here.
from chipbench.tests.test_rfr import *  # noqa: F401,F403
