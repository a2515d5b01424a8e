# The multinomial logistic family's benchmark tests (chipbench/tests/test_mlogreg.py:
# its work by hand, the manifest with its cell, the data model, the reference
# against the program and its control) run in tier-1 from here.
from chipbench.tests.test_mlogreg import *  # noqa: F401,F403
