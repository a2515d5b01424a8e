# The PCA family's benchmark tests (chipbench/tests/test_pca.py: its work
# counted by hand, its limits beside their readings, the `low_rank` data
# model), run with the tier-1 suite like the rest of the benchmark's tests
# (tests/test_chipbench.py).
from chipbench.tests.test_pca import *  # noqa: F401,F403
