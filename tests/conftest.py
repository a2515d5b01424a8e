#
# Test harness — the analog of the reference's local-mode multi-GPU trick
# (tests/conftest.py:34-70: a Spark local[N] session where partition-id ->
# GPU-id exercises the real multi-rank NCCL path on one node).  Here an
# 8-device virtual CPU mesh (`xla_force_host_platform_device_count`)
# exercises the real SPMD sharding + collective path without TPU hardware;
# the `num_workers` fixture parameterizes 1..4 ranks like `gpu_number`.
#
import os
import sys

# Must run before jax is imported (it reads both variables then).  Force
# CPU even where the machine has a TPU: tests validate the SPMD sharding
# path on an 8-device virtual mesh, not single-chip numerics.
#
# SRML_TEST_PLATFORM=tpu opts out of the CPU pin and runs the suite against
# the machine's accelerator: the hardware-evidence pass.  Mesh sizes > the
# real device count are skipped by the num_workers fixture.
_platform = os.environ.get("SRML_TEST_PLATFORM", "cpu")
if _platform == "cpu":
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Wedge guard (the hang doctor's out-of-process fallback for CI): with
# WEDGE_GUARD_S=<seconds> set, a pytest process that is still running
# after the deadline dumps ALL thread stacks to stderr and exits
# nonzero — a wedged suite (the PR-14 deadlock class) leaves evidence
# and a red build instead of silently burning the CI window until the
# outer `timeout` SIGKILLs it.  ci/test.sh arms it for every batch and
# smoke (ci/wedge/sitecustomize.py arms non-pytest invocations); unset
# or 0 disables.  The in-process hang doctor (telemetry/hang_doctor.py)
# stays the first line — it fires earlier and attaches the lock
# wait-for graph — this guard is the backstop that cannot itself
# deadlock, because faulthandler dumps from a C watchdog thread.
_wedge_s = float(os.environ.get("WEDGE_GUARD_S", "0") or 0)
if _wedge_s > 0:
    import faulthandler

    faulthandler.dump_traceback_later(_wedge_s, exit=True)


@pytest.fixture(params=[1, 2, 4])
def num_workers(request):
    """Mesh sizes exercised per test (reference `gpu_number` fixture)."""
    if _platform != "cpu" and request.param > jax.device_count():
        # only the real-hardware pass may shrink coverage; in the CPU run a
        # too-small device count means the 8-device virtual mesh failed to
        # come up, and the tests should fail loudly, not skip
        pytest.skip(
            f"mesh size {request.param} exceeds the {jax.device_count()} "
            "real device(s) (SRML_TEST_PLATFORM != cpu)"
        )
    return request.param


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False, help="run slow tests"
    )


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: mark test as slow to run")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip_slow = pytest.mark.skip(reason="need --runslow option to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)
