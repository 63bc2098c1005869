"""Test configuration.

Tests run on the CPU: a virtual 8-device CPU mesh for the sharding/mesh
tests, set before jax initializes.  The v5e compile tests
(test_tpu_compile.py) describe the chip without attaching one.  XLA_FLAGS
carries the device count to the child processes some tests start.

Every test runs under a time limit that ends it, stacks printed, even
when it is blocked in native code, so that a parallel run (``-n 6
--dist loadfile``) always reaches its end.
"""

import faulthandler
import hashlib
import os
import sys
import tempfile

_FLAG = "--xla_force_host_platform_device_count=8"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _x64_isolation():
    """Restore jax_enable_x64 after every test: the device epoch kernel
    flips it globally (per_epoch_jax._build_kernel), and under random
    test ordering that made the Pallas interpret tests compile under
    x64 — pathologically slow (the r3 suite 'hangs')."""
    before = jax.config.jax_enable_x64
    yield
    if jax.config.jax_enable_x64 != before:
        jax.config.update("jax_enable_x64", before)


# -- the per-test time limit -----------------------------------------------

TIME_LIMIT_S = 300
# `slow` tests are out of the parallel run; the longest of them wait up
# to 5,400 s on one subprocess
SLOW_TIME_LIMIT_S = 6000

_LIMIT_STREAM = None


def pytest_configure(config):
    global _LIMIT_STREAM
    # the limit's stack dump goes to the real stderr: fd 2 is redirected
    # into pytest's capture file while a test runs
    _LIMIT_STREAM = os.fdopen(os.dup(sys.stderr.fileno()), "w")


def _crash_flag(item):
    """A file that exists while ``item`` runs on an xdist worker.  One
    left behind means a worker died in the test (its limit or a crash),
    and xdist hands the test out again with the rest of its file."""
    uid = os.environ.get("PYTEST_XDIST_TESTRUNUID")
    if uid is None:
        return None
    d = os.path.join(tempfile.gettempdir(), f"pytest-limit-{uid}")
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, hashlib.sha256(item.nodeid.encode()).hexdigest())


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    """Arm the limit (the test's ``time_limit`` marker, else
    ``TIME_LIMIT_S`` or, for ``slow`` tests, ``SLOW_TIME_LIMIT_S``) over
    setup, call and teardown.  On expiry faulthandler's own thread,
    which needs no GIL, prints every thread's stack and ends the
    process: under xdist the worker is replaced, the test is reported
    as crashed, and the file's remaining tests run on."""
    marker = item.get_closest_marker("time_limit")
    if marker:
        limit = marker.args[0]
    elif item.get_closest_marker("slow"):
        limit = SLOW_TIME_LIMIT_S
    else:
        limit = TIME_LIMIT_S
    flag = _crash_flag(item)
    if flag is not None:
        item.ended_a_worker = os.path.exists(flag)
        open(flag, "w").close()
    faulthandler.dump_traceback_later(limit, exit=True, file=_LIMIT_STREAM)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        if flag is not None:
            os.remove(flag)


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_setup(item):
    if getattr(item, "ended_a_worker", False):
        pytest.fail("ended a worker before (its time limit or a crash); "
                    "not run again")
