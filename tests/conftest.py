"""Test configuration: force an 8-device virtual CPU platform so sharding
paths are exercised without TPU hardware (SURVEY.md §4: the TPU analog of
the reference's 2-rank MPI CI is multi-device pjit on CPU), and give
every test a time limit of its own.

The actual pinning dance lives in tests/_cpu.py so ad-hoc scripts can
reuse it (``import tests._cpu``); it must run before any test builds an
array.
"""

import faulthandler
import os
import signal

import jax
import pytest

import tests._cpu  # noqa: F401  (side effect: pin CPU platform)

assert jax.devices()[0].platform == "cpu"
assert len(jax.devices()) == 8, (
    "expected 8 virtual CPU devices; XLA_FLAGS was read too late"
)

# Seconds one test's set-up or call may take: three times the slowest
# test of the driver's command (CHANGES.md, PR 31). A hang then costs
# one test and names it, where the run's own limit cuts the run and
# names nothing. Every subprocess ``timeout=`` under tests/ is below it.
TEST_LIMIT_S = 420

_stderr = None


def pytest_configure(config):
    # Capture is suspended here, so fd 2 is the run's own stderr: the
    # stack dump reaches the log even if the test never returns.
    global _stderr
    _stderr = os.fdopen(os.dup(2), "w")


def _limited(item):
    def past_limit(signum, frame):
        pytest.fail(
            f"{item.nodeid} ran past the {TEST_LIMIT_S} s limit of "
            "tests/conftest.py (all threads' stacks are on stderr)"
        )

    previous = signal.signal(signal.SIGALRM, past_limit)
    # The dump comes from faulthandler's watchdog thread, which writes
    # even while the main thread sits in native code; the Python handler
    # that fails the test has to wait for the interpreter, so it is set
    # a second later and the dump is always there first.
    faulthandler.dump_traceback_later(TEST_LIMIT_S, file=_stderr)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S + 1)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    return (yield from _limited(item))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    return (yield from _limited(item))
