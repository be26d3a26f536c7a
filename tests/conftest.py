"""Shared pytest configuration.

Registers the ``requires_accel`` marker: tests that exercise real TPU/GPU
compilation paths (non-interpret Pallas lowering, full-slice meshes) carry it
and are skipped on CPU-only hosts, so the full suite collects green anywhere
while hardware CI still runs them.

Bounds every test's call at ``TEST_TIME_LIMIT_S``.  Past it, SIGALRM dumps
every thread's stack into the test's captured stderr and fails the test; the
worker lives on and the run reaches its end.  A test stuck where Python never
runs the handler (inside one C call) is ended ``BACKSTOP_S`` later by
faulthandler, which prints the stacks and exits the process.  That is a last
resort: under ``--dist loadfile`` xdist re-queues the rest of the file, the
stuck test included, on the replacement worker.
"""
import faulthandler
import os
import signal

import pytest

#: Seconds one test's call may take (about 2x the slowest test under -n 6).
TEST_TIME_LIMIT_S = 300
#: Grace after the limit before a test that ignores the signal is killed.
BACKSTOP_S = 60

_stderr_fd = pytest.StashKey[int]()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "requires_accel: needs a real TPU/GPU device; skipped on CPU-only "
        "hosts (interpret-mode equivalents still run everywhere)")
    # Output capture redirects fd 2 while a test runs; the backstop's dump
    # goes to a duplicate of the real stderr taken here, outside any test.
    config.stash[_stderr_fd] = os.dup(2)


def pytest_unconfigure(config):
    os.close(config.stash[_stderr_fd])


def _past_time_limit(signum, frame):
    faulthandler.dump_traceback(all_threads=True)
    pytest.fail(f"test ran past the {TEST_TIME_LIMIT_S} s per-test limit")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    previous = signal.signal(signal.SIGALRM, _past_time_limit)
    signal.setitimer(signal.ITIMER_REAL, TEST_TIME_LIMIT_S)
    faulthandler.dump_traceback_later(
        TEST_TIME_LIMIT_S + BACKSTOP_S, exit=True,
        file=item.config.stash[_stderr_fd])
    yield
    faulthandler.cancel_dump_traceback_later()
    signal.setitimer(signal.ITIMER_REAL, 0)
    signal.signal(signal.SIGALRM, previous)


def _accel_present() -> bool:
    try:
        import jax
        return jax.devices()[0].platform != "cpu"
    except Exception:
        return False


def pytest_collection_modifyitems(config, items):
    if _accel_present():
        return
    skip = pytest.mark.skip(
        reason="requires a TPU/GPU accelerator; CPU-only host")
    for item in items:
        if "requires_accel" in item.keywords:
            item.add_marker(skip)
