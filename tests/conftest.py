"""Shared test config.

Tests run on a virtual 8-device CPU mesh (the reference's analog is running
everything over Gloo/localhost on the CPU CI runner,
``.github/workflows/unittest.yaml``); multi-replica scenarios are threads in
one process sharing a lighthouse, mirroring the reference's
threads-as-replicas harness (``torchft/manager_integ_test.py:340-380``).
"""

import contextlib
import faulthandler
import os
import signal
import sys
import tempfile
import threading
import traceback

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# Watchdog off under tests: a deliberately-wedged timeout test must not nuke
# the pytest process (reference mocks sys.exit the same way,
# torchft/futures_test.py:102).
os.environ.setdefault("TORCHFT_WATCHDOG_TIMEOUT_SEC", "0")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import pytest  # noqa: E402

# Tests run on the virtual CPU mesh whatever the host offers.
jax.config.update("jax_platforms", "cpu")


# A compile case (``tests/test_ftbench_compile_<configuration>.py``) compiles
# a cell's whole step for a described v5e: 15-230 s each and three cores
# wide.  pytest-xdist's ``--dist load`` hands a worker a RUN of consecutive
# tests (a twelfth of what is pending: 116 when it came to these in the
# files' order), so one worker got every compile case 400 s into the run with
# a hundred quick tests behind them, ended 400 s after the other five, and a
# run cut at its limit lost that whole run of tests at once (2,015 counted of
# 2,119: PR 54).  So the step cases go to the front of the collection, where
# the workers take them from the start, and nothing else is reordered.  (The
# forward checks, a second run a quarter in until PR 55, are ``slow`` now:
# ``tests/_ftbench_view.py`` ``compile_cases``.)  The run is bound by the
# cores (ROADMAP.md D13); it ends with all six workers on the collection's
# last tests, so a cut costs the last seconds' tests and no more.
def _is_compile_case(item):
    return os.path.basename(str(item.fspath)).startswith("test_ftbench_compile_")


def pytest_collection_modifyitems(config, items):
    items.sort(key=lambda item: not _is_compile_case(item))  # stable: each kind keeps its order


@pytest.fixture(scope="session", autouse=True)
def _the_runs_directory(tmp_path_factory):
    """Where ``tests/_once.py`` keeps what is made once a run."""
    from tests import _once

    base = tmp_path_factory.getbasetemp()
    _once.directory = base.parent if os.environ.get("PYTEST_XDIST_WORKER") else base


# Every test has a limit of its own (pytest-timeout is not installed): one
# that hangs costs that one test and names itself, where it used to cost the
# run its 1,470 s and its log named nobody (ROADMAP.md D13 (b): 900 s at 0 %
# CPU in ``Manager.wait_quorum``, seen once).
#
# A compile case: the longest step case took 301 s under load, six compiling
# side by side (D13 (a)); twice that.
COMPILE_CASE_LIMIT_S = 600.0
# Every other test: three times the longest under load, which is
# ``test_chaos.py``'s paced SIGSTOP drill at 80 s (87 s before PR 55).
TEST_LIMIT_S = 240.0


def limit_for(item):
    return COMPILE_CASE_LIMIT_S if _is_compile_case(item) else TEST_LIMIT_S


@contextlib.contextmanager
def time_limit(seconds, what):
    """Fails the body, through an interval timer on the main thread, once it
    has run ``seconds``: the failure carries every thread's stack
    (``faulthandler``), stderr gets them too, and the run goes on.  The timer
    is off and SIGALRM's handler the one from before when the body ends,
    either way.  (A main thread inside a C call that does not return fails
    when the call does.)"""
    if threading.current_thread() is not threading.main_thread():
        yield  # signals are the main thread's; xdist runs tests there
        return

    def expired(signum, frame):
        with tempfile.TemporaryFile(mode="w+") as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            stacks = f.read()
        # the body's own stack first and by itself: ``faulthandler`` stops after a
        # hundred threads, the main one last, and a worker late in a run has more
        here = "".join(traceback.format_stack(frame))
        message = f"{what} ran past its limit of {seconds:g} s (tests/conftest.py), here:\n{here}every thread's stack:\n{stacks}"
        sys.stderr.write(message)
        pytest.fail(message, pytrace=False)

    handler_before = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, handler_before)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    # a module's fixtures are made in the first test's set-up
    with time_limit(limit_for(item), f"the set-up of {item.nodeid}"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with time_limit(limit_for(item), item.nodeid):
        return (yield)
