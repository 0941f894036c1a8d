"""Shared test config.

Tests run on a virtual 8-device CPU mesh (the reference's analog is running
everything over Gloo/localhost on the CPU CI runner,
``.github/workflows/unittest.yaml``); multi-replica scenarios are threads in
one process sharing a lighthouse, mirroring the reference's
threads-as-replicas harness (``torchft/manager_integ_test.py:340-380``).
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# Watchdog off under tests: a deliberately-wedged timeout test must not nuke
# the pytest process (reference mocks sys.exit the same way,
# torchft/futures_test.py:102).
os.environ.setdefault("TORCHFT_WATCHDOG_TIMEOUT_SEC", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# Tests run on the virtual CPU mesh whatever the host offers.
jax.config.update("jax_platforms", "cpu")


# A compile case (``tests/test_ftbench_compile_<configuration>.py``) compiles
# a cell's whole step for a described v5e: 15-230 s each and three cores
# wide, 1,450 s the eighteen.  pytest-xdist's ``--dist load`` hands a worker
# a RUN of consecutive tests (a twelfth of what is pending: 116 when it came
# to these in the files' order), so one worker got all eighteen 400 s into
# the run with a hundred quick tests behind them, ended 400 s after the
# other five, and a run cut at its limit lost that whole run of tests at
# once (2,015 counted of 2,119: PR 54).  Two runs of nine instead, from the
# start: the step cases at the front of the collection and the forward
# checks a quarter in, where the first worker to end its first share picks
# them up.  The run is bound by the cores either way and no shorter for it
# (ROADMAP.md D13); it ends with all six workers on the collection's last
# tests, so a cut costs the last seconds' tests and no more.
def pytest_collection_modifyitems(config, items):
    def compiles(item):
        return os.path.basename(str(item.fspath)).startswith("test_ftbench_compile_")

    cases = [item for item in items if compiles(item)]
    rest = [item for item in items if not compiles(item)]
    quarter = len(items) // 4
    rest[quarter:quarter] = [c for c in cases if c.name.startswith("test_forward_check")]
    rest[:0] = [c for c in cases if not c.name.startswith("test_forward_check")]
    items[:] = rest
