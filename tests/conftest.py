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
