"""Tier-1's view of ``ftbench/tests/test_ftbench_swa.py``: tier-1 collects
``tests/`` only, and the benchmark's own tests guard nothing unless it runs
them (ROADMAP D3).  The tests live with the benchmark; this file imports them."""

from ftbench.tests.test_ftbench_swa import *  # noqa: F401,F403
