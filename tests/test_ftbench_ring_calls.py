"""Tier-1's view of ``ftbench/tests/test_ftbench_ring_calls.py``: the
benchmark's tests, imported (``tests/_ftbench_view.py`` says why, and the rule
a view keeps)."""

from ftbench.tests.test_ftbench_ring_calls import *  # noqa: F401,F403
