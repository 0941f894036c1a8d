"""Tier-1's compile of ``granite-4.0-h-micro-vp4-1x1`` for a described v5e, on
a worker of its own (``tests/_ftbench_view.py``, ``compile_cases``, says why)."""

from ftbench.tests.test_ftbench_compile import no_compile_cache, topo  # noqa: F401
from tests._ftbench_view import compile_cases

test_step_compiles_for_v5e, test_forward_check_compiles_for_v5e = compile_cases("granite-4.0-h-micro-vp4-1x1")
