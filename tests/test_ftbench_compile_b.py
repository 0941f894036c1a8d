"""The second half of tier-1's view of ``ftbench/tests/test_ftbench_compile.py``
(``tests/test_ftbench_compile.py`` says why there are two): the
configurations ``THERE`` names, on a worker of their own."""

import pytest

from ftbench.tests import test_ftbench_compile as theirs
from ftbench.tests.test_ftbench_compile import no_compile_cache, topo  # noqa: F401
from tests.test_ftbench_compile import THERE


@pytest.mark.parametrize("config_name", THERE)
def test_step_compiles_for_v5e(topo, no_compile_cache, monkeypatch, config_name):  # noqa: F811
    theirs.test_step_compiles_for_v5e(topo, no_compile_cache, monkeypatch, config_name)


@pytest.mark.parametrize("config_name", THERE)
def test_forward_check_compiles_for_v5e(topo, no_compile_cache, monkeypatch, config_name):  # noqa: F811
    theirs.test_forward_check_compiles_for_v5e(topo, no_compile_cache, monkeypatch, config_name)
