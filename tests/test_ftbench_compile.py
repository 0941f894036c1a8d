"""Tier-1's view of ``ftbench/tests/test_ftbench_compile.py``: tier-1 collects
``tests/`` only, and the benchmark's own tests guard nothing unless it runs
them (ROADMAP D3).  The tests live with the benchmark; this file imports them.

Every case compiles a cell's whole step for a described v5e, one to three
minutes a configuration, and tier-1 hands a FILE to one worker: with seven
configurations the file alone took 671 s (PR 41), the longest pole of a run
that is cut at 1,470 s.  So the cases are the benchmark's, unchanged, in two
files: ``test_ftbench_compile_b.py`` runs the configurations it names
(``THERE``), this one every other, a later PR's new one included."""

import pytest

from ftbench.tests import test_ftbench_compile as theirs
from ftbench.tests.test_ftbench_compile import *  # noqa: F401,F403

THERE = ("trinity-mini-ep8-1x1", "keye-vl-2.0-30b-a3b-ep8-1x1")
HERE = [name for name in theirs.CONFIG_NAMES if name not in THERE]


def test_the_two_files_run_every_configuration_once():
    assert set(THERE) < set(theirs.CONFIG_NAMES) and len(HERE) + len(THERE) == len(theirs.CONFIG_NAMES)


@pytest.mark.parametrize("config_name", HERE)
def test_step_compiles_for_v5e(topo, no_compile_cache, monkeypatch, config_name):  # noqa: F811
    theirs.test_step_compiles_for_v5e(topo, no_compile_cache, monkeypatch, config_name)


@pytest.mark.parametrize("config_name", HERE)
def test_forward_check_compiles_for_v5e(topo, no_compile_cache, monkeypatch, config_name):  # noqa: F811
    theirs.test_forward_check_compiles_for_v5e(topo, no_compile_cache, monkeypatch, config_name)
