"""Tier-1's view of ``ftbench/tests/test_ftbench_compile.py``
(``tests/_ftbench_view.py`` says what a view is).  The two compile cases of a
configuration run in ``tests/test_ftbench_compile_<configuration>.py``, a file
a configuration (``compile_cases`` says why); this one runs the file's one
test without a configuration, the cases of every configuration of
``BENCHMARK.json`` that has NO file of its own (none today: a later PR's is
compiled here with no edit, and may be given a file to keep the pole short),
and holds that the files together compile every configuration exactly once.
The forward check is ``slow`` (``compile_cases``): tier-1 runs the step case."""

import glob
import importlib
import os

from ftbench.tests import test_ftbench_compile as theirs
from ftbench.tests.test_ftbench_compile import (  # noqa: F401
    no_compile_cache,
    test_a_group_of_several_chips_is_among_the_configurations,
    topo,
)
from tests._ftbench_view import compile_cases

CASES = ("test_step_compiles_for_v5e", "test_forward_check_compiles_for_v5e")
_OWN_PATHS = sorted(glob.glob(os.path.join(os.path.dirname(os.path.abspath(__file__)), "test_ftbench_compile_*.py")))
_OWN_FILES = [importlib.import_module("tests." + os.path.basename(path)[:-3]) for path in _OWN_PATHS]


def _configurations(case):
    (over,) = [mark for mark in case.pytestmark if mark.name == "parametrize"]
    return over.args[1]


# the configurations each case is parametrised over in the files of their own
_IN_A_FILE = {
    case: [name for module in _OWN_FILES if hasattr(module, case) for name in _configurations(getattr(module, case))]
    for case in CASES
}
REST = [name for name in theirs.CONFIG_NAMES if not any(name in names for names in _IN_A_FILE.values())]
if REST:  # an empty parametrisation would show as two skips in every run
    test_step_compiles_for_v5e, test_forward_check_compiles_for_v5e = compile_cases(*REST)


def test_every_configuration_is_compiled_exactly_once_across_the_files():
    for case in CASES:
        assert sorted(_IN_A_FILE[case] + REST) == sorted(theirs.CONFIG_NAMES), case


def test_the_collection_hands_the_step_cases_out_from_the_start():
    """``--dist load`` gives a worker consecutive tests: ``tests/conftest.py``
    puts the compile cases first (the step cases are all of them in tier-1:
    the forward checks are ``slow``) and leaves every other test in its
    order."""
    from types import SimpleNamespace

    from tests import conftest

    names = (
        [f"tests/test_a.py::{i}" for i in range(200)]
        + [f"tests/{os.path.basename(path)}::{CASES[0]}[x]" for path in _OWN_PATHS]
        + ["tests/test_ftbench_compile.py::rest"]
        + [f"tests/test_z.py::{i}" for i in range(1500)]
    )
    items = [SimpleNamespace(fspath=n.split("::")[0], name=n.split("::")[1], id=n) for n in names]
    conftest.pytest_collection_modifyitems(None, items)
    placed = [item.id for item in items]
    configs = len(_OWN_PATHS)
    assert all("_compile_" in n and CASES[0] in n for n in placed[:configs])
    assert placed[configs:] == [n for n in names if "_compile_" not in n]
    # a selection of a few tests keeps them all
    few = items[:3]
    conftest.pytest_collection_modifyitems(None, few)
    assert len(few) == 3


def test_the_forward_checks_are_slow_and_the_step_cases_are_not():
    """Tier-1 (``-m 'not slow'``) compiles every configuration's step; its
    forward check is run by hand (``tests/_ftbench_view.py`` ``compile_cases``)."""
    for module in _OWN_FILES:
        marks = {case: {m.name for m in getattr(module, case).pytestmark} for case in CASES}
        assert marks == {CASES[0]: {"parametrize"}, CASES[1]: {"parametrize", "slow"}}, module.__name__
