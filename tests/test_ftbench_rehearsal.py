"""Tier-1's view of ``ftbench/tests/test_ftbench_rehearsal.py``
(``tests/_ftbench_view.py`` says what a view is): the CPU walk-through of every
cell.  ``test_rehearsal_walks_the_cell`` is taken as it is for the untraced
runs; a traced case holds there the five names PR 23's readers gave, and here,
in the same walk, every reader of today that a CPU can give
(``walk_reports``).  A traced case reads the cell's ONE traced walk
(``traced_walk``), which ``tests/test_ftbench_program_spans.py`` reads too: made
in a copy with an ``out/`` of its own, where two traced walks into the repo's
``ftbench/out`` cleared each other's trace (PR 54).  A walk that fails says
what it waited on."""

import pytest

from ftbench.spec import load_cell as theirs_cell
from ftbench.tests import test_ftbench_rehearsal as theirs
from ftbench.tests.test_ftbench_rehearsal import (  # noqa: F401
    test_benchmark_alone_without_the_program_fails,
    test_no_chip_is_exit_1_and_no_result,
    test_rehearsal_walks_the_four_chip_cell_a_later_pr_adds,
)
from tests._ftbench_view import traced_walk, walk_reports

_CASES = theirs.test_rehearsal_walks_the_cell.pytestmark[0].args[1]


def _what_it_waited_on(cell, flight_dir, root=theirs.ROOT):
    """For the log of a walk that failed: the steps that were voted down or
    took long (replica, life, step, committed, seconds), from the run's
    series file under ``root``, and the errors its Managers funnelled, from
    the flight rings they dump under ``TORCHFT_FLIGHT_DIR``."""
    import glob
    import json
    import os

    said = []
    series = glob.glob(os.path.join(str(root), "ftbench", "out", f"{cell}-*.json"))
    if series:
        with open(max(series, key=os.path.getmtime)) as f:
            steps = json.load(f)["series"]
        said += [
            ("step", r["replica"], r.get("life"), r["step"], r["committed"], round(r["wall_s"], 3))
            for r in steps if not r["committed"] or r["wall_s"] > 20.0
        ]
    for path in sorted(glob.glob(os.path.join(str(flight_dir), "*.jsonl"))):
        with open(path) as f:
            for line in f:
                if '"ERROR"' in line:
                    said.append((os.path.basename(path), line.strip()[:400]))
    return "\n".join(map(repr, dict.fromkeys(said)))


@pytest.mark.parametrize(
    "cell,trace,devices,expects",
    [(c, t, d, e | walk_reports(c) if t else e) for c, t, d, e in _CASES],
)
def test_rehearsal_walks_the_cell(cell, trace, devices, expects, tmp_path, monkeypatch):
    # the walk's subprocess inherits this: every error a Manager funnels
    # dumps its flight ring there, and a failure below says what it was
    monkeypatch.setenv("TORCHFT_FLIGHT_DIR", str(tmp_path))
    walked = (tmp_path, theirs.ROOT)
    if trace:
        root, done = traced_walk(cell)
        assert devices == max(2, theirs_cell(cell).chips)  # what that walk ran on
        monkeypatch.setattr(theirs, "_run", lambda args, **k: done)
        walked = (root / "flight", root)
    try:
        theirs.test_rehearsal_walks_the_cell(cell, trace, devices, expects)
    except AssertionError as e:
        raise AssertionError(f"{e}\nwhat the walk waited on:\n{_what_it_waited_on(cell, *walked)}") from e
