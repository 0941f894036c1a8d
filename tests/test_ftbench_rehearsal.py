"""Tier-1's view of ``ftbench/tests/test_ftbench_rehearsal.py`` (ROADMAP D3):
the CPU walk-through of every cell.  ``test_rehearsal_walks_the_cell`` is
taken as it is for the untraced runs; its two traced cases hold
``would_report`` to the five names PR 23's readers gave with ``==``, and the
program's spans now give the rehearsal more to report, so those two are run
here against the sets of today (the file under ``ftbench/`` is the
benchmark's, and only a ``benchmark`` issue may edit it: PERF.md section 7)."""

import pytest

from ftbench.tests import test_ftbench_rehearsal as theirs
from ftbench.tests.test_ftbench_program_spans import KILL_READINGS, READINGS
from ftbench.tests.test_ftbench_rehearsal import (  # noqa: F401
    test_benchmark_alone_without_the_program_fails,
    test_no_chip_is_exit_1_and_no_result,
    test_rehearsal_walks_the_four_chip_cell_a_later_pr_adds,
)

_CASES = theirs.test_rehearsal_walks_the_cell.pytestmark[0].args[1]
# sync_normalize_ms is PR 27's reader of PR 26's span tpuft/manager/normalize
_NEW = {
    "mistral7b-ddp2-steady": set(READINGS) | {"sync_normalize_ms"},
    "mistral7b-ddp2-kill": set(KILL_READINGS),
}


@pytest.mark.parametrize(
    "cell,trace,devices,expects",
    [(c, t, d, e | _NEW[c] if t else e) for c, t, d, e in _CASES],
)
def test_rehearsal_walks_the_cell(cell, trace, devices, expects):
    theirs.test_rehearsal_walks_the_cell(cell, trace, devices, expects)
