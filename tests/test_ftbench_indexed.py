"""Tier-1's view of ``ftbench/tests/test_ftbench_indexed.py``: tier-1 collects
``tests/`` only, and the benchmark's own tests guard nothing unless it runs
them (ROADMAP D3).  The tests live with the benchmark; this file imports them."""

import pytest

from ftbench.tests import test_ftbench_indexed as theirs
from ftbench.tests.test_ftbench_indexed import *  # noqa: F401,F403


# PR 42: the traced walk also reports how full the experts' buffer is
# (``moe_buffer_fill_pct``, from MOE_ROUTE's ``buffer_rows``)
@pytest.mark.parametrize(
    "trace,expects",
    [(t, e | {"moe_buffer_fill_pct"} if t else e) for t, e in theirs.test_rehearsal_walks_the_cell.pytestmark[0].args[1]],
)
def test_rehearsal_walks_the_cell(trace, expects):  # noqa: F811
    theirs.test_rehearsal_walks_the_cell(trace, expects)
