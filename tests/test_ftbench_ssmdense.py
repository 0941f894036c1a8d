"""Tier-1's view of ``ftbench/tests/test_ftbench_ssmdense.py``: the benchmark's
tests, imported (``tests/_ftbench_view.py`` says why, and the rule a view
keeps).  The traced walk of the cell holds the readers of today."""

from ftbench.tests import test_ftbench_ssmdense as theirs
from ftbench.tests.test_ftbench_ssmdense import *  # noqa: F401,F403
from tests._ftbench_view import cell_walk

test_rehearsal_walks_the_cell = cell_walk(theirs)  # noqa: F811 — theirs, one walk a case
