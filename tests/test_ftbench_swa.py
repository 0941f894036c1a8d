"""Tier-1's view of ``ftbench/tests/test_ftbench_swa.py``: tier-1 collects
``tests/`` only, and the benchmark's own tests guard nothing unless it runs
them (ROADMAP D3).  The tests live with the benchmark; this file imports them
and shows one of them the lists as they were when it was written (the file
under ``ftbench/`` is the benchmark's, and only a ``benchmark`` issue may edit
it: PERF.md section 7)."""

import json

import pytest

from ftbench.tests import test_ftbench_swa as theirs
from ftbench.tests.test_ftbench_swa import *  # noqa: F401,F403

# PR 42 appended how full the experts' buffer is, which lists this cell too
LATER_READERS = ("moe_buffer_fill_pct",)
# PR 44 appended a reader of the four-chip cell, PR 46 two of the cells with a
# replica dimension and PR 47 one more of those, which do not list this one
AFTER_THOSE = ("d2h_direct_pct.hsdp", "d2h_split_pct", "sync_second_submit_ms", "ring_striped_pct")


def test_the_cell_and_the_lists_it_joined(monkeypatch):  # noqa: F811
    """Theirs holds that every metric that lists the cell is one PR 41 wrote
    or joined; a later PR appends, so here the later ones are the last of
    ``per_layer``, list the cell, and are not shown to theirs."""
    load = json.load

    def without_the_later_ones(f):
        bench = load(f)
        if isinstance(bench, dict) and "per_layer" in bench:
            after = bench["per_layer"][-len(AFTER_THOSE):]
            assert [m["name"] for m in after] == list(AFTER_THOSE)
            assert not any(theirs.CELL in m["workloads"] for m in after)
            bench["per_layer"] = bench["per_layer"][: -len(AFTER_THOSE)]
            later = bench["per_layer"][-len(LATER_READERS):]
            assert [m["name"] for m in later] == list(LATER_READERS)
            assert all(theirs.CELL in m["workloads"] for m in later)
            bench["per_layer"] = bench["per_layer"][: -len(LATER_READERS)]
        return bench

    monkeypatch.setattr(theirs.json, "load", without_the_later_ones)
    theirs.test_the_cell_and_the_lists_it_joined()


# PR 42: the traced walk also reports how full the experts' buffer is
# (``moe_buffer_fill_pct``, from MOE_ROUTE's ``buffer_rows``)
@pytest.mark.parametrize(
    "trace,expects",
    [(t, e | {"moe_buffer_fill_pct"} if t else e) for t, e in theirs.test_rehearsal_walks_the_cell.pytestmark[0].args[1]],
)
def test_rehearsal_walks_the_cell(trace, expects):  # noqa: F811
    theirs.test_rehearsal_walks_the_cell(trace, expects)
