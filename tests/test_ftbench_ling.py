"""Tier-1's view of ``ftbench/tests/test_ftbench_ling.py``: tier-1 collects
``tests/`` only, and the benchmark's own tests guard nothing unless it runs
them (ROADMAP D3).  The tests live with the benchmark; this file imports them.

One of them is held here in a corrected form.  ``test_new_readers_list_this_cell_alone``
of the benchmark's file holds the benchmark to PR 29's size (four cells,
three configurations) and three of Ling's readers to Ling's cell alone; a
PR that adds a cell may not edit that file, and PR 33's cell joined
``moe_gmm_ms``, ``moe_rows_here_per_step`` and ``moe_load_max_over_mean``,
which read the trace and MOE_ROUTE alone.  The version below asks that Ling's
cell is IN each list and is otherwise that test."""

import pytest

from ftbench.tests import test_ftbench_ling as theirs
from ftbench.tests.test_ftbench_ling import *  # noqa: F401,F403
from ftbench.tests.test_ftbench_ling import CELL, NEW_READERS, ROOT, json, os

ANY_EXPERT_CELL = ("moe_gmm_ms", "moe_rows_here_per_step", "moe_load_max_over_mean")


def test_new_readers_list_this_cell_alone():  # noqa: F811 — replaces the imported one (see above)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert CELL in listed[name]["workloads"] and listed[name]["moves"] == "tokens_per_s_per_chip"
        assert name in ANY_EXPERT_CELL or listed[name]["workloads"] == [CELL]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert entry["config"] in [c["name"] for c in bench["configs"]]


# PR 42: the traced walk also reports how full the experts' buffer is
# (``moe_buffer_fill_pct``, from MOE_ROUTE's ``buffer_rows``)
@pytest.mark.parametrize(
    "trace,expects",
    [(t, e | {"moe_buffer_fill_pct"} if t else e) for t, e in theirs.test_rehearsal_walks_the_cell.pytestmark[0].args[1]],
)
def test_rehearsal_walks_the_cell(trace, expects):  # noqa: F811
    theirs.test_rehearsal_walks_the_cell(trace, expects)
