"""Tier-1's view of ``ftbench/tests/test_ftbench_ssm.py``: tier-1 collects
``tests/`` only, and the benchmark's own tests guard nothing unless it runs
them (ROADMAP D3).  The tests live with the benchmark; this file imports them
and shows one of them the list as it was when it was written (the file under
``ftbench/`` is the benchmark's, and only a ``benchmark`` issue may edit it:
PERF.md section 7)."""

import json

import pytest

from ftbench.tests import test_ftbench_ssm as theirs
from ftbench.tests.test_ftbench_ssm import *  # noqa: F401,F403

# PR 36 appended its reader after PR 35's six, PR 37 the eleven that share
# out the compiled step by its named parts (``ftbench/device_scopes.py``),
# PR 40 the share of collectives the ring averaged itself, PR 41 the six of
# the cell ``trinitymini-ws1-seq16k``, PR 42 how full the experts' buffer is, PR 44 the share of the
# four-chip cell's gradient bytes that go from the shards into the bucket, PR 46 the two of the pieces a leaf
# over the bucket cap crosses in, PR 47 the share of the rings' bytes that crossed off lane 0
LATER_READERS = (
    "heal_serve_ahead_pct",
    "xla_mixer_proj_ms", "xla_mixer_glue_ms", "xla_ffn_ms", "xla_stream_ms", "xla_head_ms", "moe_route_ms",
    "moe_dispatch_ms", "xla_layer_scan_ms", "optimizer_ms", "step_remat_ms", "xla_unscoped_ms",
    "normalize_in_ring_pct",
    "swa_flash_ms", "swa_flash_roofline", "swa_full_flash_roofline", "swa_window_over_full_pct",
    "swa_moe_gmm_roofline", "swa_step_mfu_pct",
    "moe_buffer_fill_pct",
    "d2h_direct_pct.hsdp",
    "d2h_split_pct", "sync_second_submit_ms",
    "ring_striped_pct",
)
# PR 41 appended a configuration and a cell after PR 35's, and the cell's name to the lists PR 35's joined
LATER_CELLS = ("trinitymini-ws1-seq16k",)


def test_the_cell_and_the_lists_it_joined(monkeypatch):  # noqa: F811
    """Theirs holds PR 35's six readers to be the LAST entries of
    ``per_layer`` and its cell and configuration the last of theirs; a later
    PR appends, so here they are the last before the later ones, and the
    later ones are the last."""
    load = json.load

    def without_the_later_ones(f):
        bench = load(f)
        if isinstance(bench, dict) and "per_layer" in bench:
            later = bench["per_layer"][-len(LATER_READERS):]
            assert [m["name"] for m in later] == list(LATER_READERS)
            bench["per_layer"] = bench["per_layer"][: -len(LATER_READERS)]
            assert [w["name"] for w in bench["workloads"][-len(LATER_CELLS):]] == list(LATER_CELLS)
            configs = {w["config"] for w in bench["workloads"][-len(LATER_CELLS):]}
            assert {c["name"] for c in bench["configs"][-len(configs):]} == configs
            bench["workloads"] = bench["workloads"][: -len(LATER_CELLS)]
            bench["configs"] = bench["configs"][: -len(configs)]
            for metric in bench["end_to_end"] + bench["per_layer"]:
                if "workloads" in metric:
                    metric["workloads"] = [w for w in metric["workloads"] if w not in LATER_CELLS]
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
