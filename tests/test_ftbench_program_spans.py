"""Tier-1's view of ``ftbench/tests/test_ftbench_program_spans.py``: tier-1 collects
``tests/`` only, and the benchmark's own tests guard nothing unless it runs
them (ROADMAP D3).  The tests live with the benchmark; this file imports them
and adds what a later PR's reader needs (a file under ``ftbench/`` is the
benchmark's, and only a ``benchmark`` issue may edit it: PERF.md section 7)."""

import json
import os

import pytest

from ftbench.tests import test_ftbench_program_spans as theirs
from ftbench.tests.test_ftbench_program_spans import *  # noqa: F401,F403

# PR 27: the division by the participant count, two spans of 40 ms a step
LATER_READINGS = {"sync_normalize_ms": 80.0}
# PR 29: the readers of the cell ling3flash-ws1-seq8k, appended after it
# (their own tests: ftbench/tests/test_ftbench_ling.py)
LING_READERS = (
    "kda_fwd_ms", "kda_bwd_ms", "kda_roofline", "mla_flash_ms", "mla_flash_roofline", "moe_gmm_ms",
    "moe_gmm_roofline", "ling_step_mfu_pct", "moe_rows_here_per_step", "moe_load_max_over_mean",
)
# PR 30: the share of a step's buckets filled in kept memory, from DDP_SYNC
BUCKET_READERS = ("bucket_warm_pct",)


@pytest.mark.parametrize("name", sorted(LATER_READINGS))
def test_later_span_reader_on_synthetic_planes(run, name, monkeypatch):  # noqa: F405
    monkeypatch.setitem(theirs.READINGS, name, LATER_READINGS[name])
    theirs.test_span_reader_on_synthetic_planes(run, name, monkeypatch)


def test_new_readers_are_the_eighteen_benchmark_json_lists():  # noqa: F811
    """Theirs holds PR 26's eighteen to be the LAST entries of ``per_layer``;
    a later PR appends, so here they are the eighteen before the later ones."""
    with open(os.path.join(theirs.ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    later = len(LATER_READINGS) + len(LING_READERS) + len(BUCKET_READERS)
    assert [m["name"] for m in per_layer[-later:]] == (
        list(LATER_READINGS) + list(LING_READERS) + list(BUCKET_READERS)
    )
    theirs_new = set(theirs.READINGS) | set(theirs.KILL_READINGS) | {"flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms"}
    assert len(theirs_new) == 18
    assert {m["name"] for m in per_layer[-18 - later:-later]} == theirs_new
    for entry in per_layer[-18 - later:]:
        assert len(entry["workloads"]) == 1 and set(entry) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads",
        }


def _sync(t, warm=None, buckets=10, name="DDP_SYNC"):
    event = dict(name=name, t=t, step=int(t), buckets=buckets, bytes=973127680)
    return event if warm is None else dict(event, warm_buckets=warm)


@pytest.mark.parametrize(
    "events,expects",
    [
        # a life's first round trip lies before the window: every step warm
        ([_sync(1.0, 0), _sync(11.0, 10), _sync(12.0, 10), _sync(13.0, 10)], 100.0),
        # a round trip that failed costs the step after it its kept memory
        ([_sync(11.0, 10), _sync(12.0, 0), _sync(13.0, 10), _sync(14.0, 10)], 75.0),
        # the parent's events carry no such counter: nothing, and no error
        ([_sync(11.0), _sync(12.0)], None),
        # events of other names, and of steps outside the window, do not count
        ([_sync(11.0, 3, name="MOE_ROUTE"), _sync(30.0, 0), _sync(12.0, 5)], 50.0),
        ([], None),
    ],
    ids=["all_warm", "one_cold_step", "parent", "other_events", "no_events"],
)
def test_bucket_warm_pct_on_synthetic_flight_events(events, expects):
    read = spec.load_metric("bucket_warm_pct", theirs.BENCH_DIR).read  # noqa: F405
    window = [[dict(t_enter=10.0, t_exit=11.5), dict(t_enter=11.5, t_exit=20.0)], []]
    assert read(dict(window=window, flight=[events, [_sync(12.0, 0)]])) == expects
    assert read(dict(window=window, flight=None)) is None
    assert read(dict(window=[[], []], flight=[events, []])) is None
