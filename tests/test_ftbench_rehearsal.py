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
# sync_normalize_ms is PR 27's reader of PR 26's span tpuft/manager/normalize,
# bucket_warm_pct PR 30's of DDP_SYNC's warm_buckets, heal_serve_ahead_pct
# PR 36's of HEAL_SERVE_END's ahead_bytes (a rehearsed kill run heals jax
# leaves, so the counter is there and the share is reported),
# normalize_in_ring_pct PR 40's of the normalize span's in_ring
_NEW = {
    "mistral7b-ddp2-steady": set(READINGS) | {"sync_normalize_ms", "bucket_warm_pct", "normalize_in_ring_pct"},
    "mistral7b-ddp2-kill": set(KILL_READINGS) | {"heal_serve_ahead_pct"},
}


def _what_it_waited_on(cell, flight_dir):
    """For the log of a walk that failed: the steps that were voted down or
    took long (replica, life, step, committed, seconds), from the run's
    series file, and the errors its Managers funnelled, from the flight
    rings they dump under ``TORCHFT_FLIGHT_DIR``."""
    import glob
    import json
    import os

    said = []
    series = glob.glob(os.path.join(theirs.ROOT, "ftbench", "out", f"{cell}-*.json"))
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
    [(c, t, d, e | _NEW[c] if t else e) for c, t, d, e in _CASES],
)
def test_rehearsal_walks_the_cell(cell, trace, devices, expects, tmp_path, monkeypatch):
    # the walk's subprocess inherits this: every error a Manager funnels
    # dumps its flight ring there, and a failure below says what it was
    monkeypatch.setenv("TORCHFT_FLIGHT_DIR", str(tmp_path))
    try:
        theirs.test_rehearsal_walks_the_cell(cell, trace, devices, expects)
    except AssertionError as e:
        raise AssertionError(f"{e}\nwhat the walk waited on:\n{_what_it_waited_on(cell, tmp_path)}") from e
