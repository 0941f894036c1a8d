"""Tier-1's view of the benchmark: the rule, and the few helpers that hold it.

Tier-1 collects ``tests/`` only, and the benchmark's own tests
(``ftbench/tests/``) guard nothing unless it runs them (ROADMAP D3).  So each
``tests/test_ftbench_<name>.py`` imports ``ftbench/tests/test_ftbench_<name>.py``
and adds only what tier-1 alone knows: the tests of readers that were written
under ``tests/``, and what a traced CPU walk reports today.

**The rule.**  A file under ``tests/`` finds a cell, a configuration or a
reader in ``BENCHMARK.json`` by its NAME and holds what the entry MEANS: the
cell is IN the list; the entry's keys, ``source``, ``layer``, ``unit``,
``moves`` and ``better`` are its reader's.  It never holds a place in a list,
a count of cells, or the name of anything a later PR added.  A PR that appends
a reader, a cell and a configuration then edits no file that is here:
``tests/test_ftbench_spec.py::test_a_further_cell_fails_none_of_tier_1s_list_tests``
runs every such test on the file a later PR would leave.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from ftbench import spec

from tests import _once

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "ftbench")


def bench():
    """``BENCHMARK.json`` as it stands (through ``json.load``, which the test
    named above replaces to show a later PR's file)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def reader_entry(name, cells=(), **fields):
    """The one entry of ``per_layer`` called ``name``: it lists ``cells`` (and
    whichever later cell has what it reads), says what its reader's file says,
    under the contract's keys and no other, and ``fields`` of it are as given."""
    (entry,) = [m for m in bench()["per_layer"] if m["name"] == name]
    assert set(cells) <= set(entry["workloads"]), (name, entry["workloads"])
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    meta = spec.load_metric(name, BENCH_DIR).META
    assert meta == {k: entry[k] for k in ("source", "layer", "unit", "moves")}
    assert fields == {k: entry[k] for k in fields}, (name, fields)
    return entry


# A traced walk on the CPU reports every reader that lists the cell and does
# not read the device's trace, but these: why a CPU walk at toy widths cannot
# give them.  The next reader is one line here, or none.
NOT_ON_A_CPU_WALK = {
    "peak_hbm_gb": "the CPU's devices have no memory_stats",
    "peak_hbm_gb.ddp": "the CPU's devices have no memory_stats",
    "sync_second_submit_ms": "the toy tree is one bucket: its round trip has one submit",
    **dict.fromkeys(
        ("comm_op_ms", "ring_peer_skew_ms", "ring_beside_d2h_pct"),
        "a round trip's rings are ONE call of the op thread (PR 60): no tpuft/comm/op in a trace, on any host",
    ),
    **dict.fromkeys(
        ("sync_normalize_ms", "normalize_in_ring_pct"),
        "no done-callback runs a bucket where the rings are a session (PR 60): no tpuft/manager/normalize",
    ),
}
# the five of them that fell silent with PR 60's ring session, in every cell
# that lists them (PERF.md section 3): ftbench/tests/ still expects them of a
# walk, which only a `benchmark` PR may correct (PERF.md section 7 (cc))
SILENT_IN_A_SESSION = frozenset(
    ("comm_op_ms", "ring_peer_skew_ms", "ring_beside_d2h_pct", "sync_normalize_ms", "normalize_in_ring_pct")
)


def walk_reports(cell):
    """What a traced CPU walk of ``cell`` would report at the least."""
    listed = {m["name"] for m in bench()["per_layer"] if cell in m["workloads"] and m["source"] != "device_trace"}
    return listed - set(NOT_ON_A_CPU_WALK)


def device_trace_readers():
    """The readers a CPU walk has nothing for: its trace has no device plane."""
    return {m["name"] for m in bench()["per_layer"] if m["source"] == "device_trace"}


def traced_walk(cell):
    """(root, the ended process) of the ONE traced CPU walk of ``cell`` that
    a run of the tests makes (``tests/_once.py``): ``--rehearse --trace 1`` in
    a copy of the benchmark under ``root`` (what
    ``ftbench/tests/test_ftbench_program_spans.py`` ``_rehearse`` does: the
    trace lands in ITS ``ftbench/out`` and no other traced walk clears it),
    the program from the repo, on as many virtual devices as the cell has
    chips, two at the least.  ``mistral7b-ddp2-steady`` and
    ``mistral7b-ddp2-kill`` were walked traced by two files each, 20 and 56 s
    a walk (PR 55).  The Managers' flight rings are dumped under
    ``root/flight``."""
    root = _once.directory / f"traced-walk-{cell}"

    def walk():
        root.mkdir()
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
        shutil.copytree(BENCH_DIR, root / "ftbench", ignore=shutil.ignore_patterns("out", "__pycache__", "tests"))
        devices = max(2, spec.load_cell(cell).chips)
        env = dict(
            os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
            PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""), TORCHFT_FLIGHT_DIR=str(root / "flight"),
        )
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        return subprocess.run(
            [sys.executable, os.path.join("ftbench", "run.py"), "--workload", cell, "--seed", "3000000023",
             "--seconds", "2", "--trace", "1", "--rehearse"],
            cwd=root, env=env, capture_output=True, text=True, timeout=600,
        )

    return root, _once.once_a_run(f"traced-walk-{cell}", walk)


def cell_walk(theirs):
    """``theirs.test_rehearsal_walks_the_cell`` (a cell's own test file under
    ``ftbench/tests/``), one walk a case: its traced case holds the readers of
    its own PR with ``>=``, here it holds those of today."""
    cases = theirs.test_rehearsal_walks_the_cell.pytestmark[0].args[1]

    @pytest.mark.parametrize("trace,expects", [(t, e | walk_reports(theirs.CELL) if t else e) for t, e in cases])
    def test_rehearsal_walks_the_cell(trace, expects):
        theirs.test_rehearsal_walks_the_cell(trace, expects)

    return test_rehearsal_walks_the_cell


def compile_cases(*config_names):
    """The two cases of ``ftbench/tests/test_ftbench_compile.py`` for these
    configurations, under the ids they have there.  A case compiles a cell's
    whole step for a described v5e, one to three minutes and three cores
    wide: a configuration has a file of its own
    (``tests/test_ftbench_compile_<configuration>.py``) and
    ``tests/conftest.py`` puts the step cases first in the collection, so
    that the next configuration adds to the run's sum and not to its longest
    pole.  The step case stays in tier-1: it compiles the forward pass with
    every kernel inside the gradient step and holds its memory.  The forward
    check is ``slow`` since PR 55 (40-110 s a case, 760 s the nine): what it
    adds is the harness's own after-window programs, which only a
    ``benchmark`` PR may edit and every run of every cell on the chip
    executes; ``-m slow -k forward_check`` runs the nine by hand
    (``.claude/skills/verify/SKILL.md`` says when).  The file imports the
    fixtures ``topo`` and ``no_compile_cache`` beside this."""
    from ftbench.tests import test_ftbench_compile as theirs

    @pytest.mark.parametrize("config_name", config_names)
    def test_step_compiles_for_v5e(topo, no_compile_cache, monkeypatch, config_name):
        theirs.test_step_compiles_for_v5e(topo, no_compile_cache, monkeypatch, config_name)

    @pytest.mark.slow
    @pytest.mark.parametrize("config_name", config_names)
    def test_forward_check_compiles_for_v5e(topo, no_compile_cache, monkeypatch, config_name):
        theirs.test_forward_check_compiles_for_v5e(topo, no_compile_cache, monkeypatch, config_name)

    return test_step_compiles_for_v5e, test_forward_check_compiles_for_v5e
