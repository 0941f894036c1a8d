"""The runner's control flow, walked on the CPU at toy widths: every cell,
a traced run, the kill and the heal.  No number of these runs is a device's,
and the runner prints none."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(args, cwd=ROOT, devices=4):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join("ftbench", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600,
    )


def _lines(stdout):
    return [json.loads(l[len("ftbench: "):]) for l in stdout.splitlines() if l.startswith("ftbench: ")]


@pytest.mark.parametrize(
    "cell,trace,devices,expects",
    [
        ("mistral7b-ddp2-steady", 0, 2, {"ddp_tokens_per_s_per_chip", "setup_s"}),
        ("mistral7b-ws1-steady", 0, 2, {"tokens_per_s_per_chip", "setup_s"}),
        ("mistral7b-ddp2-kill", 0, 2, {"resume_s", "setup_s"}),
        ("mistral7b-ddp2-steady", 1, 2, {"quorum_ms.ddp", "commit_vote_ms.ddp",
                                         "ring_tx_mbytes_per_step", "grad_mbytes_per_step"}),
        ("mistral7b-ddp2-kill", 1, 2, {"detect_ms", "heal_ms", "heal_mbytes", "rejoin_first_step_ms",
                                       "survivor_stall_s"}),
    ],
)
def test_rehearsal_walks_the_cell(cell, trace, devices, expects):
    done = _run(["--workload", cell, "--seed", "3000000019", "--seconds", "2",
                 "--trace", str(trace), "--rehearse"], devices=devices)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = _lines(done.stdout)
    last = lines[-1]
    assert last["rehearsal"] is True and last["platform"] == "cpu" and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    # host-side readers have something to read on the CPU; device readers do
    # not.  A traced run reports AT LEAST these: a later PR appends readers
    # (PR 26's spans, PR 27's) and edits no file here
    if trace:
        assert set(last["would_report"]) >= expects
        assert not {"step_device_ms", "step_device_ms.ddp", "flash_roofline", "device_idle_pct"} & set(
            last["would_report"])
    else:
        assert set(last["would_report"]) == expects
    # no result line, so no metric under a device's name
    assert not any("metrics" in l for l in lines)
    assert not done.stdout.rstrip().splitlines()[-1].startswith("{")
    window = next(l for l in lines if "steps_in_window" in l)
    assert window["compiles_in_window"] == 0 and window["steps_in_window"] >= 1
    checks = next(l for l in lines if "checks" in l)
    assert len(set(checks["digests"])) == 1
    # float32 at toy widths: the absolute arm alone, and no coarse copy is made
    assert checks["reference_arm"] == "absolute" and checks["reference_tolerance_abs"] == 2e-4
    assert "coarse_token_rms" not in checks and "coarse_ratio" not in checks
    assert checks["loss_tie"] <= checks["loss_tie_abs"] == 2e-5
    assert checks["token_rms"] < 1e-4


HSDP_CELL = "mistral7b-hsdp2x2-steady"


def test_rehearsal_walks_the_four_chip_cell_a_later_pr_adds():
    """The cell PR 43 added, from the files that ship (before it: built in a
    temporary root; the name is the one tier-1's view of this file imports):
    two replica groups of two chips each walk on four virtual devices, and
    do not lay out on two.  The traced walk of the cell is
    ``test_ftbench_program_spans.py``'s, in a copy with an ``out/`` of its own."""
    args = ["--workload", HSDP_CELL, "--seed", "7", "--seconds", "2", "--trace", "0", "--rehearse"]
    done = _run(args, devices=4)
    short = _run(args, devices=2)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = _lines(done.stdout)
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True and last["failed"] == 0
    assert set(last["would_report"]) == {"ddp_tokens_per_s_per_chip", "setup_s"}
    checks = next(l for l in lines if "checks" in l)
    assert checks["checks"]["devices_as_laid_out"] and len(set(checks["digests"])) == 1
    # two groups of two chips do not fit two devices
    assert short.returncode == 1 and short.stdout.strip() == ""
    assert "needs 4 chips" in short.stderr


def test_no_chip_is_exit_1_and_no_result():
    done = _run(["--workload", "mistral7b-ws1-steady", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert done.returncode == 1
    assert done.stdout.strip() == ""
    assert "no TPU" in done.stderr


def test_benchmark_alone_without_the_program_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "ftbench"), tmp_path / "ftbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    done = _run(["--workload", "mistral7b-ws1-steady", "--seed", "1", "--seconds", "1",
                 "--trace", "0", "--rehearse"], cwd=str(tmp_path))
    assert done.returncode != 0
    assert not any(l.startswith("{") for l in done.stdout.splitlines())
