"""The reduction from a profiler trace to busy time, idle gaps, kernel
time and the wait between two programs: on a synthetic trace whose numbers
are known, and on a small trace recorded on the chip."""

import os

import pytest

from ftbench import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# two steps on one device, times in ms: grad 0-30 (the flash kernel 10-14
# inside it), update 80-90, grad 100-130, update 185-195; a second device
# that only runs a copy; a host plane with the clock mark
SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 2 offset_ps: 10000000000 duration_ps: 4000000000 }
    events { metadata_id: 1 offset_ps: 14000000000 duration_ps: 16000000000 }
    events { metadata_id: 3 offset_ps: 80000000000 duration_ps: 10000000000 }
    events { metadata_id: 1 offset_ps: 100000000000 duration_ps: 10000000000 }
    events { metadata_id: 2 offset_ps: 110000000000 duration_ps: 4000000000 }
    events { metadata_id: 1 offset_ps: 114000000000 duration_ps: 16000000000 }
    events { metadata_id: 3 offset_ps: 185000000000 duration_ps: 10000000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000000000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 30000000000 }
    events { metadata_id: 5 offset_ps: 80000000000 duration_ps: 10000000000 }
    events { metadata_id: 4 offset_ps: 100000000000 duration_ps: 30000000000 }
    events { metadata_id: 5 offset_ps: 185000000000 duration_ps: 10000000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "%flash_fwd.84 = bf16[1,32,2048,128] custom-call(bf16[1,32,2048,128] %p), custom_call_target=tpu_custom_call" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.9" } }
  event_metadata { key: 4 value { id: 4 name: "jit__step(123)" } }
  event_metadata { key: 5 value { id: 5 name: "jit__update(456)" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000000
    events { metadata_id: 1 offset_ps: 50000000000 duration_ps: 5000000000 } }
  event_metadata { key: 1 value { id: 1 name: "copy.1" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 1000000000
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "ftbench_clock" } } }
"""


@pytest.fixture(scope="module")
def space():
    from jax.profiler import ProfileData

    return trace_reduce.from_profile(ProfileData.from_text_proto(SYNTHETIC))


def test_device_planes_and_lines(space):
    planes = trace_reduce.device_planes(space)
    assert sorted(planes) == [0, 1]
    assert len(planes[0][trace_reduce.OPS_LINE]) == 8


def test_busy_is_the_union_and_gaps_are_the_rest(space):
    per_device = trace_reduce.summarize(space)
    d0 = per_device[0]
    assert d0["t1"] - d0["t0"] == pytest.approx(0.195)
    assert d0["busy_s"] == pytest.approx(0.080)
    gaps = [round(b - a, 6) for a, b in d0["gaps"]]
    assert gaps == [0.050, 0.010, 0.055]
    assert sum(gaps) + d0["busy_s"] == pytest.approx(0.195)


def test_a_window_clips_events(space):
    t0 = 1.0
    d0 = trace_reduce.summarize(space, t0 + 0.020, t0 + 0.085)[0]
    # 10 ms left of the first grad, 5 ms of the first update
    assert d0["busy_s"] == pytest.approx(0.015)
    assert [round(b - a, 6) for a, b in d0["gaps"]] == [0.050]


def test_kernel_time_by_name(space):
    ops = trace_reduce.device_planes(space)[0][trace_reduce.OPS_LINE]
    assert trace_reduce.matching_seconds(ops, r"tpu_custom_call") == pytest.approx(0.008)
    totals = trace_reduce.op_totals(ops)
    assert totals["fusion.1"] == pytest.approx(0.052)


def _sources(space, **more):
    """What the harness hands a reader, for the synthetic trace: two steps
    of one replica, host clock = trace clock less 1 s."""
    steps = [dict(t_enter=0.0, t_exit=0.1, committed=True), dict(t_enter=0.1, t_exit=0.2, committed=True)]
    shapes = dict(dim=4096, n_layers=1, n_heads=32, n_kv_heads=8, ffn_hidden=14336, vocab_size=32768)
    from ftbench import spec

    llama = spec.load_architecture("llama", os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return dict(
        trace=dict(per_device=trace_reduce.summarize(space), offset=1.0, traced_steps=[steps]),
        replicas=1, groups_share_chip=False, chips=1, architecture=llama, shapes=shapes, seq=2048,
        rows_per_replica=1, tokens_per_step_per_replica=2048, device_kind="TPU v5 lite", **more,
    )


def test_readers_over_the_trace(space):
    from ftbench import flops, spec

    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sources = _sources(space)
    read = lambda name: spec.load_metric(name, bench_dir).read(sources)  # noqa: E731
    # device 0 is busy 80 ms of the 200 ms stretch, device 1 5 ms: mean 42.5
    assert read("step_device_ms") == pytest.approx(42.5 / 2)
    assert read("device_idle_pct") == pytest.approx(100 * (1 - 0.0425 / 0.2))
    assert read("sync_exposed_ms") == pytest.approx(52.5)
    # the kernel ran 4 ms a step; the least the chip could take over that
    least = flops.flash_step_flops(sources["shapes"], 1, 2048) / 197e12
    assert read("flash_roofline") == pytest.approx(100 * least / 0.004)
    assert read("step_mfu_pct") == pytest.approx(
        100 * 2048 * flops.train_flops_per_token(sources["shapes"], 2048) / 0.02125 / 197e12
    )
    # nothing traced: nothing read
    empty = dict(sources, trace=None)
    assert spec.load_metric("flash_roofline", bench_dir).read(empty) is None
    assert spec.load_metric("step_device_ms", bench_dir).read(empty) is None


def test_wait_between_grad_and_update_programs(space):
    modules = trace_reduce.device_planes(space)[0][trace_reduce.MODULES_LINE]
    waits = trace_reduce.transitions(modules, r"jit__step", r"jit__update")
    assert [round(b - a, 6) for a, b in waits] == [0.050, 0.055]


def test_two_replicas_on_one_chip_wait_once_a_step():
    # grads of A and B, then updates of A and B: one wait, from the end of
    # the later grad to the start of the earlier update
    modules = [
        ("jit__step", 0.00, 0.03), ("jit__step", 0.03, 0.03),
        ("jit__update", 0.90, 0.01), ("jit__update", 0.91, 0.01),
        ("jit__step", 1.00, 0.03),
    ]
    assert trace_reduce.transitions(modules, "jit__step", "jit__update") == [(0.06, 0.90)]


def test_clock_mark_and_gap_names(space):
    marks = trace_reduce.clock_marks(space)
    assert len(marks) == 1 and marks[0][1] == pytest.approx(1.001)
    gaps = trace_reduce.summarize(space)[0]["gaps"]
    phases = [("ring", 1.035, 1.070), ("commit_vote", 1.070, 1.079), ("h2d_restore", 1.132, 1.184)]
    named = trace_reduce.name_gaps(gaps, phases)
    # the 50 ms gap (1.030-1.080) is cut into ring, commit vote and the rest
    assert named[0] == ("h2d_restore", pytest.approx(0.052))
    assert named[1] == ("ring", pytest.approx(0.035))
    assert ("commit_vote", pytest.approx(0.009)) in named
    assert sum(s for _, s in named) == pytest.approx(0.115)
    totals = dict(trace_reduce.gap_totals(named + [("ring", 0.5)]))
    assert totals["ring"] == pytest.approx(0.535) and len(totals) == 4


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(DATA, "small.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in ftbench/tests/data")
    return trace_reduce.load(path)


def test_recorded_trace_reduces(recorded):
    """Recorded on a v5e by record_small_trace.py: a small jitted program run
    three times with 50 ms of host sleep after each."""
    per_device = trace_reduce.summarize(recorded)
    assert per_device, sorted(recorded)
    d0 = per_device[min(per_device)]
    window = d0["t1"] - d0["t0"]
    assert 0.0 < d0["busy_s"] < window
    assert d0["busy_s"] + sum(b - a for a, b in d0["gaps"]) == pytest.approx(window)
    # the sleeps are the two longest gaps between the three runs
    long_gaps = [b - a for a, b in d0["gaps"] if b - a >= 0.045]
    assert len(long_gaps) == 2
    runs = [m for m in d0["modules"] if "small_step" in m[0]]
    assert len(runs) == 3
    assert len(trace_reduce.clock_marks(recorded)) == 1
