"""The program's spans read back from a trace (``program_spans``), and every
reader that PR 26 adds: on synthetic planes whose numbers are known, on the
small trace recorded on the chip (which has no such span: what a parent
commit gives), on synthetic flight events, and through the CPU rehearsal."""

import json
import os
import subprocess
import sys

import pytest

from ftbench import program_spans, spec, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "ftbench")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

R0, R1 = "ftbench_0:5f1c/0", "ftbench_1:9a2e/0"
STEP_MS = 1100.0


def _step_spans(step, at):
    """One step of both replicas, times in ms from ``at``: (thread, name,
    start, duration, stats)."""
    def s(thread, name, start, dur, r=R0, **stats):
        return (thread, name, at + start, dur, dict(stats, r=r, step=step))

    sync = "tpuft/ddp/allreduce_pytree"
    return [
        s("train", "tpuft/step/grad", 0, 5),
        s("train", sync, 10, 426),  # the train thread's piece
        s("train", "tpuft/ddp/plan", 10, 20),
        s("train", "tpuft/ddp/d2h", 30, 100, bucket=0),
        s("train", "tpuft/ddp/pack", 130, 100, bucket=0),
        s("train", "tpuft/ddp/submit", 230, 2, bucket=0),
        s("train", "tpuft/ddp/d2h", 232, 100, bucket=1),
        s("train", "tpuft/ddp/pack", 332, 100, bucket=1),
        s("train", "tpuft/ddp/submit", 432, 2, bucket=1),
        s("op", "tpuft/comm/op", 240, 60, k=0),
        s("op", "tpuft/manager/normalize", 300, 40),
        s("op", "tpuft/comm/op", 440, 80, k=1),
        s("op", "tpuft/manager/normalize", 520, 40),
        s("gather", sync, 436.5, 173.5),  # the gather thread's piece, to 610
        s("gather", "tpuft/ddp/ring_wait", 437, 1, bucket=0),
        s("gather", "tpuft/ddp/h2d", 438, 32, bucket=0),
        s("gather", "tpuft/ddp/ring_wait", 470, 90, bucket=1),
        s("gather", "tpuft/ddp/h2d", 560, 40, bucket=1),
        s("train", "tpuft/manager/should_commit", 900, 90),
        # the other replica enters the same collectives 10 and 25 ms later
        s("op1", "tpuft/comm/op", 250, 50, r=R1, k=0),
        s("op1", "tpuft/comm/op", 465, 55, r=R1, k=1),
    ]


def _text_proto(spans, device_ops, modules):
    """An ``.xplane.pb`` in text form: a device plane (ops and modules as
    (name, start ms, duration ms)) and one host plane, a line a thread, an
    event's stats as the profiler writes a TraceAnnotation's arguments."""
    names, stat_names = {}, {}

    def ident(table, name):
        return table.setdefault(name, len(table) + 1)

    def event(name, start, dur, stats=()):
        out = f"events {{ metadata_id: {ident(names, name)} offset_ps: {int(start * 1e9)} duration_ps: {int(dur * 1e9)}"
        for key, value in stats:
            kind = "int64_value" if isinstance(value, int) else "str_value"
            shown = value if isinstance(value, int) else json.dumps(value)
            out += f" stats {{ metadata_id: {ident(stat_names, key)} {kind}: {shown} }}"
        return out + " }"

    def metadata(table, what):
        return "\n".join(
            f'{what} {{ key: {i} value {{ id: {i} name: {json.dumps(n)} }} }}' for n, i in table.items()
        )

    device = ['planes { id: 1 name: "/device:TPU:0"']
    for line_id, (line, events) in enumerate((("XLA Ops", device_ops), ("XLA Modules", modules)), 1):
        device.append(f'lines {{ id: {line_id} name: "{line}" timestamp_ns: 1000000000')
        device += [event(*e) for e in events]
        device.append("}")
    device.append(metadata(names, "event_metadata") + " }")
    names, stat_names = {}, {}
    host = ['planes { id: 2 name: "/host:CPU"']
    threads = sorted({s[0] for s in spans})
    for line_id, thread in enumerate(threads, 1):
        host.append(f'lines {{ id: {line_id} name: "{thread}" timestamp_ns: 1000000000')
        host += [event(n, a, d, sorted(st.items())) for t, n, a, d, st in spans if t == thread]
        host.append("}")
    host.append(metadata(names, "event_metadata"))
    host.append(metadata(stat_names, "stat_metadata") + " }")
    return "\n".join(device + host)


@pytest.fixture(scope="module")
def run():
    """Two traced steps (5 and 6) of a two-replica cell: the device plane as
    ``trace_reduce`` reads it, the spans as ``program_spans`` does, and the
    ``sources`` a reader is handed."""
    from jax.profiler import ProfileData

    spans, ops, modules = [], [], []
    for i, step in enumerate((5, 6)):
        at = i * STEP_MS
        spans += _step_spans(step, at)
        ops += [("fusion.1", at, 40), ("fusion.9", at + 1000, 10)]
        modules += [("jit__step(1)", at, 40), ("jit__update(2)", at + 1000, 10)]
    profile = ProfileData.from_text_proto(_text_proto(spans, ops, modules))
    space = trace_reduce.from_profile(profile)
    per_device = trace_reduce.summarize(space, 1.0, 1.0 + 2 * STEP_MS / 1000 - 0.005)
    steps = [
        dict(step=6 + i, t_enter=1.0 + i * STEP_MS / 1000, t_exit=1.0 + (i + 1) * STEP_MS / 1000 - 0.005)
        for i in range(2)
    ]
    sources = dict(
        trace=dict(per_device=per_device, t0=1.0, t1=3.195, offset=0.0, traced_steps=[steps, steps]),
        replicas=2, groups_share_chip=True, kill=None,
    )
    return dict(spans=program_spans.from_profile(profile), sources=sources)


def test_spans_come_back_with_their_stats_and_threads(run):
    spans = run["spans"]
    assert len(spans) == 2 * 21
    assert all(s["name"].startswith("tpuft/") for s in spans)
    assert [s["start"] for s in spans] == sorted(s["start"] for s in spans)
    plan = next(s for s in spans if s["name"] == "tpuft/ddp/plan")
    assert plan["r"] == R0 and plan["step"] == 5
    assert plan["start"] == pytest.approx(1.010) and plan["end"] == pytest.approx(1.030)
    op = next(s for s in spans if s["name"] == "tpuft/comm/op" and s["r"] == R1)
    assert op["k"] == 0 and op["line"] != plan["line"]
    assert len(program_spans.of_replica(spans, 0)) == 2 * 19
    assert len(program_spans.of_replica(spans, 1)) == 2 * 2
    assert program_spans.of_replica(spans, 10) == []


def test_a_span_that_crosses_threads_is_one_interval(run):
    whole = program_spans.merged(run["spans"], program_spans.SYNC)
    assert [(round(s["start"], 4), round(s["end"], 4), s["step"]) for s in whole] == [
        (1.010, 1.610, 5), (2.110, 2.710, 6),
    ]
    # a second round trip of the same step, far from the first, stays its own
    again = [dict(s, start=s["start"] + 5.0, end=s["end"] + 5.0) for s in whole[:1]]
    assert len(program_spans.merged(run["spans"] + again, program_spans.SYNC)) == 3


def test_the_round_trip_is_tiled_and_what_is_left_has_no_name(run):
    trips = program_spans.sync_round_trips(run["sources"], spans=run["spans"])
    assert len(trips) == 2
    for whole, unnamed in trips:
        assert whole == pytest.approx(0.600)
        # 434-437 (a thread's start) and 600-610 (the tree put together again)
        assert unnamed == pytest.approx(0.013, abs=1e-6)


def test_peer_skew_pairs_the_kth_collective_of_a_step(run):
    skew = program_spans.peer_skew_s(run["spans"])
    assert [step for step, _ in skew] == [5, 6]
    assert all(s == pytest.approx(0.035) for _, s in skew)
    only_one = program_spans.of_replica(run["spans"], 0)
    assert program_spans.peer_skew_s(only_one) == []


def test_idle_seconds_go_to_one_leaf_span_each(run):
    table = dict(program_spans.idle_by_span(run["sources"], spans=run["spans"]))
    expect = {
        "tpuft/ddp/d2h": 0.380, "tpuft/ddp/pack": 0.400, "tpuft/ddp/submit": 0.008,
        "tpuft/ddp/ring_wait": 0.002, "tpuft/ddp/h2d": 0.084, "tpuft/comm/op": 0.160,
        "tpuft/manager/normalize": 0.080, "tpuft/manager/should_commit": 0.180,
        "no_span": 0.801,
    }
    assert set(table) == set(expect)
    for name, seconds in expect.items():
        assert table[name] == pytest.approx(seconds, abs=1e-6), name
    # every idle second is named once: the gaps' own sum
    device = run["sources"]["trace"]["per_device"][0]
    gaps = trace_reduce.idle_gaps(device["ops"], 1.0, 3.195)
    assert sum(table.values()) == pytest.approx(sum(b - a for a, b in gaps))
    # a parent is no leaf, and a span under a busy device names no idle time
    assert program_spans.SYNC not in table and "tpuft/step/grad" not in table


READINGS = {
    "sync_host_ms": 600.0, "sync_unnamed_ms": 13.0, "sync_plan_ms": 20.0, "d2h_wait_ms": 200.0,
    "bucket_copy_ms": 200.0, "h2d_restore_ms": 72.0, "comm_op_ms": 140.0, "ring_peer_skew_ms": 35.0,
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_span_reader_on_synthetic_planes(run, name, monkeypatch):
    monkeypatch.setattr(program_spans, "load", lambda bench_dir=None: run["spans"])
    read = spec.load_metric(name, BENCH_DIR).read
    assert read(run["sources"]) == pytest.approx(READINGS[name], abs=1e-6)
    # no device plane (the CPU rehearsal): every span of the file, two steps
    assert read(dict(run["sources"], trace=None)) == pytest.approx(READINGS[name], abs=1e-6)
    # a program without spans (a parent commit): nothing, and no error
    monkeypatch.setattr(program_spans, "load", lambda bench_dir=None: [])
    assert read(run["sources"]) is None and read(dict(run["sources"], trace=None)) is None


@pytest.mark.parametrize(
    "name,kernel,ms", [("flash_fwd_ms", "flash_fwd", 3.0), ("flash_dq_ms", "flash_dq", 2.0), ("flash_dkv_ms", "flash_dkv", 2.5)]
)
def test_flash_kernel_readers_tell_the_three_kernels_apart(name, kernel, ms):
    call = "%{}.{} = bf16[1,32,2048,128] custom-call(bf16[1,32,2048,128] %p), custom_call_target=tpu_custom_call"
    ops = []
    for step in range(2):
        at = 1.0 + step * 0.1
        ops += [
            ("fusion.1", at, 0.010),
            (call.format("flash_fwd", 7), at + 0.010, 0.003),
            (call.format("flash_dq", 12), at + 0.020, 0.002),
            (call.format("flash_dkv", 12), at + 0.030, 0.0025),
            # an operation that only MENTIONS a kernel, as its operand
            (f"%get-tuple-element.3 = bf16[1,32,2048,128] get-tuple-element(%{kernel}.7), index=0", at + 0.040, 0.001),
        ]
    steps = [dict(t_enter=1.0, t_exit=1.1), dict(t_enter=1.1, t_exit=1.2)]
    sources = dict(
        trace=dict(per_device={0: dict(ops=ops)}, offset=0.0, traced_steps=[steps]),
        replicas=1, groups_share_chip=False,
    )
    read = spec.load_metric(name, BENCH_DIR).read
    assert read(sources) == pytest.approx(ms)
    # the three together are the Mosaic time flash_roofline divides by
    assert trace_reduce.matching_seconds(ops, r"tpu_custom_call") == pytest.approx(2 * 0.0075)
    # kernels without a name (a parent commit), or no trace: nothing
    nameless = [(n.replace(kernel, "closed_call"), a, d) for n, a, d in ops]
    assert read(dict(sources, trace=dict(sources["trace"], per_device={0: dict(ops=nameless)}))) is None
    assert read(dict(sources, trace=None)) is None


def _kill_sources(with_spans=True):
    def ev(name, t, **more):
        return dict(name=name, t=t, **(more if with_spans or name in ("QUORUM_START", "QUORUM_ADOPT") else {}))

    kills = []
    for i, t_kill in enumerate((100.0, 140.0)):
        kills.append(dict(
            t_kill=t_kill, first_commit=t_kill + 16.0,
            events=[
                ev("QUORUM_START", t_kill + 2.5 + i), ev("QUORUM_START", t_kill + 17.0),
                ev("HEAL_RECV_END", t_kill + 12.0, t0=t_kill + 3.0, duration_s=9.0, read_s=8.0 + i, bytes=2_919_380_000),
                ev("HEAL_APPLY", t_kill + 12.5, t0=t_kill + 12.3, duration_s=0.2 + 0.1 * i),
            ],
        ))
    survivor = [
        # before the first kill: the other replica's init_sync, not a heal of the run
        ev("HEAL_SEND_END", 20.0, duration_s=5.0), ev("HEAL_SERVE_END", 30.0, d2h_s=9.0, write_s=9.0, bytes=1),
    ]
    for i, t_kill in enumerate((100.0, 140.0)):
        survivor += [
            ev("QUORUM_ADOPT", t_kill + 3.0),
            ev("HEAL_SEND_END", t_kill + 3.5, duration_s=0.4 + 0.02 * i),
            ev("HEAL_SERVE_END", t_kill + 12.0, d2h_s=3.0 + i, write_s=5.0 - i, bytes=2_919_380_000, part="full"),
        ]
    return dict(kill=dict(kills=kills, survivor_events=survivor, state_bytes=2_919_400_000), trace=None)


KILL_READINGS = {
    "rejoin_init_ms": 3000.0, "heal_snapshot_ms": 410.0, "heal_serve_d2h_ms": 3500.0,
    "heal_serve_write_ms": 4500.0, "heal_read_ms": 8500.0, "heal_apply_ms": 250.0,
    "heal_wire_mbytes": 2919.38,
}


@pytest.mark.parametrize("name", sorted(KILL_READINGS))
def test_kill_reader_on_synthetic_flight_events(name):
    read = spec.load_metric(name, BENCH_DIR).read
    assert read(_kill_sources()) == pytest.approx(KILL_READINGS[name])
    assert read(dict(kill=None, trace=None)) is None
    # a program whose events lack the new fields (a parent commit)
    bare = read(_kill_sources(with_spans=False))
    assert bare == (pytest.approx(3000.0) if name == "rejoin_init_ms" else None)


def test_kill_mean_takes_the_survivors_events_kill_by_kill():
    sources = _kill_sources()
    # the second kill alone: its own serve, not the first's nor the init_sync's
    sources["kill"]["kills"] = sources["kill"]["kills"][1:]
    assert program_spans.kill_mean(sources, "HEAL_SERVE_END", "d2h_s", 1.0, survivor=True) == pytest.approx(4.0)


def test_the_chips_small_trace_has_no_program_span():
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(os.path.join(DATA, "small.xplane.pb"))
    assert program_spans.from_profile(profile) == []
    assert program_spans.all_in_stretch(dict(trace=None), spans=[]) is None


def test_new_readers_are_the_eighteen_benchmark_json_lists():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = set(READINGS) | set(KILL_READINGS) | {"flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms"}
    assert len(new) == 18
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert new <= set(listed)
    assert [m["name"] for m in bench["per_layer"]][-18:] == [n for n in listed if n in new]
    for name in new:
        assert len(listed[name]["workloads"]) == 1 and set(listed[name]) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads",
        }


def _rehearse(cell, root, devices=2):
    """A traced rehearsal in a copy of the benchmark under ``root`` (the
    trace lands in ITS ``ftbench/out``: another test's traced run may be
    writing the repo's at this moment); the program comes from the repo."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH_DIR, os.path.join(root, "ftbench"),
                    ignore=shutil.ignore_patterns("out", "__pycache__", "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, os.path.join("ftbench", "run.py"), "--workload", cell, "--seed", "3000000023",
         "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(l[len("ftbench: "):]) for l in done.stdout.splitlines() if l.startswith("ftbench: ")]
    assert lines[-1]["rehearsal"] is True and lines[-1]["correct"] is True
    return set(lines[-1]["would_report"])


@pytest.mark.parametrize(
    "cell,new",
    [
        ("mistral7b-ddp2-steady", set(READINGS)),
        ("mistral7b-ddp2-kill", set(KILL_READINGS)),
        # the flash kernels do not run on the CPU, and it has no device plane
        ("mistral7b-ws1-steady", set()),
    ],
)
def test_rehearsal_would_report_the_program_span_metrics(cell, new, tmp_path):
    """The whole path on the CPU: the program's spans into a profiler trace
    and its flight ring, ``program_spans`` and the readers out of them."""
    reported = _rehearse(cell, str(tmp_path))
    assert new <= reported
    assert not reported & {"flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms"}
    if cell == "mistral7b-ddp2-steady":
        spans = program_spans.load(str(tmp_path / "ftbench"))
        mine = program_spans.of_replica(spans, 0)
        assert {s["name"] for s in mine} >= {
            "tpuft/step/grad", "tpuft/step/update", "tpuft/manager/quorum", "tpuft/manager/fence",
            "tpuft/manager/should_commit", "tpuft/manager/normalize", "tpuft/comm/op",
            "tpuft/ddp/allreduce_pytree", "tpuft/ddp/plan", "tpuft/ddp/d2h", "tpuft/ddp/pack",
            "tpuft/ddp/submit", "tpuft/ddp/ring_wait", "tpuft/ddp/h2d",
        }
        assert all(isinstance(s.get("step"), int) for s in mine)
        assert program_spans.of_replica(spans, 1)
        trips = program_spans.sync_round_trips(dict(trace=None), spans=spans)
        assert trips and all(0.0 <= unnamed <= whole for whole, unnamed in trips)
