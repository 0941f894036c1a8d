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
    # ``run`` is the per-call path's round trip (a ``tpuft/comm/op`` a collective): by that name
    skew = program_spans.peer_skew_s(run["spans"], "tpuft/comm/op")
    assert [step for step, _ in skew] == [5, 6]
    assert all(s == pytest.approx(0.035) for _, s in skew)
    only_one = program_spans.of_replica(run["spans"], 0)
    assert program_spans.peer_skew_s(only_one, "tpuft/comm/op") == []
    # by default the ONE span a round trip has since PR 60 (``python -m ftbench.program_spans``'s table)
    assert program_spans.peer_skew_s(run["spans"]) == []
    sessions = [dict(s, name="tpuft/comm/session") for s in run["spans"] if s["name"] == "tpuft/comm/op" and s["k"] == 0]
    assert [round(skew, 6) for _, skew in program_spans.peer_skew_s(sessions)] == [0.010, 0.010]


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


# ``comm_op_ms`` (140.0) and ``ring_peer_skew_ms`` (35.0) were two more until PR 66 retired them: they read
# ``tpuft/comm/op``, which no trace holds since a round trip's rings are ONE call (PR 60)
READINGS = {
    "sync_host_ms": 600.0, "sync_unnamed_ms": 13.0, "sync_plan_ms": 20.0, "d2h_wait_ms": 200.0,
    "bucket_copy_ms": 200.0, "h2d_restore_ms": 72.0,
}

# PR 43, the four-chip cell ``mistral7b-hsdp2x2-steady``.  These nine
# readers (thirteen until PR 66) stand ONCE and list both two-group cells (PR 58; PR 43 to 57 the
# four-chip cell read each under a twin ``<name>.hsdp``, because tier-1 then
# held these lists to one cell): a reader reads replica or group 0 in whichever
# cell it runs
HSDP_CELL = "mistral7b-hsdp2x2-steady"
TWO_GROUP_CELLS = ("mistral7b-ddp2-steady", HSDP_CELL)
TWO_GROUP_READERS = tuple(sorted(READINGS)) + ("bucket_warm_pct", "sync_first_submit_ms", "normalize_in_ring_pct")
# Two more read the per-call path's spans (``tpuft/manager/normalize``, ``tpuft/comm/op``), which no cell runs since
# PR 60 and no trace of a session holds.  PR 66 took their ENTRIES out (the driver's check refuses an entry that
# reads nothing in a cell it lists); their FILES stay, because tier-1's ``tests/test_ftbench_program_spans.py``
# loads both by name and a ``benchmark`` PR edits nothing there (``test_ftbench_spec.FILES_WITHOUT_AN_ENTRY``)
PER_CALL_READERS = ("sync_normalize_ms", "ring_beside_d2h_pct")
# the one reader of the four-chip cell alone (a group of one chip has no shard
# to write): it keeps the suffix, which two tests under ``tests/`` load it by
HSDP_ONLY = "d2h_direct_pct.hsdp"
# what PR 58 retired: the thirteen twins, and two readers that told nothing more
# (README.md, "On four chips"; PERF.md section 6, PR 58)
RETIRED = tuple(name + ".hsdp" for name in TWO_GROUP_READERS + PER_CALL_READERS) + ("ring_ms", "ring_average_ms") + (
    # PR 66: the two readers of ``tpuft/comm/op``, and the twins they once had
    "comm_op_ms", "ring_peer_skew_ms", "comm_op_ms.hsdp", "ring_peer_skew_ms.hsdp",
)
# ``normalize_in_ring_pct`` counts a session's pieces (``tpuft/comm/session``, ``pieces=``) and so reads 100 in both
# cells on the chip, but only inside a device trace's stretch: tier-1 holds its ENTRY to list ``mistral7b-ddp2-steady``
# (``tests/test_ftbench_program_spans.py``) AND that a traced CPU walk of either cell does not report it
# (``tests/_ftbench_view.py`` ``SILENT_IN_A_SESSION``), and a ``benchmark`` PR edits nothing there (PERF.md section 7)
SILENT_ON_A_CPU_WALK = frozenset(("normalize_in_ring_pct",))
# what a CPU walk at toy widths cannot give for a reason of its own
NOT_ON_A_CPU_WALK = {
    "peak_hbm_gb.ddp": "the CPU's devices have no memory_stats",
    "sync_second_submit_ms": "the toy tree is one bucket: its round trip has one submit",
}
# the lists the cell joined itself: those whose readers find something to read
# on the CPU, and those that need a device plane or ``memory_stats``
HSDP_JOINED_ON_THE_HOST = {
    "quorum_ms.ddp", "commit_vote_ms.ddp", "grad_mbytes_per_step", "ring_tx_mbytes_per_step",
}
HSDP_JOINED = HSDP_JOINED_ON_THE_HOST | {"step_device_ms.ddp", "sync_exposed_ms", "device_idle_pct.ddp", "peak_hbm_gb.ddp"}


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
    # sixteen since PR 66 retired ``comm_op_ms`` and ``ring_peer_skew_ms`` (the name is the one tier-1 calls it by)
    new = set(READINGS) | set(KILL_READINGS) | {"flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms"}
    assert len(new) == 16
    # found by name: a later PR appends its own readers after them
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert new <= set(listed) and len(listed) == len(bench["per_layer"]) >= 18
    # each lists the cell it was written for (and whichever later cell has
    # the part it reads: no count), under the contract's keys and no other
    for name in new:
        cell = ("mistral7b-ddp2-steady" if name in READINGS else
                "mistral7b-ddp2-kill" if name in KILL_READINGS else "mistral7b-ws1-steady")
        assert cell in listed[name]["workloads"] and set(listed[name]) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads",
        }


def _rehearse(cell, root):
    """A traced rehearsal in a copy of the benchmark under ``root`` (the
    trace lands in ITS ``ftbench/out``: another test's traced run may be
    writing the repo's at this moment); the program comes from the repo.  On
    as many virtual devices as the cell has chips, two at the least."""
    import shutil

    devices = max(2, spec.load_cell(cell).chips)

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
        # PR 43: two groups of two chips; the two-group readers read group 0's
        # spans under their own names (PR 58), and the cell's own reader beside them
        (HSDP_CELL, set(TWO_GROUP_READERS) - SILENT_ON_A_CPU_WALK - set(NOT_ON_A_CPU_WALK) | {HSDP_ONLY} | HSDP_JOINED_ON_THE_HOST),
    ],
)
def test_rehearsal_would_report_the_program_span_metrics(cell, new, tmp_path):
    """The whole path on the CPU: the program's spans into a profiler trace
    and its flight ring, ``program_spans`` and the readers out of them."""
    reported = _rehearse(cell, str(tmp_path))
    assert new <= reported
    assert not reported & {"flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms"}
    if cell in ("mistral7b-ddp2-steady", HSDP_CELL):
        spans = program_spans.load(str(tmp_path / "ftbench"))
        mine = program_spans.of_replica(spans, 0)
        assert {s["name"] for s in mine} >= {
            "tpuft/step/grad", "tpuft/step/update", "tpuft/manager/quorum", "tpuft/manager/fence",
            "tpuft/manager/should_commit", "tpuft/comm/session",
            "tpuft/ddp/allreduce_pytree", "tpuft/ddp/plan", "tpuft/ddp/d2h", "tpuft/ddp/pack",
            "tpuft/ddp/submit", "tpuft/ddp/ring_wait", "tpuft/ddp/h2d",
        }
        # a round trip's rings are ONE call of the op thread (PR 60): a span that has ended cannot be annotated
        assert not {s["name"] for s in mine} & {"tpuft/comm/op", "tpuft/manager/normalize"}
        assert all(isinstance(s.get("step"), int) for s in mine)
        assert program_spans.of_replica(spans, 1)
        trips = program_spans.sync_round_trips(dict(trace=None), spans=spans)
        assert trips and all(0.0 <= unnamed <= whole for whole, unnamed in trips)


@pytest.mark.parametrize("cell", TWO_GROUP_CELLS)
def test_no_host_reader_lists_a_two_group_cell_it_reads_nothing_in(cell, tmp_path):
    """PR 66 (iii): of the readers that list the cell and do not read the
    device's trace, a traced walk leaves out EXACTLY the two a CPU at toy widths
    cannot give and the one that reads a session inside a device's stretch
    alone; none else lists a cell in which it finds nothing (five did for five
    checks running, and none of the five does now: PERF.md section 6, PR 66)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"] if cell in m["workloads"] and m["source"] != "device_trace"}
    assert SILENT_ON_A_CPU_WALK <= listed and not ({"comm_op_ms", "ring_peer_skew_ms"} | set(PER_CALL_READERS)) & listed
    silent = listed - _rehearse(cell, str(tmp_path))
    assert silent == (set(NOT_ON_A_CPU_WALK) | SILENT_ON_A_CPU_WALK) & listed, sorted(silent)


# ----------------------------------------------------------------------
# PR 43: the four-chip cell, its lists and its own reader; PR 58: the twins folded
# ----------------------------------------------------------------------


def _listed():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return bench, {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}


def test_the_four_chip_cell_and_the_lists_it_joined():
    bench, listed = _listed()
    entry = next(w for w in bench["workloads"] if w["name"] == HSDP_CELL)
    assert entry == dict(entry, config="mistral-7b-v0.3-2x2", traffic="ddp2-steady", chips=4)
    assert len(entry["why"]) <= 200 and "ICI" in entry["why"] and "bypasses" in entry["why"]
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    assert config["reduced"] == ["num_hidden_layers"]
    cell = spec.load_cell(HSDP_CELL)
    assert cell.config["layout"] == dict(chips_per_group=2, groups_share_chip=False, fsdp=2)
    assert cell.config["num_hidden_layers"] == 2 and cell.traffic["replicas"] == 2
    # two groups of two chips are the cell's four
    assert cell.traffic["replicas"] * cell.config["layout"]["chips_per_group"] == cell.chips == 4
    # every width is the one-chip twin's, which is the published one
    twin = spec.load_cell("mistral7b-ddp2-steady").config
    assert {k: v for k, v in cell.config.items() if isinstance(v, (int, float)) and k != "num_hidden_layers"} == {
        k: v for k, v in twin.items() if isinstance(v, (int, float)) and k != "num_hidden_layers"
    }
    assert HSDP_CELL in listed["ddp_tokens_per_s_per_chip"]["workloads"]
    for name in HSDP_JOINED:
        assert HSDP_CELL in listed[name]["workloads"], name
    assert listed["ici_collective_ms"]["workloads"] == [HSDP_CELL]
    for name, metric in listed.items():
        cells = metric.get("workloads", [])
        # nothing that reads the one-chip two-replica cell is lost to the four-chip
        # one in silence: it lists both
        if "mistral7b-ddp2-steady" in cells:
            assert HSDP_CELL in cells, name
        # and no twin comes back: one name keeps the suffix, excepted by name
        assert not name.endswith(".hsdp") or name == HSDP_ONLY, name
        # what it reports moves the throughput of two replica groups, nothing else
        if HSDP_CELL in cells and "moves" in metric:
            assert metric["moves"] == "ddp_tokens_per_s_per_chip", name


@pytest.mark.parametrize("name", TWO_GROUP_READERS)
def test_a_two_group_reader_stands_once_and_lists_both_cells(name):
    _, listed = _listed()
    entry = listed[name]
    assert set(TWO_GROUP_CELLS) <= set(entry["workloads"]) and entry["moves"] == "ddp_tokens_per_s_per_chip"
    assert spec.load_metric(name, BENCH_DIR).META["moves"] == entry["moves"]
    # under one name: no entry and no file of a twin
    assert name + ".hsdp" not in listed
    assert not os.path.exists(os.path.join(BENCH_DIR, "layer_metrics", name + ".hsdp.py"))


# what the thirteen read off ``run``'s two traced steps: the eight of READINGS,
# PR 27's, PR 32's two, and the two shares at the values the test below lays in
TWO_GROUP_READINGS = dict(
    READINGS, sync_normalize_ms=80.0, sync_first_submit_ms=220.0, ring_beside_d2h_pct=100.0 * 92 / 220,
    bucket_warm_pct=100.0, normalize_in_ring_pct=100.0,
)


@pytest.mark.parametrize("name", TWO_GROUP_READERS + PER_CALL_READERS)
def test_a_two_group_reader_reads_group_0_beside_a_whole_group_1(run, name, monkeypatch):
    """What the fold rests on: in the four-chip cell ONE process records both
    groups' whole round trips (``run`` has group 1's collectives alone), each
    group on chips of its own, and a reader still reads group 0's."""
    zero = [dict(s, in_ring=1) if s["name"] == "tpuft/manager/normalize" else s
            for s in program_spans.of_replica(run["spans"], 0)]
    # group 1's round trip: group 0's spans 7 ms later and half as long again, on
    # lines of their own, dividing in the callback (its collectives are there already)
    one = [
        dict(s, r=R1, start=s["start"] + 0.007, end=s["start"] + 0.007 + 1.5 * (s["end"] - s["start"]),
             line=(s["line"][0], f"group 1, {s['line'][1]}"), in_ring=0)
        for s in zero if s["name"] != "tpuft/comm/op"
    ]
    spans = sorted(zero + program_spans.of_replica(run["spans"], 1) + one, key=lambda s: s["start"])
    monkeypatch.setattr(program_spans, "load", lambda bench_dir=None: spans)
    trace = run["sources"]["trace"]
    steps = trace["traced_steps"][0]
    warm = [dict(name="DDP_SYNC", t=r["t_enter"] + 0.5, bytes=1409368064, buckets=89, warm_buckets=89) for r in steps]
    cold = [dict(e, warm_buckets=0) for e in warm]
    sources = dict(
        run["sources"], groups_share_chip=False,
        trace=dict(trace, per_device={chip: trace["per_device"][0] for chip in range(4)}),
        window=[steps, steps], flight=[warm, cold],
    )
    read = spec.load_metric(name, BENCH_DIR).read
    assert read(sources) == pytest.approx(TWO_GROUP_READINGS[name], abs=1e-6)


def _sessions(run, pieces):
    """``run``'s spans as a session leaves them: ONE ``tpuft/comm/session`` a
    round trip where the op thread's first ``tpuft/comm/op`` stood, ``pieces``
    on it as the profiler hands an annotation's argument back, and neither
    ``tpuft/comm/op`` nor ``tpuft/manager/normalize``."""
    out = []
    for s in run["spans"]:
        if s["name"] == "tpuft/comm/op" and s["k"] == 0:
            out.append(dict(s, name="tpuft/comm/session", pieces=pieces))
        elif s["name"] not in ("tpuft/comm/op", "tpuft/manager/normalize"):
            out.append(s)
    return out


@pytest.mark.parametrize(
    "pieces,per_call,expects",
    [
        # both two-group cells since PR 60: every bucket a piece of the round trip's one session
        (62, [], 100.0),
        # as the profiler may hand the argument back
        ("62", [], 100.0),
        # a quantized call beside the sessions (in_ring=0: its callback divided the sum): 124 of 125
        (62, [0], 100.0 * 124 / 125),
        # and one whose ring divided, on the per-call path
        (2, [1, 0], 100.0 * 5 / 6),
    ],
    ids=["sessions", "strings", "one_quantized_call", "both_paths"],
)
def test_normalize_in_ring_pct_counts_a_sessions_pieces(run, pieces, per_call, expects, monkeypatch):
    """PR 66: a session is opened with the divisor and in no other way
    (``Manager.ring_session``), so each of its pieces is a collective whose
    average the ring made; replica 1's sessions do not count."""
    spans = _sessions(run, pieces)
    assert len([s for s in spans if s["name"] == "tpuft/comm/session" and s["r"] == R0]) == 2
    (at,) = {s["start"] for s in spans if s["name"] == "tpuft/comm/session" and s["r"] == R0 and s["step"] == 5}
    spans += [
        dict(name="tpuft/manager/normalize", start=at + 0.3 + 0.01 * i, end=at + 0.301 + 0.01 * i, r=R0, step=5,
             line=("/host:CPU", "op0"), in_ring=flag)
        for i, flag in enumerate(per_call)
    ]
    monkeypatch.setattr(program_spans, "load", lambda bench_dir=None: sorted(spans, key=lambda s: s["start"]))
    read = spec.load_metric("normalize_in_ring_pct", BENCH_DIR).read
    assert read(run["sources"]) == pytest.approx(expects)


def test_normalize_in_ring_pct_reads_a_session_inside_a_devices_stretch_alone(run, monkeypatch):
    """A trace with no device plane (the CPU rehearsal: ``trace`` is None) has
    no stretch, and under a session the reader finds nothing there: what
    tier-1's ``SILENT_IN_A_SESSION`` holds of a walk.  The per-call path's
    spans read with and without one, as they did."""
    spans = _sessions(run, 62)
    monkeypatch.setattr(program_spans, "load", lambda bench_dir=None: spans)
    read = spec.load_metric("normalize_in_ring_pct", BENCH_DIR).read
    assert read(run["sources"]) == 100.0
    assert read(dict(run["sources"], trace=None)) is None
    flagged = [dict(s, in_ring=1) if s["name"] == "tpuft/manager/normalize" else s for s in run["spans"]]
    monkeypatch.setattr(program_spans, "load", lambda bench_dir=None: flagged)
    assert read(run["sources"]) == read(dict(run["sources"], trace=None)) == 100.0


@pytest.mark.parametrize("name", RETIRED)
def test_a_retired_name_is_gone(name):
    """No entry, no reader's file, nothing for ``spec.load_metric`` to load,
    and the benchmark's own page does not name it."""
    _, listed = _listed()
    assert name not in listed
    assert not os.path.exists(os.path.join(BENCH_DIR, "layer_metrics", name + ".py"))
    assert spec.load_metric(name, BENCH_DIR) is None
    with open(os.path.join(BENCH_DIR, "README.md")) as f:
        assert f"`{name}`" not in f.read()


def _device_op(name, start_us, dur_us, category="", tf_op="jit(_step)/jit(main)/x"):
    from ftbench import device_scopes

    return device_scopes.annotate(dict(
        name=name, start_ps=start_us * 10**6, dur_ps=dur_us * 10**6, start=start_us * 1e-6, dur_s=dur_us * 1e-6,
        tf_op=tf_op, category=category, source="",
    ))


def _two_steps(ops):
    """``sources`` whose traced stretch is two steps of one second each, and
    the planes ``device_scopes.load`` would hand out: ``ops`` on chip 0."""
    steps = [dict(t_enter=0.0, t_exit=1.0, committed=True), dict(t_enter=1.0, t_exit=2.0, committed=True)]
    sources = dict(trace=dict(traced_steps=[steps], offset=0.0, per_device={0: {}}), replicas=2, groups_share_chip=False)
    return sources, {0: ops, 1: [_device_op("%all-gather-done.9", 0, 10**6)]}


def test_ici_collective_ms_is_the_own_time_of_the_collectives_on_the_traced_chip(monkeypatch):
    from ftbench import device_scopes

    ops = [
        # a step's loop lies over its body: own time is what is left of it
        _device_op("%while.1", 0, 1000),
        _device_op("%all-gather-start.1", 0, 5),  # asynchronous: the start is short,
        # a product runs beside the exchange; the trace names an operation by its
        # whole HLO line, and an OPERAND that is a collective makes it none,
        _device_op("%fusion.7 = bf16[2048,4096]{1,0} fusion(bf16[8]{0} %all-gather-start.1)", 5, 300, "convolution fusion"),
        _device_op("%all-gather-done.1", 305, 45),  # and the wait for it is what the exchange cost
        _device_op("%reduce-scatter.3 = bf16[1024]{0} reduce-scatter(bf16[2048]{0} %fusion.7)", 400, 100),
        # a collective by its category: the v5e's fusion of a product and its reduce-scatter
        _device_op("%fusion.8 = bf16[2048,32768]{1,0} fusion(%p)", 500, 400, category="all-reduce-scatter fusion"),
        _device_op("%collective-permute-done.2", 1_000_100, 50),
        _device_op("%all-reduce.5", 3_000_000, 999),  # after the traced stretch
    ]
    sources, planes = _two_steps(ops)
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: planes)
    read = spec.load_metric("ici_collective_ms", BENCH_DIR).read
    # (5 + 45 + 100 + 400 + 50) us over two steps, chip 0 alone
    assert read(sources) == pytest.approx(0.3)
    # a group of one chip has no exchange: nothing, never 0
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: {0: [_device_op("%fusion.7", 5, 300)]})
    device_scopes._CUT.clear()
    assert read(sources) is None
    # no device plane (the CPU), no traced stretch
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: {})
    assert read(sources) is None and read(dict(sources, trace=None)) is None


# ----------------------------------------------------------------------
# the sharded case of what PR 30, 32 and 40 tested on whole leaves: two groups
# of ``fsdp`` 2 on four virtual devices through ``ddp.allreduce_pytree``
# ----------------------------------------------------------------------


@pytest.fixture()
def two_groups_of_two():
    """Two Managers behind one lighthouse, as the cell builds them (the
    tier the machine has), each with the model at toy widths laid out over its
    own two devices; the process's span buffer on and empty."""
    import jax

    from torchft_tpu import tier as tier_mod
    from torchft_tpu.checkpointing.http_transport import HTTPTransport
    from torchft_tpu.manager import Manager
    from torchft_tpu.obs import spans as obs_spans
    from torchft_tpu.parallel.hsdp import fsdp_shardings
    from torchft_tpu.parallel.mesh import make_mesh

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip(f"four devices are needed, jax has {len(devices)} (ftbench/tests/conftest.py asks for eight)")
    tier = tier_mod.default_tier()
    lighthouse = tier_mod.make_lighthouse(
        bind="127.0.0.1:0", min_replicas=2, join_timeout_ms=100, quorum_tick_ms=20, heartbeat_timeout_ms=5000,
        tier=tier,
    )
    cell = spec.load_cell(HSDP_CELL)
    config = dict(cell.config, **cell.architecture.TOY["config"])
    groups = []
    for g in range(2):
        mesh = make_mesh(fsdp=2, devices=devices[2 * g : 2 * g + 2])
        model = cell.architecture.model(config)
        state = {"w": g}
        manager = Manager(
            comm=tier_mod.make_communicator(timeout_s=20.0, tier=tier),
            checkpoint_transport=HTTPTransport(timeout=20.0),
            load_state_dict=state.update, state_dict=lambda state=state: dict(state),
            min_replica_size=2, timeout=20.0, quorum_timeout=20.0, connect_timeout=20.0,
            replica_id=f"hsdp_group_{g}", lighthouse_addr=lighthouse.local_address(),
            server_cls=tier_mod.manager_server_cls(tier),
        )
        groups.append(dict(mesh=mesh, model=model, manager=manager, shardings=fsdp_shardings(model, mesh)[0]))
    obs_spans.configure(True)
    obs_spans.clear()
    yield groups
    obs_spans.configure(None)
    obs_spans.clear()
    for group in groups:
        group["manager"].shutdown()
    lighthouse.shutdown()


def test_sharded_leaves_of_two_groups_of_fsdp_2_through_allreduce_pytree(two_groups_of_two, monkeypatch):
    """Three committed steps.  From the second every bucket is filled in kept
    memory (PR 30), every collective's average is the ring's own (PR 40), and
    what comes back is, leaf by leaf, the plain mean of the two groups'
    gradients, laid out over the group's two devices as it went in."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu import ddp
    from torchft_tpu.obs import spans as obs_spans

    # 16 KB a bucket: the toy model's leaves split into several buckets
    monkeypatch.setenv(ddp.BUCKET_CAP_MB_ENV, str(16384 / (1 << 20)))
    groups = two_groups_of_two

    def gradients(g, step):
        # the model's own tree in the model's own layout, other values a group and a step
        with groups[g]["mesh"]:
            return jax.jit(groups[g]["model"].init, out_shardings=groups[g]["shardings"])(
                jax.random.PRNGKey(100 * step + g)
            )

    def one(g, tree):
        manager = groups[g]["manager"]
        with jax.default_device(groups[g]["mesh"].devices.flat[0]):
            manager.start_quorum()
            out = ddp.allreduce_pytree(manager, tree).wait(timeout=60.0)
            took_part = manager.is_participating()
            assert manager.should_commit()
        return out, took_part

    with ThreadPoolExecutor(max_workers=2) as pool:
        for step in range(3):
            trees = [gradients(g, step) for g in range(2)]
            sharded = [x for x in jax.tree_util.tree_leaves(trees[0]) if len(x.sharding.device_set) == 2
                       and not x.sharding.is_fully_replicated]
            assert sharded, "no leaf of the model is sharded over the group's two devices"
            done = [f.result(timeout=120.0) for f in [pool.submit(one, g, trees[g]) for g in range(2)]]
            outs, took_part = [d[0] for d in done], [d[1] for d in done]
            # as in the cell, the group that heals in its life's first step
            # (``init_sync``) rides the ring with zeros; then both send theirs
            assert all(took_part) or step == 0
            for t in threading.enumerate():
                if t.name == "tpuft_ddp_gather":
                    t.join(timeout=10.0)
            host = [[np.asarray(x) for x in jax.tree_util.tree_leaves(t)] for t in trees]
            for g in range(2):
                got = jax.tree_util.tree_leaves(outs[g])
                assert len(got) == len(host[0])
                for leaf, mine, *theirs in zip(got, jax.tree_util.tree_leaves(trees[g]), *host):
                    sent = jnp.stack([x if took else jnp.zeros_like(x) for x, took in zip(theirs, took_part)])
                    np.testing.assert_array_equal(np.asarray(leaf), np.asarray(jnp.mean(sent, axis=0)))
                    assert leaf.sharding == mine.sharding and leaf.dtype == mine.dtype
    for group in groups:
        syncs = [e for e in group["manager"]._flight.snapshot() if e["name"] == "DDP_SYNC"]
        buckets = syncs[0]["buckets"]
        assert len(syncs) == 3 and buckets > 1
        # a life's first round trip fills fresh memory, every later one the kept buckets
        assert [e["warm_buckets"] for e in syncs] == [0, buckets, buckets]
        assert [e["buckets"] for e in syncs] == [buckets] * 3
    spans = obs_spans.snapshot()
    normalize = [s for s in spans if s["name"] == "tpuft/manager/normalize"]
    sessions = [s for s in spans if s["name"] == "tpuft/comm/session"]
    if sessions:
        # a round trip's rings are ONE call of the op thread (PR 60): a session a group and step over the
        # step's buckets, the ring divides inside it and no done-callback runs a bucket
        assert len(sessions) == 2 * 3 and not normalize
        assert all(s["attrs"]["pieces"] == buckets for s in sessions)
    else:
        # the per-call path (tier-1 holds it by taking ``Manager.ring_session`` away): one span a collective,
        # two groups, three steps, ``buckets`` rings each
        assert len(normalize) == 2 * 3 * buckets
        assert all(s["attrs"]["in_ring"] == 1 for s in normalize)
