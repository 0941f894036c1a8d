"""Tier-1's view of ``ftbench/tests/test_ftbench_program_spans.py``: the
benchmark's tests, imported (``tests/_ftbench_view.py`` says why, and the rule
a view keeps), and the tests of the readers that later PRs wrote under
``tests/`` (a file under ``ftbench/`` is the benchmark's, and only a
``benchmark`` issue may edit it: PERF.md section 7)."""

import copy

import pytest

from ftbench.tests import test_ftbench_program_spans as theirs
from ftbench.tests.test_ftbench_program_spans import *  # noqa: F401,F403
from ftbench.tests.test_ftbench_rehearsal import _lines
from tests._ftbench_view import (
    SILENT_IN_A_SESSION, bench, device_trace_readers, reader_entry, traced_walk, walk_reports,
)

# PR 27's reader on theirs' synthetic planes: the division by the participant
# count, two spans of 40 ms a step
LATER_READINGS = {"sync_normalize_ms": 80.0}
STEADY_CELLS = ("mistral7b-ddp2-steady", theirs.HSDP_CELL)


@pytest.mark.parametrize("name", sorted(LATER_READINGS))
def test_later_span_reader_on_synthetic_planes(run, name, monkeypatch):  # noqa: F405
    monkeypatch.setitem(theirs.READINGS, name, LATER_READINGS[name])
    theirs.test_span_reader_on_synthetic_planes(run, name, monkeypatch)


@pytest.mark.parametrize(
    "cell,new", theirs.test_rehearsal_would_report_the_program_span_metrics.pytestmark[0].args[1]
)
def test_rehearsal_would_report_the_program_span_metrics(cell, new, monkeypatch):  # noqa: F811
    """Theirs, on the cell's one traced walk (``traced_walk``, which is their
    ``_rehearse`` kept for every test that reads it), and of the same walk:
    it reports every reader of today that lists the cell and can be read on a
    CPU, and none of the device's trace (no device plane: PR 37's readers of
    the scopes find nothing and say so).  Theirs still lists the five readers
    that fell silent with PR 60's ring session; the walk must NOT report them
    (a reader that found a ``tpuft/comm/op`` in a trace again would say the
    op thread left its one call)."""
    from ftbench import device_scopes

    root, done = traced_walk(cell)

    def rehearsed(cell, root):  # what ``_rehearse`` holds of its walk
        assert done.returncode == 0, done.stderr[-3000:]
        last = _lines(done.stdout)[-1]
        assert last["rehearsal"] is True and last["correct"] is True
        return set(last["would_report"])

    monkeypatch.setattr(theirs, "_rehearse", rehearsed)
    reported = rehearsed(cell, root)
    if cell in STEADY_CELLS:
        # theirs, line for line, but for the two spans a session's round trip
        # does not have and the one it has in their place
        assert new - SILENT_IN_A_SESSION <= reported and not reported & SILENT_IN_A_SESSION
        assert not reported & {"flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms"}
        spans = theirs.program_spans.load(str(root / "ftbench"))
        mine = theirs.program_spans.of_replica(spans, 0)
        names = {s["name"] for s in mine}
        assert names >= {
            "tpuft/step/grad", "tpuft/step/update", "tpuft/manager/quorum", "tpuft/manager/fence",
            "tpuft/manager/should_commit", "tpuft/comm/session",
            "tpuft/ddp/allreduce_pytree", "tpuft/ddp/plan", "tpuft/ddp/d2h", "tpuft/ddp/pack",
            "tpuft/ddp/submit", "tpuft/ddp/ring_wait", "tpuft/ddp/h2d",
        }
        assert not names & {"tpuft/comm/op", "tpuft/manager/normalize"}
        assert all(isinstance(s.get("step"), int) for s in mine)
        assert theirs.program_spans.of_replica(spans, 1)
        trips = theirs.program_spans.sync_round_trips(dict(trace=None), spans=spans)
        assert trips and all(0.0 <= unnamed <= whole for whole, unnamed in trips)
    else:
        theirs.test_rehearsal_would_report_the_program_span_metrics(cell, new, root)
    assert walk_reports(cell) <= reported and not reported & device_trace_readers()
    assert device_scopes.load(str(root / "ftbench")) == {}


def test_sharded_leaves_of_two_groups_of_fsdp_2_through_allreduce_pytree(two_groups_of_two, monkeypatch):  # noqa: F405,F811
    """Theirs counts one ``tpuft/manager/normalize`` a collective, which is the
    PER-CALL path's (PR 40): held there, a ring an op, as the Python tier and a
    quantized call still run it.  Through the session the same sharded leaves
    are the four-chip cell's on the chip, and ``tests/test_ddp_buckets.py``
    holds the session's bits and spans."""
    from torchft_tpu.manager import Manager

    monkeypatch.setattr(Manager, "ring_session", lambda self, pieces: None)
    theirs.test_sharded_leaves_of_two_groups_of_fsdp_2_through_allreduce_pytree(two_groups_of_two, monkeypatch)


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


def _direct(t, direct=None, name="DDP_SYNC"):
    event = dict(_sync(t, 10, name=name), bytes=1409384448)
    return event if direct is None else dict(event, direct_bytes=direct)


@pytest.mark.parametrize(
    "events,expects",
    [
        # Mistral on two chips a group: all but the float32 norms lie in shards
        ([_direct(11.0, 1409286144), _direct(12.0, 1409286144)], 100.0 * 1409286144 / 1409384448),
        # a group on one chip has no shards to write
        ([_direct(11.0, 0), _direct(12.0, 0)], 0.0),
        # the parent's events carry no such counter: every leaf was made whole on the host
        ([_direct(11.0), _direct(12.0)], 0.0),
        # events of other names, and of steps outside the window, do not count
        ([_direct(11.0, 7, name="MOE_ROUTE"), _direct(30.0, 0), _direct(12.0, 1409384448)], 100.0),
        ([], None),
    ],
    ids=["sharded_group", "one_chip_group", "parent", "other_events", "no_events"],
)
def test_d2h_direct_pct_on_synthetic_flight_events(events, expects):
    read = spec.load_metric("d2h_direct_pct.hsdp", theirs.BENCH_DIR).read  # noqa: F405
    window = [[dict(t_enter=10.0, t_exit=11.5), dict(t_enter=11.5, t_exit=20.0)], []]
    assert read(dict(window=window, flight=[events, [_direct(12.0, 5)]])) == expects
    assert read(dict(window=window, flight=None)) is None
    assert read(dict(window=[[], []], flight=[events, []])) is None


def test_d2h_direct_pct_is_its_entry_and_lists_the_four_chip_cell():
    reader_entry(
        "d2h_direct_pct.hsdp", cells=[theirs.HSDP_CELL], better="higher",
        source="program_counter", layer="device-host boundary", unit="%", moves="ddp_tokens_per_s_per_chip",
    )


def _split(t, split=None, total=973127680, name="DDP_SYNC"):
    event = dict(name=name, t=t, bytes=total)
    return event if split is None else dict(event, split_bytes=split)


@pytest.mark.parametrize(
    "events,expects",
    [
        # ddp2-steady at the cap of 32 MiB: embedding, head and the three stacked MLP matrices
        ([_split(11.0, 889192448), _split(12.0, 889192448)], 100.0 * 889192448 / 973127680),
        # hsdp2x2-steady: those and the two attention matrices of 67 MB
        ([_split(11.0, 1375731712, 1409368064)], 100.0 * 1375731712 / 1409368064),
        # a tree whose leaves all fit under the cap
        ([_split(11.0, 0), _split(12.0, 0)], 0.0),
        # the parent's events carry no such counter: a leaf over the cap was one bucket
        ([_split(11.0), _split(12.0)], 0.0),
        # events of other names, and of steps outside the window, do not count
        ([_split(11.0, 7, name="MOE_ROUTE"), _split(30.0, 0), _split(12.0, 973127680)], 100.0),
        ([], None),
    ],
    ids=["one_chip_groups", "two_chip_groups", "under_the_cap", "parent", "other_events", "no_events"],
)
def test_d2h_split_pct_on_synthetic_flight_events(events, expects):
    read = spec.load_metric("d2h_split_pct", theirs.BENCH_DIR).read  # noqa: F405
    window = [[dict(t_enter=10.0, t_exit=11.5), dict(t_enter=11.5, t_exit=20.0)], []]
    assert read(dict(window=window, flight=[events, [_split(12.0, 5)]])) == expects
    assert read(dict(window=window, flight=None)) is None
    assert read(dict(window=[[], []], flight=[events, []])) is None


@pytest.mark.parametrize(
    "submits,expects",
    [
        # two round trips of replica 0: the second submit 0.25 and 0.35 s in
        ({1: [0.09, 0.25, 0.4], 2: [0.1, 0.35]}, 300.0),
        # a round trip with one submit (one bucket) has no second; the other counts
        ({1: [0.09], 2: [0.1, 0.5, 0.6]}, 500.0),
        ({1: [0.09], 2: []}, None),
    ],
    ids=["two_trips", "a_trip_of_one_bucket", "no_second_submit"],
)
def test_sync_second_submit_ms_on_synthetic_spans(submits, expects, monkeypatch):
    from ftbench import program_spans

    spans = []
    for step, offsets in submits.items():
        t0 = 10.0 * step
        spans.append(dict(name=program_spans.SYNC, start=t0, end=t0 + 2.0, r=0, step=step))
        spans += [dict(name="tpuft/ddp/submit", start=t0 + o, end=t0 + o + 0.001, r=0, step=step) for o in offsets]
        # the other replica's submits lie inside the same seconds and do not count
        spans.append(dict(name="tpuft/ddp/submit", start=t0 + 0.01, end=t0 + 0.02, r=1, step=step))
    mine = [s for s in spans if s["r"] == 0]
    monkeypatch.setattr(program_spans, "in_stretch", lambda sources, replica=0, spans=None: (mine, len(submits)))
    read = spec.load_metric("sync_second_submit_ms", theirs.BENCH_DIR).read  # noqa: F405
    got = read({})
    assert got is None if expects is None else abs(got - expects) < 1e-6
    monkeypatch.setattr(program_spans, "in_stretch", lambda sources, replica=0, spans=None: None)
    assert read({}) is None


@pytest.mark.parametrize("name,source,unit,better", [
    ("d2h_split_pct", "program_counter", "%", "higher"), ("sync_second_submit_ms", "program_span", "ms", "lower"),
])
def test_the_piece_readers_are_their_entries_and_list_the_two_steady_cells(name, source, unit, better):
    reader_entry(
        name, cells=STEADY_CELLS, better=better,
        source=source, layer="device-host boundary", unit=unit, moves="ddp_tokens_per_s_per_chip",
    )


def _striped(t, striped=None, ring=973127680, total=973127680, name="DDP_SYNC"):
    event = dict(name=name, t=t, bytes=total)
    return event if striped is None else dict(event, striped_bytes=striped, ring_bytes=ring)


@pytest.mark.parametrize(
    "events,expects",
    [
        # the parent's events carry no such counter: its auto is one lane
        ([_striped(11.0), _striped(12.0)], 0.0),
        # four lanes, every frame of at least two floors: three parts of four off lane 0
        ([_striped(11.0, 729845760), _striped(12.0, 729845760)], 75.0),
        # an explicit TORCHFT_RING_LANES=1 on a program that counts: 0, not nothing
        ([_striped(11.0, 0), _striped(12.0, 0)], 0.0),
        # a ring of three sends 4/3 of the tree: the share is of what the rings sent
        ([_striped(11.0, 973127680, ring=1297503574)], 100.0 * 973127680 / 1297503574),
        # events of other names, and of steps outside the window, do not count
        ([_striped(11.0, 7, name="MOE_ROUTE"), _striped(30.0, 0), _striped(12.0, 486563840)], 50.0),
        ([], None),
    ],
    ids=["parent", "four_lanes", "pinned_to_one", "ring_of_three", "other_events", "no_events"],
)
def test_ring_striped_pct_on_synthetic_flight_events(events, expects):
    read = spec.load_metric("ring_striped_pct", theirs.BENCH_DIR).read  # noqa: F405
    window = [[dict(t_enter=10.0, t_exit=11.5), dict(t_enter=11.5, t_exit=20.0)], []]
    got = read(dict(window=window, flight=[events, [_striped(12.0, 5)]]))
    assert got is None if expects is None else got == pytest.approx(expects, abs=1e-9)
    assert read(dict(window=window, flight=None)) is None
    assert read(dict(window=[[], []], flight=[events, []])) is None


def test_the_lane_reader_is_its_entry_and_lists_the_two_steady_cells():
    entry = reader_entry(
        "ring_striped_pct", cells=STEADY_CELLS, better="higher",
        source="program_counter", layer="host data plane", unit="%", moves="ddp_tokens_per_s_per_chip",
    )
    # a layer that other readers name too, not one of its own
    assert any(m["layer"] == entry["layer"] for m in bench()["per_layer"] if m["name"] != entry["name"])


def _served(sources, ahead):
    """Theirs' kill run with ``ahead_bytes`` on the survivor's HEAL_SERVE_END
    events: ``ahead`` of each kill's serve, and all but nothing of the other
    replica's init_sync before the first kill, which is no heal of the run."""
    run = copy.deepcopy(sources)
    serves = [e for e in run["kill"]["survivor_events"] if e["name"] == "HEAL_SERVE_END"]
    assert len(serves) == 1 + len(ahead)
    for event, share in zip(serves, [1.0] + list(ahead)):
        event["ahead_bytes"] = int(share * event["bytes"])
    return run


@pytest.mark.parametrize(
    "ahead,expects",
    [
        # the first leaf of 268 of the 2,919 MB cannot come ahead
        ((0.908, 0.908), 90.8),
        # the second kill's healer asked again for a staged plan: all ahead
        ((0.9, 1.0), 95.0),
        # nothing asked ahead (a state of numpy leaves) reads 0, not nothing
        ((0.0, 0.0), 0.0),
    ],
    ids=["first_leaf_exposed", "one_serve_all_ahead", "numpy_state"],
)
def test_heal_serve_ahead_pct_on_synthetic_flight_events(ahead, expects):
    read = spec.load_metric("heal_serve_ahead_pct", theirs.BENCH_DIR).read  # noqa: F405
    assert read(_served(theirs._kill_sources(), ahead)) == pytest.approx(expects, abs=1e-6)


@pytest.mark.parametrize(
    "sources",
    [theirs._kill_sources(), theirs._kill_sources(with_spans=False), dict(kill=None, trace=None), dict(trace=None)],
    ids=["parent", "parent_before_pr26", "no_kill", "steady_cell"],
)
def test_heal_serve_ahead_pct_reads_nothing_from_a_run_without_the_field(sources):
    """The parent's HEAL_SERVE_END carries ``bytes``, ``d2h_s``, ``write_s``
    and no ``ahead_bytes``: nothing, and no error (the driver runs this
    reader over the parent's checkout too)."""
    assert spec.load_metric("heal_serve_ahead_pct", theirs.BENCH_DIR).read(sources) is None  # noqa: F405


def test_heal_serve_ahead_pct_is_its_entry_and_lists_the_kill_cell():
    reader_entry("heal_serve_ahead_pct", cells=["mistral7b-ddp2-kill"], better="higher")


def _with_in_ring(spans, flags):
    """``spans`` with ``in_ring`` set on replica 0's ``tpuft/manager/normalize``
    spans in turn (None: the span carries no such attribute, the parent's)."""
    out, flags = [], list(flags)
    for s in spans:
        s = dict(s)
        if s["name"] == "tpuft/manager/normalize" and s["r"] == theirs.R0:
            flag = flags.pop(0)
            if flag is not None:
                s["in_ring"] = flag
        out.append(s)
    assert not flags
    return out


@pytest.mark.parametrize(
    "flags,expects",
    [
        # every collective of both traced steps was averaged by its ring
        ([1, 1, 1, 1], 100.0),
        # a program from before PR 40: the callback divides every sum
        ([None] * 4, 0.0),
        # the quantized ring hands back sums: one collective in four
        ([1, 0, 1, 1], 75.0),
        ([0, 0, 0, 0], 0.0),
        # as the profiler hands a TraceAnnotation's argument back
        (["1", "1", "0", "0"], 50.0),
    ],
    ids=["all_in_ring", "parent", "one_quantized", "none", "strings"],
)
def test_normalize_in_ring_pct_on_the_synthetic_planes(run, flags, expects, monkeypatch):  # noqa: F405
    read = spec.load_metric("normalize_in_ring_pct", theirs.BENCH_DIR).read  # noqa: F405
    spans = _with_in_ring(run["spans"], flags)
    monkeypatch.setattr(program_spans, "load", lambda bench_dir=None: spans)
    for sources in (run["sources"], dict(run["sources"], trace=None)):
        assert read(sources) == pytest.approx(expects)
    # sync_normalize_ms beside it reads the same spans' seconds, whatever they carry
    assert spec.load_metric("sync_normalize_ms", theirs.BENCH_DIR).read(run["sources"]) == pytest.approx(80.0)  # noqa: F405
    # a run with no such span (a one-replica cell), and one with no span at all
    bare = [s for s in spans if s["name"] != "tpuft/manager/normalize"]
    monkeypatch.setattr(program_spans, "load", lambda bench_dir=None: bare)
    assert read(run["sources"]) is None
    monkeypatch.setattr(program_spans, "load", lambda bench_dir=None: [])
    assert read(run["sources"]) is None


def test_normalize_in_ring_pct_reads_the_attribute_out_of_a_profile():
    """Through the protobuf: an integer argument of the annotation comes back
    as the span's ``in_ring``; the other replica's spans do not count."""
    from jax.profiler import ProfileData

    spans = []
    for step, at in ((5, 0.0), (6, theirs.STEP_MS)):
        for thread, name, start, dur, stats in theirs._step_spans(step, at):
            if name == "tpuft/manager/normalize":
                stats = dict(stats, in_ring=1, bytes=1 << 20)
            spans.append((thread, name, start, dur, stats))
        spans.append(("op1", "tpuft/manager/normalize", at + 520, 1, dict(r=theirs.R1, step=step, in_ring=0)))
    profile = ProfileData.from_text_proto(theirs._text_proto(spans, [("fusion.1", 0, 40)], [("jit__step(1)", 0, 40)]))
    read_back = program_spans.from_profile(profile)
    mine = [s for s in read_back if s["name"] == "tpuft/manager/normalize" and s["r"] == theirs.R0]
    assert len(mine) == 4 and all(s["in_ring"] == 1 for s in mine)
    import unittest.mock as mock

    with mock.patch.object(program_spans, "load", lambda bench_dir=None: read_back):
        assert spec.load_metric("normalize_in_ring_pct", theirs.BENCH_DIR).read(dict(trace=None)) == 100.0  # noqa: F405


def test_normalize_in_ring_pct_is_its_entry_and_lists_the_steady_two_replica_cell():
    reader_entry("normalize_in_ring_pct", cells=["mistral7b-ddp2-steady"], better="higher")


def _round_trip(step, at, buckets):
    """One round trip of replica 0 as ``program_spans.from_profile`` gives
    it (seconds): ``buckets`` as (megabytes, when its leaves have landed in
    ms from the round trip's start); a bucket is packed in 2 ms and
    submitted, the op thread rings (2/3) and divides (1/3) them in order,
    1 ms a megabyte."""
    spans = []

    def s(thread, name, start, end, **stats):
        spans.append(dict(stats, name=name, start=at + start / 1e3, end=at + end / 1e3,
                          line=("/host:CPU", thread), r=theirs.R0, step=step))

    now, op_free = 10.0, 0.0
    s(0, "tpuft/ddp/plan", 0.0, now)
    for b, (mbytes, lands) in enumerate(buckets):
        s(0, "tpuft/ddp/d2h", now, max(now + 0.1, lands), bucket=b)
        now = max(now + 0.1, lands)
        s(0, "tpuft/ddp/pack", now, now + 2.0, bucket=b)
        s(0, "tpuft/ddp/submit", now + 2.0, now + 2.5, bucket=b)
        now += 2.5
        begins = max(now, op_free)
        op_free = begins + mbytes
        s(1, "tpuft/comm/op", begins, begins + 2 * (op_free - begins) / 3, k=b)
        s(1, "tpuft/manager/normalize", begins + 2 * (op_free - begins) / 3, op_free)
    s(0, program_spans.SYNC, 0.0, now)  # the train thread's piece
    s(2, program_spans.SYNC, now, op_free + 40.0)  # the gather thread's
    s(2, "tpuft/ddp/h2d", op_free, op_free + 40.0, bucket=len(buckets) - 1)
    return spans


# the parent's shape (every copy started at once: the ten buckets land
# together, 1,390 ms in, embed first in line) and the change's (a small
# bucket first, then by falling size, each landing when its bytes are across)
MB = [268.0, 117.0, 117.0, 117.0, 8.0, 34.0, 34.0, 8.0, 268.0, 0.02]
PARENT_SHAPE = [(mb, 1390.0) for mb in MB]
FLOW = [0.02, 268.0, 268.0, 117.0, 117.0, 117.0, 34.0, 34.0, 8.0, 8.0]
CHANGE_SHAPE = [(mb, 10.0 + 1.42 * sum(FLOW[: k + 1])) for k, mb in enumerate(FLOW)]


@pytest.mark.parametrize(
    "shape,first_submit_ms,beside_between",
    [
        # the rings begin when the first bucket is packed, 23 ms before the
        # train thread has walked through the other nine that lie there
        (PARENT_SHAPE, 1392.0, (0.0, 3.0)),
        # the op thread's 971 ms but the 88 ms it is behind at the last
        # landing: from the last 117 MB bucket on, 201 ms of ring against
        # 119 ms of transfer
        (CHANGE_SHAPE, 12.1, (90.0, 92.0)),
    ],
    ids=["parent", "change"],
)
def test_order_readers_on_synthetic_round_trips(shape, first_submit_ms, beside_between, monkeypatch):
    spans = sorted(
        _round_trip(5, 1.0, shape) + _round_trip(6, 4.0, shape),
        key=lambda s: s["start"],
    )
    monkeypatch.setattr(program_spans, "load", lambda bench_dir=None: spans)
    sources = dict(trace=None, replicas=2)
    first = spec.load_metric("sync_first_submit_ms", theirs.BENCH_DIR).read  # noqa: F405
    beside = spec.load_metric("ring_beside_d2h_pct", theirs.BENCH_DIR).read  # noqa: F405
    assert first(sources) == pytest.approx(first_submit_ms, abs=1e-6)
    # by hand: everything the op thread did before the last landing
    trip = [s for s in spans if s["step"] == 5]
    landed = max(s["end"] for s in trip if s["name"] == "tpuft/ddp/d2h")
    ops = [s for s in trip if s["name"] in ("tpuft/comm/op", "tpuft/manager/normalize")]
    total = sum(s["end"] - s["start"] for s in ops)
    assert total == pytest.approx(0.97102, abs=1e-5)
    beside_pct = 100 * sum(max(0.0, min(s["end"], landed) - s["start"]) for s in ops) / total
    assert beside_between[0] < beside_pct < beside_between[1]
    assert beside(sources) == pytest.approx(beside_pct, abs=1e-6)
    # a program without spans, and one whose round trips hold no such stage
    monkeypatch.setattr(program_spans, "load", lambda bench_dir=None: [])
    assert first(sources) is None and beside(sources) is None
    bare = [s for s in spans if s["name"] in (program_spans.SYNC, "tpuft/ddp/plan")]
    monkeypatch.setattr(program_spans, "load", lambda bench_dir=None: bare)
    assert first(sources) is None and beside(sources) is None


def test_order_readers_on_the_synthetic_planes(run, monkeypatch):  # noqa: F405
    """Theirs is a two-bucket round trip in which the first ring runs beside
    the second bucket's transfer: submit 220 ms in; of the op thread's 220 ms
    the first ring's 60 and 32 of its division lie before the last landing."""
    monkeypatch.setattr(program_spans, "load", lambda bench_dir=None: run["spans"])
    for sources in (run["sources"], dict(run["sources"], trace=None)):
        assert spec.load_metric("sync_first_submit_ms", theirs.BENCH_DIR).read(sources) == pytest.approx(220.0, abs=1e-6)  # noqa: F405
        assert spec.load_metric("ring_beside_d2h_pct", theirs.BENCH_DIR).read(sources) == pytest.approx(100 * 92 / 220, abs=1e-6)  # noqa: F405
