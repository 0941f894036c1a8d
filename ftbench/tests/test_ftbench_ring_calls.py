"""The reader of how many buckets one ring call carries (PR 60:
``layer_metrics/ring_buckets_per_call.py``) on hand-made ``sources``: DDP_SYNC
events of a program whose rings are calls of their own, of one whose round
trip is a session, of a parent's that counts no calls, and none in the window;
and the reader's entry in ``BENCHMARK.json``."""

import json
import os

import pytest

from ftbench import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "ftbench")
CELLS = ("mistral7b-ddp2-steady", "mistral7b-hsdp2x2-steady")
NAME = "ring_buckets_per_call"
WINDOW = [[dict(t_enter=10.0, t_exit=11.5), dict(t_enter=11.5, t_exit=20.0)], []]


def _sync(t, name="DDP_SYNC", total=973127680, **fields):
    return dict(name=name, t=t, bytes=total, **fields)


def _read(events, other=()):
    read = spec.load_metric(NAME, BENCH_DIR).read
    return read(dict(window=WINDOW, flight=[list(events), list(other)]))


@pytest.mark.parametrize(
    "events,expects",
    [
        # the session: one call a round trip, whatever the buckets
        ([_sync(11.0, buckets=58, ring_calls=1), _sync(12.0, buckets=58, ring_calls=1)], 58.0),
        ([_sync(11.0, buckets=89, ring_calls=1)], 89.0),
        # the per-call path: a call a bucket
        ([_sync(11.0, buckets=58, ring_calls=58), _sync(12.0, buckets=58, ring_calls=58)], 1.0),
        # a round trip of each (a reconfiguration's first step fell back): the sums' ratio
        ([_sync(11.0, buckets=58, ring_calls=1), _sync(12.0, buckets=58, ring_calls=58)], 116 / 59),
        # outside the window, another event's name, an epoch that changed under the
        # round trip (no counter): none is read
        (
            [
                _sync(11.0, buckets=58, ring_calls=1), _sync(30.0, buckets=58, ring_calls=58),
                _sync(12.0, name="MOE_ROUTE", buckets=58, ring_calls=58), _sync(13.0, buckets=58),
            ],
            58.0,
        ),
    ],
)
def test_buckets_over_calls(events, expects):
    # the other replica's events are not read
    assert _read(events, other=[_sync(11.0, buckets=58, ring_calls=58)]) == pytest.approx(expects)


def test_a_parents_events_read_as_nothing():
    # the parent's DDP_SYNC carries buckets and the seven seconds and no ring_calls
    events = [_sync(11.0, buckets=58, ring_bytes=973127680, ring_tail_s=0.06), _sync(12.0, buckets=58)]
    assert _read(events) is None


def test_no_event_in_the_window_reads_as_nothing():
    read = spec.load_metric(NAME, BENCH_DIR).read
    assert _read([_sync(30.0, buckets=58, ring_calls=1)]) is None
    assert _read([]) is None
    assert read(dict(window=WINDOW, flight=None)) is None
    assert read(dict(window=[[], []], flight=[[_sync(11.0, buckets=58, ring_calls=1)], []])) is None
    # a round trip that made no ring call (its epoch changed before the first) is no reading
    assert _read([_sync(11.0, buckets=58, ring_calls=0)]) is None


def test_the_reader_is_its_entry_and_lists_the_two_steady_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == NAME]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert entry["workloads"] == list(CELLS) and entry["better"] == "higher"
    meta = spec.load_metric(NAME, BENCH_DIR).META
    assert meta == {k: entry[k] for k in ("source", "layer", "unit", "moves")}
    assert meta == dict(
        source="program_counter", layer="host data plane", unit="buckets/call", moves="ddp_tokens_per_s_per_chip"
    )
