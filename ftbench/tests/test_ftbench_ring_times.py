"""The six readers of where the ring says its time goes (PR 54:
``layer_metrics/_ring.py`` and ``ring_rx_ms`` ... ``ring_tail_ms``; seven until
PR 58 retired ``ring_average_ms``, whose pass no cell's ring takes since PR 57) on
hand-made ``sources``: DDP_SYNC events that carry the fields, events that do
not (what a parent's program writes), and none in the window; and each
reader's entry in ``BENCHMARK.json``."""

import json
import os

import pytest

from ftbench import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "ftbench")
CELLS = ("mistral7b-ddp2-steady", "mistral7b-hsdp2x2-steady")

# reader -> DDP_SYNC's field, and the seconds two round trips of the window carry
READERS = {
    "ring_rx_ms": ("ring_rx_s", (0.300, 0.340)),
    "ring_add_ms": ("ring_add_s", (0.100, 0.120)),
    "ring_tx_ms": ("ring_tx_s", (0.250, 0.270)),
    "ring_reduce_phase_ms": ("ring_reduce_s", (0.280, 0.300)),
    "ring_gather_phase_ms": ("ring_gather_s", (0.230, 0.250)),
    "ring_tail_ms": ("ring_tail_s", (0.040, 0.060)),
}
WINDOW = [[dict(t_enter=10.0, t_exit=11.5), dict(t_enter=11.5, t_exit=20.0)], []]


def _sync(t, name="DDP_SYNC", total=973127680, **fields):
    return dict(name=name, t=t, bytes=total, **fields)


def _counted(t, which, name="DDP_SYNC"):
    """A round trip of a program that counts: every field, the ``which``-th value."""
    return _sync(t, name=name, **{field: values[which] for field, values in READERS.values()})


def _read(name, events, other=()):
    read = spec.load_metric(name, BENCH_DIR).read
    return read(dict(window=WINDOW, flight=[list(events), list(other)]))


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_counting_programs_events_read_as_their_mean_in_ms(name):
    _, (first, second) = READERS[name]
    events = [
        _counted(11.0, 0), _counted(12.0, 1),
        _counted(30.0, 0),  # a step outside the window
        _counted(12.5, 0, name="MOE_ROUTE"),  # an event of another name
        _sync(13.0),  # a round trip whose epoch changed under it: no field, not a zero
    ]
    # the other replica's events are not read
    got = _read(name, events, other=[_counted(12.0, 0)])
    assert got == pytest.approx(1000.0 * (first + second) / 2, abs=1e-9)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_parents_events_read_as_nothing(name):
    # the parent's DDP_SYNC carries bytes and stage seconds and none of the fields
    events = [_sync(11.0, ring_bytes=973127680, striped_bytes=729845760, ring_wait_s=0.5), _sync(12.0)]
    assert _read(name, events) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_no_event_in_the_window_reads_as_nothing(name):
    read = spec.load_metric(name, BENCH_DIR).read
    assert _read(name, [_counted(30.0, 0)]) is None
    assert _read(name, []) is None
    assert read(dict(window=WINDOW, flight=None)) is None
    assert read(dict(window=[[], []], flight=[[_counted(11.0, 0)], []])) is None
    # a field that is there and 0 is a reading (a ring that hands back sums divides nothing)
    field, _ = READERS[name]
    assert _read(name, [_sync(11.0, **{field: 0.0})]) == 0.0


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_reader_is_its_entry_and_lists_the_two_group_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert set(CELLS) <= set(entry["workloads"]) and entry["better"] == "lower"
    meta = spec.load_metric(name, BENCH_DIR).META
    assert meta == {k: entry[k] for k in ("source", "layer", "unit", "moves")}
    assert meta == dict(
        source="program_counter", layer="host data plane", unit="ms", moves="ddp_tokens_per_s_per_chip"
    )


def test_the_helper_is_no_metric_of_its_own():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = {m["name"] for m in json.load(f)["per_layer"]}
    assert "_ring" not in names and set(READERS) <= names
