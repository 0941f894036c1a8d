"""The reader ``ftbench/layer_metrics/moe_buffer_fill_pct.py`` (PR 42) on
made MOE_ROUTE flight events, in the four expert cells, and its entry in
``BENCHMARK.json``.  It sits here and not beside the cells' own tests because a
file under ``ftbench/`` is the benchmark's and only a ``benchmark`` issue may
edit it (PERF.md section 7); the made sources are those files' own.  That the
traced walk of each cell would report it is held by the cells' views
(``tests/_ftbench_view.py``, ``walk_reports``).  No number here is a device's."""

import pytest

from ftbench import spec
from ftbench.tests import test_ftbench_indexed, test_ftbench_ling, test_ftbench_ssm, test_ftbench_swa
from tests._ftbench_view import BENCH_DIR, reader_entry

# a cell's own test file (its ``_trace_sources``: two steps of a window that
# opens at 1.0) and its expert layers
CELLS = {
    "ling3flash-ws1-seq8k": (test_ftbench_ling, 6),
    "keye2-ws1-seq16k": (test_ftbench_indexed, 10),
    "nemotron3nano-ws1-seq16k": (test_ftbench_ssm, 4),
    "trinitymini-ws1-seq16k": (test_ftbench_swa, 7),
}
OPS = [("%fusion.1 = bf16[2048,4096] fusion(%p)", 1.0, 0.1), ("%fusion.1 = bf16[2048,4096] fusion(%p)", 1.7, 0.1)]


def _event(t, rows, buffer=None):
    """One step's event: a layer's rows, and where given the buffer rows they went through."""
    event = dict(name="MOE_ROUTE", t=t, rows_here=list(rows), load_max=[r / 8 for r in rows], load_mean=[r / 16 for r in rows])
    return event if buffer is None else dict(event, buffer_rows=list(buffer))


def _read(cell_name, flight):
    module, _ = CELLS[cell_name]
    sources = module._trace_sources(spec.load_cell(cell_name), OPS, flight)
    read = spec.load_metric("moe_buffer_fill_pct", BENCH_DIR).read
    assert read(dict(sources, trace=None)) == read(sources)  # a counter: it needs no trace
    return read(sources)


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_fill_is_the_rows_over_the_buffer_rows_of_the_windows_events(cell_name):
    layers = CELLS[cell_name][1]
    # one layer of the second step needs a second pass; the event before the window is left out
    flight = [
        _event(1.4, [16000.0] * layers, [20480.0] * layers),
        _event(1.9, [16000.0] * (layers - 1) + [21000.0], [20480.0] * (layers - 1) + [40960.0]),
        _event(0.5, [9.0] * layers, [131072.0] * layers),
    ]
    rows = 16000.0 * (2 * layers - 1) + 21000.0
    buffer = 20480.0 * (2 * layers - 1) + 40960.0
    assert _read(cell_name, flight) == pytest.approx(100 * rows / buffer)
    assert 60 < _read(cell_name, flight) < 100
    # the parent's buffer, had it counted: four times the uniform load whatever arrived
    assert _read(cell_name, [_event(1.4, [16384.0] * layers, [65536.0] * layers)]) == pytest.approx(25.0)


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_a_program_that_records_no_buffer_rows_reads_nothing(cell_name):
    """The parent commit's event has no ``buffer_rows``: the reader returns
    None, never raises, and the line leaves the metric out."""
    layers = CELLS[cell_name][1]
    assert _read(cell_name, [_event(1.4, [16000.0] * layers), _event(1.9, [16000.0] * layers)]) is None
    # one old event among new ones (a life that began on another program): still nothing
    assert _read(cell_name, [_event(1.4, [16000.0] * layers, [20480.0] * layers), _event(1.9, [16000.0] * layers)]) is None
    assert _read(cell_name, []) is None
    assert _read(cell_name, [_event(0.5, [9.0] * layers, [512.0] * layers)]) is None  # none in the window
    assert _read(cell_name, [_event(1.4, [0.0] * layers, [0.0] * layers)]) is None  # no expert layer at all


def test_the_reader_is_its_entry_and_lists_the_four_expert_cells():
    entry = reader_entry(
        "moe_buffer_fill_pct", cells=CELLS, better="higher",
        source="program_counter", layer="experts", unit="%", moves="tokens_per_s_per_chip",
    )
    # only cells that have experts: the dispatch's own time is read in each of them
    assert set(entry["workloads"]) <= set(reader_entry("moe_dispatch_ms")["workloads"])
