"""Host data plane: spans ``tpuft/manager/normalize`` (one a collective) on
replica 0's communicator op thread: ``Manager.allreduce``'s done-callback,
which the next bucket's ring waits for.  Until PR 40 it turned the ring's sum
into the average; since then the ring is handed the divisor and returns the
average, and the callback divides only what still comes back as sums (the
quantized ring, ``in_ring=0``): the callback's microseconds in both two-group
cells.  Summed over a step's collectives, mean over the traced steps.

NO ENTRY of ``BENCHMARK.json`` names this file since PR 66, so no run loads
it: where a round trip's rings are a session (PR 60, both two-group cells) no
callback runs and the span never opens.  The file stays because tier-1's
``tests/test_ftbench_program_spans.py`` loads it by name and a ``benchmark`` PR
edits nothing there (``ftbench/tests/test_ftbench_spec.py``
``FILES_WITHOUT_AN_ENTRY``; README.md, "What the benchmark has")."""

META = dict(source="program_span", layer="host data plane", unit="ms", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    from ftbench import program_spans

    return program_spans.per_step_ms(sources, "tpuft/manager/normalize")
