"""Host data plane: how much of the op thread's work runs BESIDE the
gradients' transfer to the host and not after it: of the seconds of replica
0's ``tpuft/comm/op`` and ``tpuft/manager/normalize`` spans inside a round
trip (span ``tpuft/ddp/allreduce_pytree``), the share that lies before the
end of that round trip's last ``tpuft/ddp/d2h`` span.  100 x the sum of the
one over the sum of the other, over the round trips of the traced steps.
About 0 where the buckets land together and the rings start only then; the
nearer 100, the less of the ring stands in series with the transfer.  None
where there is no such span.

NO ENTRY of ``BENCHMARK.json`` names this file since PR 66, so no run loads
it: where a round trip's rings are a session (PR 60, both two-group cells) no
trace holds either span.  The file stays because tier-1's
``tests/test_ftbench_program_spans.py`` loads it by name and a ``benchmark`` PR
edits nothing there (``ftbench/tests/test_ftbench_spec.py``
``FILES_WITHOUT_AN_ENTRY``; README.md, "What the benchmark has")."""

META = dict(source="program_span", layer="host data plane", unit="%", moves="ddp_tokens_per_s_per_chip")

OP_THREAD = ("tpuft/comm/op", "tpuft/manager/normalize")


def read(sources):
    from ftbench import program_spans

    found = program_spans.in_stretch(sources)
    if found is None:
        return None
    spans, _ = found
    total = beside = 0.0
    for trip in program_spans.merged(spans, program_spans.SYNC):
        inside = [s for s in spans if trip["start"] <= s["start"] <= trip["end"]]
        landed = [s["end"] for s in inside if s["name"] == "tpuft/ddp/d2h"]
        if not landed:
            continue
        for s in inside:
            if s["name"] in OP_THREAD:
                total += s["end"] - s["start"]
                beside += max(0.0, min(s["end"], max(landed)) - s["start"])
    return 100.0 * beside / total if total else None
