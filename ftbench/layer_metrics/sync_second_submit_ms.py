"""Device-host boundary: how long a round trip runs before the op thread is
given its first LARGE bucket: from the start of replica (or group) 0's span
``tpuft/ddp/allreduce_pytree`` to the start of the SECOND
``tpuft/ddp/submit`` inside it.  The first submit is the plan's smallest
bucket (``sync_first_submit_ms``: the gradient program's wait and a few
kilobytes), the second the largest, and everything the op thread does
follows it.  Mean over the round trips of the traced steps.  A program whose
largest bucket is a whole leaf reads that leaf's landing here; one that
brings a leaf over the cap across in pieces (PR 46) reads one cap's worth.
None where there is no such span or no round trip with two submits."""

META = dict(source="program_span", layer="device-host boundary", unit="ms", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    from ftbench import program_spans
    from ftbench.sources import mean_ms

    found = program_spans.in_stretch(sources)
    if found is None:
        return None
    spans, _ = found
    submits = sorted(s["start"] for s in spans if s["name"] == "tpuft/ddp/submit")
    seconds = []
    for trip in program_spans.merged(spans, program_spans.SYNC):
        inside = [t for t in submits if trip["start"] <= t <= trip["end"]]
        if len(inside) > 1:
            seconds.append(inside[1] - trip["start"])
    return mean_ms(seconds)
