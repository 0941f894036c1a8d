"""Device-host boundary: how long a round trip runs before the op thread is
given anything: from the start of replica 0's span
``tpuft/ddp/allreduce_pytree`` to the start of the first ``tpuft/ddp/submit``
inside it (the first bucket landed, packed and handed to its ring).  Mean
over the round trips of the traced steps.  A program that starts every
leaf's copy to the host at once reads the whole transfer here (the copies
land together); one that brings the buckets over in an order, a few at a
time, reads the first bucket's transfer.  None where there is no such span."""

META = dict(source="program_span", layer="device-host boundary", unit="ms", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    from ftbench import program_spans
    from ftbench.sources import mean_ms

    found = program_spans.in_stretch(sources)
    if found is None:
        return None
    spans, _ = found
    submits = [s["start"] for s in spans if s["name"] == "tpuft/ddp/submit"]
    firsts = []
    for trip in program_spans.merged(spans, program_spans.SYNC):
        inside = [t for t in submits if trip["start"] <= t <= trip["end"]]
        if inside:
            firsts.append(min(inside) - trip["start"])
    return mean_ms(firsts)
