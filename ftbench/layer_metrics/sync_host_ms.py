"""Device-host boundary: the replica-dimension round trip of one step as the
program sees it: span ``tpuft/ddp/allreduce_pytree`` of replica 0, from the
train thread's entry to the composite work's completion on the gather
thread.  Mean over the traced steps."""

META = dict(source="program_span", layer="device-host boundary", unit="ms", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    from ftbench import program_spans
    from ftbench.sources import mean_ms

    trips = program_spans.sync_round_trips(sources)
    return mean_ms([whole for whole, _ in trips]) if trips else None
