"""Device-host boundary: what of the round trip (``sync_host_ms``) no stage
span covers: the parent's duration less the union, clipped to the parent, of
the ``tpuft/ddp/*``, ``tpuft/comm/*`` and ``tpuft/manager/normalize`` spans
on any thread of replica 0.  The test of the tiling.  Mean over the traced
steps."""

META = dict(source="program_span", layer="device-host boundary", unit="ms", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    from ftbench import program_spans
    from ftbench.sources import mean_ms

    trips = program_spans.sync_round_trips(sources)
    return mean_ms([unnamed for _, unnamed in trips]) if trips else None
