"""Host data plane: how far apart the replicas enter the same collective:
per step, the sum over its collectives of the distance between the two
replicas' starts of the k-th ``tpuft/comm/op``.  A ring cannot move before
its later member arrives.  Mean over the traced steps."""

META = dict(source="program_span", layer="host data plane", unit="ms", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    from ftbench import program_spans
    from ftbench.sources import mean_ms

    found = program_spans.all_in_stretch(sources)
    if found is None:
        return None
    return mean_ms([skew for _, skew in program_spans.peer_skew_s(found[0])])
