"""Device-host boundary: spans ``tpuft/ddp/pack`` (one a bucket) on replica
0's train thread: allocating the bucket and copying the leaves into it.
Summed over a step's buckets, mean over the traced steps."""

META = dict(source="program_span", layer="device-host boundary", unit="ms", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    from ftbench import program_spans

    return program_spans.per_step_ms(sources, "tpuft/ddp/pack")
