"""Device-host boundary: spans ``tpuft/ddp/h2d`` (one a bucket) on replica
0's gather thread: a ``device_put`` a leaf of the averaged bucket.  Summed
over a step's buckets, mean over the traced steps."""

META = dict(source="program_span", layer="device-host boundary", unit="ms", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    from ftbench import program_spans

    return program_spans.per_step_ms(sources, "tpuft/ddp/h2d")
