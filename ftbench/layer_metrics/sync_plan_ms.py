"""Device-host boundary: span ``tpuft/ddp/plan`` on replica 0's train thread:
flattening the gradient tree, starting every leaf's copy to the host,
grouping the leaves into buckets.  Milliseconds a step, mean over the
traced steps."""

META = dict(source="program_span", layer="device-host boundary", unit="ms", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    from ftbench import program_spans

    return program_spans.per_step_ms(sources, "tpuft/ddp/plan")
