"""Host data plane: spans ``tpuft/comm/op`` (one a collective) on replica
0's communicator op thread: the ring allreduce itself, from the moment the
op thread takes the bucket to its result.  Summed over a step's
collectives, mean over the traced steps."""

META = dict(source="program_span", layer="host data plane", unit="ms", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    from ftbench import program_spans

    return program_spans.per_step_ms(sources, "tpuft/comm/op")
