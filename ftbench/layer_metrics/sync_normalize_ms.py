"""Host data plane: spans ``tpuft/manager/normalize`` (one a collective) on
replica 0's communicator op thread: ``Manager.allreduce`` turning the ring's
sum into the average, in the future's done-callback, so the next bucket's
ring waits for it.  Summed over a step's collectives, mean over the traced
steps."""

META = dict(source="program_span", layer="host data plane", unit="ms", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    from ftbench import program_spans

    return program_spans.per_step_ms(sources, "tpuft/manager/normalize")
