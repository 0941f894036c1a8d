"""Compiled step: model FLOP/s utilization of the chip WHILE the step runs,
in every model cell: the operations forward and backward NEED a token (what
the cell's architecture counts from its shapes:
``sources["architecture"].flops.train_flops_per_token``: 6 a matrix-product
parameter a token really touches, the attention's live pairs, a recurrence's
products; recomputed work and the dead blocks a kernel walks never counted)
times the tokens a chip takes a second of device time, over the chip's bf16
peak.  End to end the utilization is lower by the idle share.  The share of
the whole step that bounds any later claim in a cell: a kernel taken off the
path leaves its roofline silent, and this still counts its work.

ONE reader since PR 66 (ten before it, one an architecture behind its own
``is_mine``): a new architecture's file brings ``flops`` and its cell's name
joins this entry's list."""

META = dict(source="device_trace", layer="compiled step", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import flops
    from ftbench.sources import arch_flops, chips_per_group, step_device_s

    per_token = arch_flops(sources, "train_flops_per_token")
    s = step_device_s(sources)
    if s is None or per_token is None:
        return None
    tokens_per_s = sources["tokens_per_step_per_replica"] / chips_per_group(sources) / s
    return (
        100.0 * tokens_per_s * per_token(sources["shapes"], sources["seq"])
        / flops.peaks(sources["device_kind"])["bf16_flops"]
    )
