"""Compiled step: model FLOP/s utilization of the chip WHILE the step runs,
for the architecture ``sambay``: the operations forward and backward need for
the matrices a token passes through (the tied head among them), for attention
over the LIVE pairs and for the recurrence (``sambay_flops.train_flops_per_token``;
recomputed work not counted) over device time and the chip's bf16 peak.  The
share of the whole step that bounds any later claim in the cell."""

META = dict(source="device_trace", layer="compiled step", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import flops
    from ftbench.layer_metrics import _sambay
    from ftbench.sources import step_device_s

    s = step_device_s(sources)
    if s is None or not _sambay.is_mine(sources):
        return None
    per_token = _sambay.flops().train_flops_per_token(sources["shapes"], sources["seq"])
    tokens_per_s = sources["tokens_per_step_per_replica"] / s
    return 100.0 * tokens_per_s * per_token / flops.peaks(sources["device_kind"])["bf16_flops"]
