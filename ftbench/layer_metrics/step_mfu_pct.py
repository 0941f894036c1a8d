"""Compiled step: model FLOP/s utilization of the chip WHILE the step
runs: the operations forward and backward need (``flops.py``; recomputed
work not counted) over device time and the chip's bf16 peak.  End to end
the utilization is lower by the idle share."""

META = dict(source="device_trace", layer="compiled step", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import flops
    from ftbench.sources import step_device_s

    s = step_device_s(sources)
    if s is None:
        return None
    chips_per_group = 1 if sources["groups_share_chip"] else sources["chips"] // sources["replicas"]
    tokens_per_chip = sources["tokens_per_step_per_replica"] / chips_per_group
    return flops.mfu_pct(tokens_per_chip / s, sources["shapes"], sources["seq"], sources["device_kind"])
