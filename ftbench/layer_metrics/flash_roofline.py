"""Kernels: the flash attention kernels' share of their roofline.  The
least time the chip could take for the attention of one step (forward and
backward, causal; ``flops.flash_step_flops`` and ``flash_step_bytes``)
over the device time of the kernels in the trace.  At S=2048, D=128 the
bound is compute (about 340 FLOP a byte against the chip's 240)."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")

# The trace names an operation by its HLO text.  The step's only Mosaic
# kernels are ops/flash_attention.py's three (forward, dq, dkv), and each is
# a custom-call whose target is tpu_custom_call (the kernels carry no name
# of their own: PERF.md, Open questions).
KERNELS = r"tpu_custom_call"


def read(sources):
    from ftbench import flops, trace_reduce
    from ftbench.sources import traced_stretch

    stretch = traced_stretch(sources)
    if stretch is None:
        return None
    a, b, steps = stretch
    device = sources["trace"]["per_device"]
    ops = trace_reduce.clip(device[min(device)]["ops"], a, b)
    seconds = trace_reduce.matching_seconds(ops, KERNELS)
    if seconds <= 0.0:
        return None
    sharing = sources["replicas"] if sources["groups_share_chip"] else 1
    chips_per_group = 1 if sources["groups_share_chip"] else sources["chips"] // sources["replicas"]
    rows = sources["rows_per_replica"] / chips_per_group
    per_step = seconds / steps / sharing
    return flops.roofline_pct(
        flops.flash_step_flops(sources["shapes"], rows, sources["seq"]),
        flops.flash_step_bytes(sources["shapes"], rows, sources["seq"]),
        per_step,
        sources["device_kind"],
    )["pct"]
