"""Kernels: the share of their roofline of the launches ``flash_fwd``,
``flash_dq`` and ``flash_dkv`` (``ops/flash_attention.py`` over every earlier
key: a windowed layer's are named ``flash_win_*`` and another reader's), in
every cell that runs them.  The least time the chip could take for that
attention of one step, forward and backward, over the three kernels' device
time on the first chip.  What it needs is the architecture's count
(``sources["architecture"].flops.flash_step(shapes, rows, seq)``: the causal
half at the architecture's own heads, 128, 192 over 128 or 256, as often as
its layers and passes launch them; the recomputed scores and a ``flash_fwd``
run again to rematerialise a layer not credited).  At 2,048 positions and
above the bound is compute.

ONE reader since PR 66 (seven before it, one an architecture).  ``rows`` are
ONE chip's: a group of several chips shares its rows out, and replica groups
that share a chip share its kernel time."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import flops
    from ftbench.layer_metrics import _ling as shared
    from ftbench.sources import arch_flops, chips_per_group

    flash_step = arch_flops(sources, "flash_step")
    if flash_step is None:
        return None
    seconds = shared.kernel_s_per_step(sources, shared.FLASH)
    if seconds is None:
        return None
    sharing = sources["replicas"] if sources["groups_share_chip"] else 1
    rows = sources["rows_per_replica"] / chips_per_group(sources)
    need = flash_step(sources["shapes"], rows, sources["seq"])
    return flops.roofline_pct(*need, seconds / sharing, sources["device_kind"])["pct"]
