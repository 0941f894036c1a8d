"""Kernels: the flash kernels' share of their roofline at heads of 256 (16
query heads over 2 key and value heads), on the full-attention layers of a
``gated_delta_moe`` cell.  The least time for their causal attention of one
step (``gdn_flops.flash_step``: the live causal pairs, forward and backward,
the recomputed scores not credited) over the device time of ``flash_fwd``,
``flash_dq`` and ``flash_dkv``.  At 16,384 positions the bound is compute."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _gdn

    return _gdn.roofline(
        sources, _gdn.FLASH, lambda s: _gdn.flops().flash_step(s, sources["rows_per_replica"], sources["seq"])
    )
