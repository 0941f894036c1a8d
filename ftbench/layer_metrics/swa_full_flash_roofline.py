"""Kernels: the flash kernels' share of their roofline on the FULL attention
layers of a ``windowed_moe`` cell, at 32 query heads of 128 over 4 key and
value heads (``flash_roofline`` and ``ssm_flash_roofline`` count with other
architectures' shapes).  The least time for their causal attention of one
step (``swa_flops.full_flash_step``: the causal half, forward and backward,
the recomputed scores not credited) over the device time of ``flash_fwd``,
``flash_dq`` and ``flash_dkv``, which the windowed layers' kernels, named
``flash_win_*``, do not enter."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _swa

    return _swa.roofline(
        sources, _swa.FLASH,
        lambda s: _swa.flops().full_flash_step(s, sources["rows_per_replica"], sources["seq"]),
    )
