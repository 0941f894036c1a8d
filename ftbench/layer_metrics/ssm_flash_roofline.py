"""Kernels: the flash kernels' share of their roofline in an
``ssm_hybrid_moe`` cell, at 32 query heads of 128 over 2 key and value heads
(``flash_roofline`` counts with another architecture's shapes).  The least
time for the causal attention of one step (``ssm_flops.flash_step``: forward
and backward, the recomputed scores not credited) over the three kernels'
device time.  At S = 16,384 the bound is compute."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _ssm

    return _ssm.roofline(
        sources, _ssm.FLASH,
        lambda s: _ssm.flops().flash_step(s, sources["rows_per_replica"], sources["seq"]),
    )
