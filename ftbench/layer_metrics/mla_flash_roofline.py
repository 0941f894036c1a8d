"""Kernels: the flash kernels' share of their roofline at latent
attention's heads (192 for q and k, 128 for v).  The least time for the
causal attention of one step (``ling_flops.mla_flash_step``: forward and
backward, the recomputed scores and the rematerialised forward not
credited) over the three kernels' device time.  At S = 8,192 the bound is
compute."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import flops
    from ftbench.layer_metrics import _ling as ling

    if not ling.is_ling(sources):
        return None
    seconds = ling.kernel_s_per_step(sources, ling.FLASH)
    if seconds is None:
        return None
    need = ling.flops().mla_flash_step(sources["shapes"], sources["rows_per_replica"], sources["seq"])
    return flops.roofline_pct(*need, seconds, sources["device_kind"])["pct"]
