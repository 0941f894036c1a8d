"""Kernels: device time a step of the three flash kernels (``flash_fwd``,
``flash_dq``, ``flash_dkv``) in a ``ling_hybrid`` cell, where they are the
latent-attention layers' (q and k heads of 192, v heads of 128, 32 heads
each with its own keys)."""

META = dict(source="device_trace", layer="kernels", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _ling as ling

    if not ling.is_ling(sources):
        return None
    seconds = ling.kernel_s_per_step(sources, ling.FLASH)
    return None if seconds is None else 1000.0 * seconds
