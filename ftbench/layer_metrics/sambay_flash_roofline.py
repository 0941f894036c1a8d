"""Kernels: the flash kernels' share of their roofline in a ``sambay`` cell:
the SIX programs of a step (``flash_win_fwd`` / ``_dq`` / ``_dkv`` of the layers
under the window, ``flash_fwd`` / ``_dq`` / ``_dkv`` of the whole layer and of
the cross-attention over its keys), each ONE launch over both softmaxes of
every pair at heads of 64 for q and k and 128 for v.  The least time for the
attention of one step (``sambay_flops.flash_step``: the live pairs of a window
of 512 alone, the causal half of a whole launch, heads as they are and never
padded to 128, nothing recomputed credited) over the device time of the six.
The bound is compute."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _sambay

    return _sambay.roofline(sources, _sambay.FLASH_ALL, _sambay.flops().flash_step)
