"""Kernels: the flash kernels' share of their roofline in a ``looped`` cell,
``n_layers x loop_passes`` calls of each a step at 16 heads of 128 with a key
and value head a query head.  The least time for the causal attention of one
step (``looped_flops.flash_step``: the live causal pairs, forward and
backward, the recomputed scores not credited and a ``flash_fwd`` run again to
rematerialise a layer not either) over the device time of ``flash_fwd``,
``flash_dq`` and ``flash_dkv``.  At 16,384 positions the bound is compute."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import flops as peaks
    from ftbench.layer_metrics import _loop

    seconds = _loop.kernel_s_per_step(sources, _loop.FLASH) if _loop.is_mine(sources) else None
    if seconds is None:
        return None
    need = _loop.flops().flash_step(sources["shapes"], sources["rows_per_replica"], sources["seq"])
    return peaks.roofline_pct(*need, seconds, sources["device_kind"])["pct"]
