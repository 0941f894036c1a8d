"""Kernels: the windowed flash kernels' share of their roofline at a window
of 4,096 and a GQA group of seven.  The least time for the windowed layers'
attention of one step (``prerouted_flops.win_flash_step``: forward and
backward over the LIVE pairs alone, ``S W - W (W - 1) / 2`` a head, never the
blocks walked: the dead part of the two edge blocks and the recomputed scores
are the kernels' choice and not credited) over the device time of
``flash_win_fwd``, ``flash_win_dq`` and ``flash_win_dkv``.  A kernel that
masked a full walk would read 44 % of what one that skips reads."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import flops as peaks
    from ftbench.layer_metrics import _prerouted

    count = _prerouted.flops(sources)
    if count is None:
        return None
    seconds = _prerouted.kernel_s_per_step(sources, _prerouted.FLASH_WIN)
    if seconds is None:
        return None
    need = count.win_flash_step(sources["shapes"], sources["rows_per_replica"], sources["seq"])
    return peaks.roofline_pct(*need, seconds, sources["device_kind"])["pct"]
