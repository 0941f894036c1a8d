"""Kernels: the windowed flash kernels' share of their roofline.  The least
time for the windowed layers' attention of one step (``swa_flops.
win_flash_step``: forward and backward over the LIVE pairs alone, ``S W - W (W
- 1) / 2`` a head, never the blocks walked: the dead part of the two edge
blocks and the recomputed scores are the kernels' choice and not credited)
over the device time of ``flash_win_fwd``, ``flash_win_dq`` and
``flash_win_dkv``.  A kernel that masked a full walk would read a quarter of
what one that skips reads."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _swa

    return _swa.roofline(
        sources, _swa.FLASH_WIN,
        lambda s: _swa.flops().win_flash_step(s, sources["rows_per_replica"], sources["seq"]),
    )
