"""Kernels: what a windowed layer's attention costs beside a full layer's:
100 x the device time a step and WINDOWED layer of ``flash_win_fwd``,
``flash_win_dq`` and ``flash_win_dkv`` over the device time a step and FULL
layer of ``flash_fwd``, ``flash_dq`` and ``flash_dkv``.  At 16,384 positions a
window of 2,048 leaves 23 % of the causal pairs alive and blocks of 512 walk
5 key blocks a row block against 16.5: 23-30 where the kernels skip the dead
blocks, 100 where they mask a full walk.  None without both kinds of layer."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _swa

    windowed = _swa.layer_ms(sources, _swa.FLASH_WIN, "n_windowed")
    full = _swa.layer_ms(sources, _swa.FLASH, "n_full")
    if windowed is None or full is None:
        return None
    return 100.0 * windowed / full
