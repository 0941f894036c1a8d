"""Kernels: what a windowed layer's attention costs beside the global
layer's: 100 x the device time a step and WINDOWED layer of ``flash_win_fwd``,
``flash_win_dq`` and ``flash_win_dkv`` over the device time a step and GLOBAL
layer of ``flash_fwd``, ``flash_dq`` and ``flash_dkv``.  At 16,384 positions a
window of 4,096 leaves 44 % of the causal pairs alive and blocks of 512 walk
up to 9 key blocks a row block against 16.5; a windowed layer also runs its
forward kernel twice (it keeps nothing through its rematerialisation) where
the global layer runs it once: 44-60 where the kernels skip the dead blocks,
100 and over where they mask a full walk.  None without both kinds of layer."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _prerouted

    windowed = _prerouted.layer_ms(sources, _prerouted.FLASH_WIN, "n_prerouted_windowed")
    full = _prerouted.layer_ms(sources, _prerouted.FLASH, "n_prerouted_global")
    if windowed is None or full is None:
        return None
    return 100.0 * windowed / full
