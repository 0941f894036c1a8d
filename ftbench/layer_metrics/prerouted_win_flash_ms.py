"""Kernels: device time a step AND WINDOWED LAYER of the three Mosaic kernels
of SmallThinker's windowed attention layers, ``flash_win_fwd``,
``flash_win_dq`` and ``flash_win_dkv`` (``ops/flash_attention.py`` with a
``window`` of 4,096: the grid holds only the key blocks a row block's window
touches, nine of 512 at the most; 28 query heads to 4 key heads, so ``dkv``
walks a key block's rows SEVEN times), by the names their ``pallas_call``s
carry in the trace.  The global layer's ``flash_fwd``, ``flash_dq`` and
``flash_dkv`` have their own three metrics.  None on a program without them,
and on another architecture's shapes."""

META = dict(source="device_trace", layer="kernels", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _prerouted

    return _prerouted.layer_ms(sources, _prerouted.FLASH_WIN, "n_prerouted_windowed")
