"""Kernels: device time a step of the three Mosaic kernels of the WINDOWED
attention layers, ``flash_win_fwd``, ``flash_win_dq`` and ``flash_win_dkv``
(``ops/flash_attention.py`` with a ``window``: the grid holds only the key
blocks a row block's window touches), by the names their ``pallas_call``s
carry in the trace.  The full layers' ``flash_fwd``, ``flash_dq`` and
``flash_dkv`` have their own three metrics.  None on a program without them."""

META = dict(source="device_trace", layer="kernels", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _swa

    seconds = _swa.kernel_s_per_step(sources, _swa.FLASH_WIN)
    return None if seconds is None else 1000.0 * seconds
