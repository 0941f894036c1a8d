"""Kernels: device time a step of the Mosaic kernel ``dsa_select``
(``ops/indexed_attention.py``: the exact ``min(t + 1, 2048)`` best scores of
every row by bisection on their bit patterns, written as bits; once a step
and layer), by the name its ``pallas_call`` carries in the trace.  None on a
program without it."""

META = dict(source="device_trace", layer="kernels", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _dsa

    return _dsa.kernel_ms(sources, _dsa.SELECT)
