"""Kernels: device time a step of the Mosaic kernel ``dsa_index``
(``ops/indexed_attention.py``: the index's float32 scores of every causal
pair, a chunk of 512 query rows at a time; once a step and layer, the
selection is kept through the backward pass), by the name its
``pallas_call`` carries in the trace.  None on a program without it."""

META = dict(source="device_trace", layer="kernels", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _dsa

    return _dsa.kernel_ms(sources, _dsa.INDEX)
