"""Kernels: device time a step of the three Mosaic kernels of the attention
over the picked keys, ``dsa_attn_fwd`` (once a step and layer since PR 34,
which keeps its output for the backward pass; twice before, the second time to
rematerialise the layer), ``dsa_attn_dq`` and ``dsa_attn_dkv``
(``ops/indexed_attention.py``), by the names their ``pallas_call`` carry in
the trace: every such event there is.  None on a program without them."""

META = dict(source="device_trace", layer="kernels", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _dsa

    return _dsa.kernel_ms(sources, _dsa.ATTN)
