"""Kernels: device time a step of the three Mosaic kernels of the chunk-summary
attention, ``eva_fwd``, ``eva_dq`` and ``eva_dkv`` (``ops/flash_attention.py``
``eva_attention``: two key sources under one softmax, the grid holding a row
block's live summary blocks and then its live token blocks), by the names
their ``pallas_call``s carry in the trace; each stands once a layer in a
step.  None on a program without them."""

META = dict(source="device_trace", layer="kernels", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _eva

    seconds = _eva.kernel_s_per_step(sources, _eva.EVA)
    return None if seconds is None else 1000.0 * seconds
