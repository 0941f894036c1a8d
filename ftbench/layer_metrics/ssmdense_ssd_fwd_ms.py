"""Kernels: device time a step of the Mosaic kernel ``ssd_fwd`` (``ops/ssd.py``:
the chunked state-space scan, forward) in an ``ssm_hybrid_dense`` cell, by the
name its ``pallas_call`` carries in the trace: ONE group of 64 heads, walked in
head blocks under one chunk of ``B`` and ``C``.  A layer runs it ONCE a step:
``models/ssm_hybrid_dense.py`` keeps the scan's output and chunk-start states
through a layer's rematerialisation (``ssd.KEPT_NAMES``), so nine launches a
step at nine Mamba-2 layers.  None on a program without it or on another
architecture's cell."""

META = dict(source="device_trace", layer="kernels", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _ssmdense

    return _ssmdense.kernel_ms(sources, r"^%?ssd_fwd\b")
