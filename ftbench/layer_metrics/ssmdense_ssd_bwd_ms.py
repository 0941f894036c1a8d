"""Kernels: device time a step of the Mosaic kernel ``ssd_bwd`` (``ops/ssd.py``:
the chunked state-space scan's hand-written transpose, the chunks in reverse)
in an ``ssm_hybrid_dense`` cell, by the name its ``pallas_call`` carries in the
trace: once a Mamba-2 layer and step; what a group's 64 heads share of the
cotangents (``dB``, ``dC``, ``d(C B^T)``) is summed over the head blocks in
VMEM.  None on a program without it or on another architecture's cell."""

META = dict(source="device_trace", layer="kernels", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _ssmdense

    return _ssmdense.kernel_ms(sources, r"^%?ssd_bwd\b")
