"""Kernels: device time a step of the Mosaic kernel ``selscan_bwd``
(``ops/selscan.py``: the chunks in reverse, a chunk's states made again in VMEM
from its kept starting state, then the hand-written transpose of the
recurrence), by the name its ``pallas_call`` carries in the trace.  None on a
program without it."""

META = dict(source="device_trace", layer="kernels", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _sambay

    return _sambay.kernel_ms(sources, r"^%?selscan_bwd\b")
