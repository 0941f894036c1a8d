"""Kernels: device time a step of the Mosaic kernel ``selscan_fwd``
(``ops/selscan.py``: the selective scan with a decay for every (channel, state)
pair, forward, the recurrence itself on the vector unit with the state in
VMEM; once a step and scan layer, its output and chunk-start states kept
through the layer's rematerialisation), by the name its ``pallas_call`` carries
in the trace.  None on a program without it."""

META = dict(source="device_trace", layer="kernels", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _sambay

    return _sambay.kernel_ms(sources, r"^%?selscan_fwd\b")
