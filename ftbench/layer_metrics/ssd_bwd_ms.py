"""Kernels: device time a step of the Mosaic kernel ``ssd_bwd``
(``ops/ssd.py``: the chunked state-space scan's hand-written backward, the
chunks in reverse from the kept states), by the name its ``pallas_call``
carries in the trace.  None on a program without it."""

META = dict(source="device_trace", layer="kernels", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import program_spans

    return program_spans.kernel_ms_per_step(sources, "ssd_bwd")
