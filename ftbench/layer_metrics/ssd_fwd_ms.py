"""Kernels: device time a step of the Mosaic kernel ``ssd_fwd``
(``ops/ssd.py``: the chunked state-space scan, forward; TWICE a state-space
layer and step where the rematerialised layer does not keep its output and
chunk-start states, as ``models/ssm_hybrid_moe.py`` does not at the published
widths), by the name its ``pallas_call`` carries in the trace.  None on a
program without it."""

META = dict(source="device_trace", layer="kernels", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import program_spans

    return program_spans.kernel_ms_per_step(sources, "ssd_fwd")
