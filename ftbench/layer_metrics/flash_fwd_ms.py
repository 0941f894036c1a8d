"""Kernels: device time a step of the Mosaic kernel ``flash_fwd``
(``ops/flash_attention.py``, the forward pass), by the name its
``pallas_call`` carries in the trace (the operation's OWN name, at the
start of its text: other operations mention it as their operand).  The
three kernels' sum is what ``flash_roofline`` divides by."""

META = dict(source="device_trace", layer="kernels", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import program_spans

    return program_spans.kernel_ms_per_step(sources, "flash_fwd")
