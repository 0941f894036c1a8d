"""Compiled step: own device time a step of the residual stream's own work (the
scope ``tpuft.stream``, ``obs/spans.py``: a layer's norm of the stream, the
residual add, the casts between float32 and bfloat16).  None on a program
without scopes."""

META = dict(source="device_trace", layer="compiled step", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import device_scopes

    return device_scopes.part_ms(sources, "stream")
