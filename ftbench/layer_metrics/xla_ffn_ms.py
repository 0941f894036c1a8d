"""Compiled step: own device time a step of the dense MLPs (the scope
``tpuft.ffn``, ``obs/spans.py``: Mistral's SwiGLU, Ling's dense layer, the
shared expert).  None on a program without scopes."""

META = dict(source="device_trace", layer="compiled step", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import device_scopes

    return device_scopes.part_ms(sources, "ffn")
