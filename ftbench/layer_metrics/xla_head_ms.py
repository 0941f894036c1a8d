"""Compiled step: own device time a step of the two ends of the model (the
scopes ``tpuft.head`` and ``tpuft.embed``, ``obs/spans.py``: final norm, logits,
the losses, the step summary; the embedding lookup and its scatter-add
gradient).  None on a program without scopes."""

META = dict(source="device_trace", layer="compiled step", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import device_scopes

    return device_scopes.part_ms(sources, "head", "embed")
