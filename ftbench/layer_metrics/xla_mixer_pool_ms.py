"""Compiled step: own device time a step of what XLA made of a mixer's pooling
of keys and values into chunk summaries (the scope ``tpuft.mixer_pool``,
``obs/spans.py``; ``models/eva.py`` ``_pool``: the scores against the learned
vector, the softmax over a chunk, the two weighted sums, the learned offset,
forward, rematerialised and backward).  Rope, reshapes and casts stay
``mixer_glue``'s.  None on a program without the scope."""

META = dict(source="device_trace", layer="compiled step", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import device_scopes

    return device_scopes.part_ms(sources, "mixer_pool") or None
