"""Compiled step: own device time a step of what XLA made of a looped model's
exit gate and what it weighs (the scope ``tpuft.loop_gate``, ``obs/spans.py``;
``models/looped.py``: every pass's gate logit, ``softplus``, the exit
distribution, the expected loss under it, its entropy and the step's summary,
forward and backward).  The heads' logits and cross-entropies stay ``head``'s.
None on a program without the scope."""

META = dict(source="device_trace", layer="compiled step", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import device_scopes

    return device_scopes.part_ms(sources, "loop_gate") or None
