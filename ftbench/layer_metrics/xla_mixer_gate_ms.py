"""Compiled step: own device time a step of what XLA made of a Mamba-2 mixer's
gated norm (the scope ``tpuft.mixer_gate``, ``obs/spans.py``: ``y * silu(z)``,
the RMSNorm over the group's channels and its weight, forward, rematerialised
and backward).  The scope lies INSIDE ``tpuft.mixer_glue`` and the innermost
scope is an operation's part, so ``xla_mixer_glue_ms`` does not count it.  None
on a program without scopes, with nothing under this one, or on another
architecture's cell."""

META = dict(source="device_trace", layer="compiled step", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _ssmdense

    return _ssmdense.part_ms(sources, "mixer_gate")
