"""Compiled step: own device time a step of what XLA made of a Mamba-2 mixer's
short convolution (the scope ``tpuft.mixer_conv``, ``obs/spans.py``: the causal
depthwise convolution of four taps over the ``X | B | C`` channels, its bias and
the SiLU, forward, rematerialised and backward).  The scope lies INSIDE
``tpuft.mixer_glue`` and the innermost scope is an operation's part, so
``xla_mixer_glue_ms`` does not count it; with this one and ``xla_mixer_gate_ms``
the part metrics, ``xla_unscoped_ms`` and the kernels add up to
``step_device_ms``.  None on a program without scopes, with nothing under this
one, or on another architecture's cell."""

META = dict(source="device_trace", layer="compiled step", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _ssmdense

    return _ssmdense.part_ms(sources, "mixer_conv")
