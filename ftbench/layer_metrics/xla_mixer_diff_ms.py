"""Compiled step: own device time a step of what XLA made of differential
attention's combination of its two softmaxes (the scope ``tpuft.mixer_diff``,
``obs/spans.py``: ``lambda``, ``O1 - lambda O2``, the RMSNorm over a pair's
channels and ``1 - lambda_0``, forward, rematerialised and backward).  The scope
lies INSIDE ``tpuft.mixer_glue`` and the innermost scope is an operation's part,
so ``xla_mixer_glue_ms`` does not count it; with this one the part metrics,
``xla_unscoped_ms`` and the kernels add up to ``step_device_ms`` again.  None on
a program without scopes or with nothing under this one."""

META = dict(source="device_trace", layer="compiled step", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import device_scopes

    return device_scopes.part_ms(sources, "mixer_diff") or None
