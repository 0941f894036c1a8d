"""Compiled step: own device time a step of every XLA operation in the traced
stretch, whatever program it is of, that lies under no ``tpuft.`` scope
(``obs/spans.py``): the check that the parts tile the step.  The part metrics,
this one and the Mosaic kernels' own time add up to ``step_device_ms``.  None
on a program without scopes."""

META = dict(source="device_trace", layer="compiled step", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import device_scopes

    return device_scopes.own_ms_per_step(sources, lambda op: op["part"] is None and not op["kernel"])
