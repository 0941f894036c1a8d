"""Compiled step: own device time a step of the update program (the scope
``tpuft.optimizer``, ``obs/spans.py``: optax, ``apply_updates``,
``advance_state``).  None on a program without scopes."""

META = dict(source="device_trace", layer="compiled step", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import device_scopes

    return device_scopes.part_ms(sources, "optimizer")
