"""Compiled step: device time of one replica group's step on one of its
chips (union of the device's operations over the traced steps)."""

META = dict(source="device_trace", layer="compiled step", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.sources import step_device_s

    s = step_device_s(sources)
    return None if s is None else 1000.0 * s
