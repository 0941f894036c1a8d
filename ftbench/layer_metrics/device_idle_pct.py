"""Device: the share of the traced steps in which no operation ran on the
chip, averaged over the chips."""

META = dict(source="device_trace", layer="device", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.sources import device_busy_s

    busy = device_busy_s(sources)
    return None if busy is None else 100.0 * (1.0 - busy[0] / busy[1])
