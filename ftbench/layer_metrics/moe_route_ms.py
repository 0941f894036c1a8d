"""Experts: own device time a step of the routing (the scope
``tpuft.experts_route``, ``obs/spans.py``: router product, scores, groups, top-k,
the load count, the balance loss).  None on a program without scopes."""

META = dict(source="device_trace", layer="experts", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import device_scopes

    return device_scopes.part_ms(sources, "experts_route")
