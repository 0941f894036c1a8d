"""Compiled step: own device time a step of what XLA made of the products into
and out of the mixers (the scope ``tpuft.mixer_proj``, ``obs/spans.py``: q/k/v/o,
MLA's low-rank pairs, KDA's and Mamba-2's ``w_in`` / ``w_out``, the index's
projections), forward, backward and rematerialised together.  None on a
program without scopes."""

META = dict(source="device_trace", layer="compiled step", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import device_scopes

    return device_scopes.part_ms(sources, "mixer_proj")
