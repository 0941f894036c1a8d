"""Compiled step: own device time a step of what XLA made of a mixer between its
projections and its kernel (the scope ``tpuft.mixer_glue``, ``obs/spans.py``:
rope, q/k norms, the short convolution, softplus and decays, gates, the gated
norm, splits, reshapes and their layout copies).  The Mosaic kernels traced
under the scope are NOT counted: they have their metrics.  None on a program
without scopes."""

META = dict(source="device_trace", layer="compiled step", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import device_scopes

    return device_scopes.part_ms(sources, "mixer_glue")
