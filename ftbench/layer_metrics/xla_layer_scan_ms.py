"""Compiled step: own device time a step of the operations whose innermost scope
is ``tpuft.layers`` (``obs/spans.py``): the ``lax.scan`` over a run of layers
with no part of the body inside it, which is the loop's machinery, the slices
of the stacked weights, the plain ``dynamic-update-slice`` writes of stacked
values and what the compiler makes for the ``while`` itself.  NOT here: a
weight-gradient product that XLA fuses with the write of its stacked gradient
carries the product's path on the v5e and is its part's (PERF.md section 6,
PR 37).  None on a program without scopes."""

META = dict(source="device_trace", layer="compiled step", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import device_scopes

    return device_scopes.part_ms(sources, "layers")
