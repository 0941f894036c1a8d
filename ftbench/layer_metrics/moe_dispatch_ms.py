"""Experts: own device time a step of the dispatch around the grouped products
(the scope ``tpuft.experts_dispatch``, ``obs/spans.py``: argsort, gather, masks,
activation, weights, scatter-add, their transposes, and the ``conditional``'s own
time).  The ``gmm`` / ``tgmm`` kernels inside it are NOT counted: ``moe_gmm_ms``
reads them.  None on a program without scopes."""

META = dict(source="device_trace", layer="experts", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import device_scopes

    return device_scopes.part_ms(sources, "experts_dispatch")
