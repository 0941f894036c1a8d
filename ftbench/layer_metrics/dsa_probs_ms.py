"""Kernels: device time a step of the index's loss, the Mosaic kernel
``dsa_probs`` of ``ops/indexed_attention.py`` (the head-mean of the
attention's softmax over the picked keys, from its ``lse``, against the
index's softmax, with the gradient to the index's three operands in the same
pass; once a step and layer since PR 34, twice before, the second time to
rematerialise the layer), by the name its ``pallas_call`` carries in the
trace: every such event there is.  None on a program without it."""

META = dict(source="device_trace", layer="kernels", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _dsa

    return _dsa.kernel_ms(sources, _dsa.PROBS)
