"""Kernels: the index's scoring as a share of its roofline.  The least time
for the scores of every causal pair of one step (``dsa_flops.index_step``:
``2 * 16 * 64`` operations a pair, once; the index's operands read and a bit
a pair written) over the device time of ``dsa_index``.  The kernel also
writes its float32 scores for the selection to read, which the mathematics
does not need and the share does not credit."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _dsa

    return _dsa.roofline(
        sources, _dsa.INDEX,
        lambda s: _dsa.flops().index_step(s, sources["rows_per_replica"], sources["seq"]),
    )
