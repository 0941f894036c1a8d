"""Kernels: the attention over the picked keys as a share of its roofline.
The least time for one step's attention over the keys each query PICKED
(``dsa_flops.attn_step``: ``sum_t min(t + 1, 2048)`` pairs a sequence,
forward and backward; not the blocks a masked kernel walks, and the
rematerialised forward not credited) over the device time of
``dsa_attn_fwd``, ``dsa_attn_dq`` and ``dsa_attn_dkv``.  At 16,384 positions
the picked pairs are 23 % of the causal ones, so a kernel that walks every
causal block at a flash kernel's pace reads about a quarter of that
kernel's share."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _dsa

    return _dsa.roofline(
        sources, _dsa.ATTN,
        lambda s: _dsa.flops().attn_step(s, sources["rows_per_replica"], sources["seq"]),
    )
