"""Kernels: the Gated DeltaNet kernels' share of their roofline.  The least
time the chip could take for the delta rule of one step (forward and backward
of the RECURRENCE, ``gdn_flops.gdn_step``: the chunked form's extra products
and the rematerialised forward are the program's choice and not credited)
over the device time of ``gdn_fwd`` and ``gdn_bwd`` in the trace.  At dk = dv
= 128 the bound is memory (at 16,384 positions 0.98 ms a layer of bytes, q and
k once for two value heads, against 0.79 ms of operations); ``kda_roofline``
is the same share of ``ops/kda.py``'s."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _gdn

    return _gdn.roofline(
        sources, _gdn.GDN, lambda s: _gdn.flops().gdn_step(s, sources["rows_per_replica"], sources["seq"])
    )
