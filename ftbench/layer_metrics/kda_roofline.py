"""Kernels: the KDA kernels' share of their roofline.  The least time the
chip could take for the delta rule of one step (forward and backward of the
RECURRENCE, ``ling_flops.kda_step``: the chunked form's extra products and
the rematerialised forward are the program's choice and not credited) over
the device time of ``kda_fwd`` and ``kda_bwd`` in the trace.  At dk = dv =
128 the bound is memory (q, k, g, v, o once each way)."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import flops
    from ftbench.layer_metrics import _ling as ling

    if not ling.is_ling(sources):
        return None
    seconds = ling.kernel_s_per_step(sources, ling.KDA)
    if seconds is None:
        return None
    need = ling.flops().kda_step(sources["shapes"], sources["rows_per_replica"], sources["seq"])
    return flops.roofline_pct(*need, seconds, sources["device_kind"])["pct"]
