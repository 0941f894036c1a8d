"""Kernels: the scan kernels' share of their roofline.  The least time the
chip could take for the state-space scan of one step, forward and backward
(``ssm_flops.ssd_step``: the chunked form's products over the causal half of
a chunk, every operand and cotangent moved once, the chunk-start states
written and read as float32; a second forward, should a layer rematerialise
one, is not credited) over the device time of ``ssd_fwd`` and ``ssd_bwd`` in
the trace.  At heads of 64 by a state of 128 the bound is memory."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _ssm

    return _ssm.roofline(
        sources, _ssm.SSD,
        lambda s: _ssm.flops().ssd_step(s, sources["rows_per_replica"], sources["seq"]),
    )
