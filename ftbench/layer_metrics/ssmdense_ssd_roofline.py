"""Kernels: the scan kernels' share of their roofline in an ``ssm_hybrid_dense``
cell.  The least time the chip could take for the state-space scans of one
step, forward and backward (``ssmdense_flops.ssd_step``: ``ssm_flops.ssd_step``'s
arithmetic at ONE group: the chunked form's products over the causal half of a
chunk, ``C B^T`` once a group, ``B``, ``C`` and their cotangents moved once,
every other operand and cotangent once, the chunk-start states written and read
as float32; nothing recomputed credited) over the device time of ``ssd_fwd``
and ``ssd_bwd`` in the trace.  At heads of 64 by a state of 128 the bound is
memory."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _ssmdense

    return _ssmdense.roofline(sources, _ssmdense.SSD, _ssmdense.flops().ssd_step)
