"""Kernels: the selective-scan kernels' share of their roofline.  The least
time the chip could take for the scans of one step (forward and backward of
the RECURRENCE, ``sambay_flops.selscan_step``: 22 operations a (token, channel,
state) and the bytes of u, dt, y and their cotangents; a chunk's states made
again in the backward pass are the kernel's choice and not credited) over the
device time of ``selscan_fwd`` and ``selscan_bwd`` in the trace.  The bound is
MEMORY by ``flops.roofline_pct``'s two peaks (2.25 ms a layer of bytes against
0.15 ms of operations at the matrix unit's 197 TFLOP/s), because the table of
peaks has no vector-unit peak and the recurrence runs on the vector unit: the
share can only read low, and says how far the kernels are from streaming their
operands, not how busy the vector unit is."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _sambay

    return _sambay.roofline(sources, _sambay.SELSCAN, _sambay.flops().selscan_step)
