"""Kernels: device time a step of the Mosaic kernel ``gdn_fwd``
(``ops/gdn.py``: the chunked gated delta rule with one decay a head, forward;
it runs twice a step and DeltaNet layer, the second time to rematerialise the
layer), by the name its ``pallas_call`` carries in the trace.  None on a
program without it."""

META = dict(source="device_trace", layer="kernels", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _gdn

    return _gdn.kernel_ms(sources, r"^%?gdn_fwd\b")
