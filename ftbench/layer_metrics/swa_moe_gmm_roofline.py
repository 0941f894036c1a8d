"""Kernels: the grouped products' share of their roofline in a
``windowed_moe`` cell (``moe_gmm_roofline`` counts with another
architecture's shapes).  The least time for the held experts' SwiGLU of one
step, three products an expert forward and six backward, at the rows the
router REALLY sent here (the mean of ``rows_here`` over the window's
MOE_ROUTE flight events; ``swa_flops.gmm_step``; the rematerialised forward
not credited) over the ``gmm``/``tgmm`` kernels' device time."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    import statistics

    from ftbench.layer_metrics import _swa

    events = _swa.route_events(sources)
    if not events:
        return None
    rows_here = statistics.fmean(statistics.fmean(e["rows_here"]) for e in events)
    return _swa.roofline(sources, _swa.GMM, lambda s: _swa.flops().gmm_step(s, rows_here))
