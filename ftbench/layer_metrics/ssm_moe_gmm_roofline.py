"""Kernels: the grouped products' share of their roofline in an
``ssm_hybrid_moe`` cell, whose experts are TWO matrices (``moe_gmm_roofline``
counts three, with another architecture's shapes).  The least time for the
held experts' squared-ReLU of one step, forward and backward, at the rows
the router REALLY sent here (the mean of ``rows_here`` over the window's
MOE_ROUTE flight events; ``ssm_flops.gmm_step``; the rematerialised forward
not credited) over the ``gmm``/``tgmm`` kernels' device time."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    import statistics

    from ftbench.layer_metrics import _ssm

    events = _ssm.route_events(sources)
    if not events:
        return None
    rows_here = statistics.fmean(statistics.fmean(e["rows_here"]) for e in events)
    return _ssm.roofline(sources, _ssm.GMM, lambda s: _ssm.flops().gmm_step(s, rows_here))
