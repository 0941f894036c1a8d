"""Kernels: the grouped products' share of their roofline.  The least time
for the held experts' SwiGLU of one step, forward and backward, at the rows
the router REALLY sent here (the mean of ``rows_here`` over the window's
MOE_ROUTE flight events; ``ling_flops.gmm_step``; the rematerialised
forward not credited) over the ``gmm``/``tgmm`` kernels' device time.  At
128 rows an expert the bound is memory: the experts' weights."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    import statistics

    from ftbench import flops
    from ftbench.layer_metrics import _ling as ling

    events = ling.route_events(sources)
    seconds = ling.kernel_s_per_step(sources, ling.GMM)
    if not ling.is_ling(sources) or not events or seconds is None:
        return None
    rows_here = statistics.fmean(statistics.fmean(e["rows_here"]) for e in events)
    need = ling.flops().gmm_step(sources["shapes"], rows_here)
    return flops.roofline_pct(*need, seconds, sources["device_kind"])["pct"]
