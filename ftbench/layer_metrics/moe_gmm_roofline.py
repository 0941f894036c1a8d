"""Kernels: the grouped products' share of their roofline, in every cell with
routed experts.  The least time for the held experts' products of one step,
forward and backward, at the rows the routers REALLY sent here (the mean of
``rows_here`` over the window's MOE_ROUTE flight events) over the
``gmm``/``tgmm`` kernels' device time.  What the products need is the
architecture's count (``sources["architecture"].flops.gmm_step(shapes,
rows_here)``: three matrices an expert or two, the layers that hold experts, a
prediction module's among them; the rematerialised forward not credited).  At
a hundred rows an expert the bound is memory: the experts' weights.

ONE reader since PR 66 (five before it, one an architecture); an architecture
without experts has no ``gmm_step`` and reads nothing."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    import statistics

    from ftbench import flops
    from ftbench.layer_metrics import _ling as shared
    from ftbench.sources import arch_flops

    gmm_step = arch_flops(sources, "gmm_step")
    if gmm_step is None:
        return None
    events = shared.route_events(sources)
    seconds = shared.kernel_s_per_step(sources, shared.GMM)
    if not events or seconds is None:
        return None
    rows_here = statistics.fmean(statistics.fmean(e["rows_here"]) for e in events)
    return flops.roofline_pct(*gmm_step(sources["shapes"], rows_here), seconds, sources["device_kind"])["pct"]
