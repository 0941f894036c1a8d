"""Experts: the (token, choice) pairs a step's routers send to the experts
this chip holds, summed over the expert layers: the mean over the window's
MOE_ROUTE flight events (one a committed step, ``HSDPTrainer``).  A uniform
router sends ``tokens * top_k * held / experts`` a layer."""

META = dict(source="program_counter", layer="experts", unit="rows", moves="tokens_per_s_per_chip")


def read(sources):
    import statistics

    from ftbench.layer_metrics import _ling as ling

    events = ling.route_events(sources)
    return statistics.fmean(sum(e["rows_here"]) for e in events) if events else None
