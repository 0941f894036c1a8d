"""Experts: how uneven the load of the held experts is: the busiest held
expert's tokens over their mean, the worst expert layer of a step, the mean
over the window's MOE_ROUTE flight events.  1 is even; the selection bias
is there to bring it down."""

META = dict(source="program_counter", layer="experts", unit="x", moves="tokens_per_s_per_chip")


def read(sources):
    import statistics

    from ftbench.layer_metrics import _ling as ling

    events = ling.route_events(sources)
    if not events:
        return None
    return statistics.fmean(
        max(hi / mean for hi, mean in zip(e["load_max"], e["load_mean"]) if mean > 0) for e in events
    )
