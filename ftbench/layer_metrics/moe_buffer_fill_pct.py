"""Experts: how full the experts' buffer is: 100 x the (token, choice) pairs
the routers sent to the experts this chip holds over the rows of the buffer
they went through, both summed over the expert layers and the window's
MOE_ROUTE flight events (``buffer_rows``: ``parallel/moe.py``'s
``buffer_size`` times the passes a layer's rows needed that step).  The
gather, the masks and the scatter-add run over the buffer's rows, so 100
less this is their share that moves zeros.  A program that records no
``buffer_rows`` (one buffer of four times the uniform load: 21-25) reads
nothing."""

META = dict(source="program_counter", layer="experts", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _ling as ling

    events = ling.route_events(sources)
    if not events or any("buffer_rows" not in e for e in events):
        return None
    buffer = sum(sum(e["buffer_rows"]) for e in events)
    return 100.0 * sum(sum(e["rows_here"]) for e in events) / buffer if buffer else None
