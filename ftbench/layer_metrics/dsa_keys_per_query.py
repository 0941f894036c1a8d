"""Index: the keys a query read, the mean over a step's rows and layers and
over the window's MOE_ROUTE flight events (``keys_per_query``, counted by
``dsa_select`` on the device from the bits it wrote).  ``sum_t min(t + 1,
2048) / S``: 1,920.0625 at 16,384 positions.  None on a program whose
events lack the field."""

META = dict(source="program_counter", layer="index", unit="keys", moves="tokens_per_s_per_chip")


def read(sources):
    import statistics

    from ftbench.layer_metrics import _dsa

    events = [e for e in _dsa.route_events(sources) if e.get("keys_per_query")]
    return statistics.fmean(statistics.fmean(e["keys_per_query"]) for e in events) if events else None
