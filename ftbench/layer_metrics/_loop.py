"""Shared by the readers of the ``looped`` cells (no metric itself:
``BENCHMARK.json`` names no ``_loop``)."""

# the step's flight events out of the window are any architecture's: the
# helper PR 29 brought
from ftbench.layer_metrics._ling import route_events


def event_mean(sources, field, pick=lambda value: value):
    """The mean over the window's step events of ``pick(event[field])``; None
    where no event has the field."""
    import statistics

    values = [pick(e[field]) for e in route_events(sources) if e.get(field) is not None]
    return statistics.fmean(values) if values else None
