"""Shared by the readers of the ``looped`` cells (no metric itself:
``BENCHMARK.json`` names no ``_loop``).  Every helper returns None on a program
without the architecture, as the parent of the PR that brought them is."""

# reading a trace by a kernel's name and the step's flight events out of the
# window are any architecture's: the helpers PR 29 brought.  ``FLASH`` is the
# three kernels of every layer of every pass
from ftbench.layer_metrics._ling import FLASH, kernel_s_per_step, route_events  # noqa: F401


def flops():
    """``looped_flops`` of ``architectures/looped.py``."""
    from ftbench.architectures import looped

    return looped.looped_flops


def is_mine(sources):
    """Whether the cell's shapes are this architecture's."""
    return flops().is_mine(sources.get("shapes"))


def event_mean(sources, field, pick=lambda value: value):
    """The mean over the window's step events of ``pick(event[field])``; None
    where no event has the field."""
    import statistics

    values = [pick(e[field]) for e in route_events(sources) if e.get(field) is not None]
    return statistics.fmean(values) if values else None
