"""Shared by the readers of the ``gated_delta_moe`` cells (no metric itself:
``BENCHMARK.json`` names no ``_gdn``).  Every helper returns None on a program
without the kernels or the architecture, as the parent of the PR that brought
them is."""

# reading a trace by a kernel's name and MOE_ROUTE out of the window are any
# architecture's: the helpers PR 29 brought
from ftbench.layer_metrics._ling import kernel_s_per_step, route_events  # noqa: F401

# the two kernels of ``ops/gdn.py``
GDN = r"^%?gdn_(fwd|bwd)\b"


def flops():
    """``gdn_flops`` of ``architectures/gated_delta_moe.py``."""
    from ftbench.architectures import gated_delta_moe

    return gated_delta_moe.gdn_flops


def is_mine(sources):
    """Whether the cell's shapes are this architecture's."""
    return flops().is_mine(sources.get("shapes"))


def kernel_ms(sources, pattern):
    """Device milliseconds a step of the first chip's operations whose own
    name matches ``pattern``; None off this architecture, where there is no
    trace or no such operation."""
    seconds = kernel_s_per_step(sources, pattern) if is_mine(sources) else None
    return None if seconds is None else 1000.0 * seconds


def roofline(sources, pattern, need):
    """The share of its roofline of the kernels ``pattern`` names, ``need``
    being ``(operations, bytes)`` of a step from the cell's shapes."""
    from ftbench import flops as peaks

    seconds = kernel_s_per_step(sources, pattern) if is_mine(sources) else None
    if seconds is None:
        return None
    return peaks.roofline_pct(*need(sources["shapes"]), seconds, sources["device_kind"])["pct"]
