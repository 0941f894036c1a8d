"""Shared by the readers of the ``sambay`` cells (no metric itself:
``BENCHMARK.json`` names no ``_sambay``).  Every helper returns None on a
program without the kernels or the architecture, as the parent of the PR that
brought them is."""

# reading a trace by a kernel's name and the step's flight events out of the
# window are any architecture's: the helpers PR 29 brought
from ftbench.layer_metrics._ling import kernel_s_per_step, route_events  # noqa: F401

# the two kernels of ``ops/selscan.py``
SELSCAN = r"^%?selscan_(fwd|bwd)\b"
# the SIX flash programs of a step: the windowed layers' and the whole and cross layers'
FLASH_ALL = r"^%?flash_(win_)?(fwd|dq|dkv)\b"


def flops():
    """``sambay_flops`` of ``architectures/sambay.py``."""
    from ftbench.architectures import sambay

    return sambay.sambay_flops


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
    """The share of its roofline of the kernels ``pattern`` names, ``need(shapes,
    rows, seq)`` being ``(operations, bytes)`` of a step."""
    from ftbench import flops as peaks

    seconds = kernel_s_per_step(sources, pattern) if is_mine(sources) else None
    if seconds is None:
        return None
    return peaks.roofline_pct(
        *need(sources["shapes"], sources["rows_per_replica"], sources["seq"]), seconds, sources["device_kind"]
    )["pct"]
