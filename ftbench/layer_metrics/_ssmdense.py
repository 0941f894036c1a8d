"""Shared by the readers of the ``ssm_hybrid_dense`` cells (no metric itself:
``BENCHMARK.json`` names no ``_ssmdense``).  Every helper returns None on a
program without the kernels, the parts or the architecture, as the parent of
the PR that brought them is."""

# reading a trace by a kernel's name and the step's flight events out of the
# window are any architecture's: the helpers PR 29 brought
from ftbench.layer_metrics._ling import kernel_s_per_step, route_events  # noqa: F401

# the two kernels of ``ops/ssd.py``
SSD = r"^%?ssd_(fwd|bwd)\b"


def flops():
    """``ssmdense_flops`` of ``architectures/ssm_hybrid_dense.py``."""
    from ftbench.architectures import ssm_hybrid_dense

    return ssm_hybrid_dense.ssmdense_flops


def is_mine(sources):
    """Whether the cell's shapes are this architecture's."""
    return flops().is_mine(sources.get("shapes"))


def kernel_ms(sources, pattern):
    """Device milliseconds a step of the first chip's operations whose own
    name matches ``pattern``; None off this architecture, where there is no
    trace or no such operation."""
    seconds = kernel_s_per_step(sources, pattern) if is_mine(sources) else None
    return None if seconds is None else 1000.0 * seconds


def part_ms(sources, name):
    """Own device milliseconds a step of what XLA made of the part ``name``;
    None off this architecture, on a program without scopes or with nothing
    under this one."""
    from ftbench import device_scopes

    return (device_scopes.part_ms(sources, name) or None) if is_mine(sources) else None


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
