"""Shared by the readers of the ``indexed_sparse_moe`` cells (no metric
itself: ``BENCHMARK.json`` names no ``_dsa``).  Every helper returns None
on a program without the kernels or the flight fields, as the parent of the
PR that brought them is."""

# reading a trace by a kernel's name and MOE_ROUTE out of the window are any
# architecture's: the helpers PR 29 brought
from ftbench.layer_metrics._ling import kernel_s_per_step, route_events  # noqa: F401


def flops():
    """``dsa_flops`` of ``architectures/indexed_sparse_moe.py``."""
    from ftbench.architectures import indexed_sparse_moe

    return indexed_sparse_moe.dsa_flops


def is_mine(sources):
    """Whether the cell's shapes are this architecture's."""
    return flops().is_mine(sources.get("shapes"))


def kernel_ms(sources, pattern):
    """Device milliseconds a step of the first chip's operations whose own
    name matches ``pattern``; None where there is no trace or no such
    operation."""
    seconds = kernel_s_per_step(sources, pattern)
    return None if seconds is None else 1000.0 * seconds


INDEX = r"^%?dsa_index\b"
SELECT = r"^%?dsa_select\b"
ATTN = r"^%?dsa_attn_(fwd|dq|dkv)\b"
PROBS = r"^%?dsa_probs\b"


def roofline(sources, pattern, need):
    """The share of its roofline of the kernels ``pattern`` names, ``need``
    being ``(operations, bytes)`` of a step from the cell's shapes."""
    from ftbench import flops as peaks

    seconds = kernel_s_per_step(sources, pattern)
    if not is_mine(sources) or seconds is None:
        return None
    return peaks.roofline_pct(*need(sources["shapes"]), seconds, sources["device_kind"])["pct"]
