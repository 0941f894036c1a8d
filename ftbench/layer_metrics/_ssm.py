"""Shared by the readers of the ``ssm_hybrid_moe`` cells (no metric itself:
``BENCHMARK.json`` names no ``_ssm``).  Every helper returns None on a
program without the kernels or the architecture, as the parent of the PR
that brought them is."""

# reading a trace by a kernel's name is any architecture's: the helper PR 29 brought
from ftbench.layer_metrics._ling import kernel_s_per_step

SSD = r"^%?ssd_(fwd|bwd)\b"


def flops():
    """``ssm_flops`` of ``architectures/ssm_hybrid_moe.py``."""
    from ftbench.architectures import ssm_hybrid_moe

    return ssm_hybrid_moe.ssm_flops


def is_mine(sources):
    """Whether the cell's shapes are this architecture's."""
    return flops().is_mine(sources.get("shapes"))


def roofline(sources, pattern, need):
    """The share of its roofline of the kernels ``pattern`` names, ``need``
    being ``(operations, bytes)`` of a step from the cell's shapes."""
    from ftbench import flops as peaks

    if not is_mine(sources):
        return None
    seconds = kernel_s_per_step(sources, pattern)
    if seconds is None:
        return None
    return peaks.roofline_pct(*need(sources["shapes"]), seconds, sources["device_kind"])["pct"]
