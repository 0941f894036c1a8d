"""Shared by the readers of the ``eva`` cells (no metric itself:
``BENCHMARK.json`` names no ``_eva``).  Every helper returns None on a program
without the kernels or the architecture, as the parent of the PR that brought
them is."""

# reading a trace by a kernel's name and the step's flight events out of the
# window are any architecture's: the helpers PR 29 brought
from ftbench.layer_metrics._ling import kernel_s_per_step, route_events  # noqa: F401

# the three kernels of ``ops/flash_attention.py`` ``eva_attention``
EVA = r"^%?eva_(fwd|dq|dkv)\b"


def flops():
    """``eva_flops`` of ``architectures/eva.py``."""
    from ftbench.architectures import eva

    return eva.eva_flops


def is_mine(sources):
    """Whether the cell's shapes are this architecture's."""
    return flops().is_mine(sources.get("shapes"))
