"""Shared by the readers of the ``windowed_moe`` cells (no metric itself:
``BENCHMARK.json`` names no ``_swa``).  Every helper returns None on a
program without the kernels or the architecture, as the parent of the PR
that brought them is."""

# reading a trace by a kernel's name is any architecture's: the helper PR 29
# brought.  ``FLASH`` is the FULL layers' three kernels: ``flash_fwd`` does not
# match ``flash_win_fwd``
from ftbench.layer_metrics._ling import FLASH, kernel_s_per_step  # noqa: F401

# the windowed layers' three kernels (``ops/flash_attention.py`` with a window)
FLASH_WIN = r"^%?flash_win_(fwd|dq|dkv)\b"


def flops():
    """``swa_flops`` of ``architectures/windowed_moe.py``."""
    from ftbench.architectures import windowed_moe

    return windowed_moe.swa_flops


def is_mine(sources):
    """Whether the cell's shapes are this architecture's."""
    return flops().is_mine(sources.get("shapes"))


def layer_ms(sources, pattern, layers):
    """Device milliseconds a step AND LAYER of the kernels ``pattern`` names,
    ``layers`` being the shapes' count of the layers that run them."""
    if not is_mine(sources) or not sources["shapes"][layers]:
        return None
    seconds = kernel_s_per_step(sources, pattern)
    return None if seconds is None else 1000.0 * seconds / sources["shapes"][layers]


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
