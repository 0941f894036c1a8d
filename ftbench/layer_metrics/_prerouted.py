"""Shared by the readers of the ``prerouted_moe`` cells (no metric itself:
``BENCHMARK.json`` names no ``_prerouted``).  Every helper returns None on a
program without the kernels or the architecture, as the parent of the PR
that brought them is."""

# the kernels' names are the program's, not an architecture's: ``FLASH`` the
# GLOBAL layers' three (``flash_fwd`` does not match ``flash_win_fwd``),
# ``FLASH_WIN`` the windowed layers' three, as Trinity's readers know them
from ftbench.layer_metrics._swa import FLASH, FLASH_WIN, kernel_s_per_step  # noqa: F401


def flops(sources):
    """``prerouted_flops`` of ``architectures/prerouted_moe.py`` where the
    cell's shapes are this architecture's, else None."""
    from ftbench.architectures import prerouted_moe

    count = prerouted_moe.prerouted_flops
    return count if count.is_mine(sources.get("shapes")) else None


def layer_ms(sources, pattern, layers):
    """Device milliseconds a step AND LAYER of the kernels ``pattern`` names,
    ``layers`` being the shapes' count of the layers that run them."""
    if flops(sources) is None or not sources["shapes"][layers]:
        return None
    seconds = kernel_s_per_step(sources, pattern)
    return None if seconds is None else 1000.0 * seconds / sources["shapes"][layers]
