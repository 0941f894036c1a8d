"""Shared by the readers of the ``ling_hybrid`` cells (no metric itself:
``BENCHMARK.json`` names no ``_ling``)."""


def flops():
    """``ling_flops`` of ``architectures/ling_hybrid.py``."""
    from ftbench.architectures import ling_hybrid

    return ling_hybrid.ling_flops


def is_ling(sources):
    """Whether the cell's shapes are this architecture's."""
    return "n_kda" in (sources.get("shapes") or {})


def kernel_s_per_step(sources, pattern):
    """Device seconds a step of the first chip's operations whose OWN name
    (the start of the operation's text) matches ``pattern``; None where
    there is no trace or no such operation."""
    from ftbench import trace_reduce
    from ftbench.sources import traced_stretch

    stretch = traced_stretch(sources)
    if stretch is None:
        return None
    a, b, steps = stretch
    device = sources["trace"]["per_device"]
    ops = trace_reduce.clip(device[min(device)]["ops"], a, b)
    seconds = trace_reduce.matching_seconds(ops, pattern)
    return seconds / steps if seconds > 0.0 else None


# megablox names its two kernels by the jitted functions around them: gmm
# (forward, and backward to the rows) and tgmm (backward to the weights)
GMM = r"^%?[\w.\-]*gmm[\w.\-]*"
FLASH = r"^%?flash_(fwd|dq|dkv)\b"
KDA = r"^%?kda_(fwd|bwd)\b"


def route_events(sources):
    """Replica 0's MOE_ROUTE flight events of the window's steps."""
    window = sources["window"][0]
    if not window or not sources.get("flight"):
        return []
    t0, t1 = window[0]["t_enter"], window[-1]["t_exit"]
    return [
        e for e in sources["flight"][0] or []
        if e.get("name") == "MOE_ROUTE" and t0 <= e.get("t", 0.0) <= t1 + 1.0
    ]
