"""Kernels: device time a step of the experts' grouped matrix products
(``megablox``'s ``gmm`` and ``tgmm`` kernels, ``parallel/moe.py``
``RoutedExperts``), by the names they carry in the trace."""

META = dict(source="device_trace", layer="kernels", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _ling as ling

    seconds = ling.kernel_s_per_step(sources, ling.GMM)
    return None if seconds is None else 1000.0 * seconds
