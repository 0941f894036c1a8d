"""Kernels: the most negative log decay ``-dt_t exp(A_log)`` a token that a
step's state-space scans were fed in an ``ssm_hybrid_dense`` cell, the minimum
over the window's step events (``decay_min``, taken on the device inside the
compiled step; ``models/ssm_hybrid_dense.py`` ``summary_stats``).  What
``ops/ssd.py`` turns into ``exp`` of a running sum inside a chunk: the further
below 0, the more of a chunk's square rounds to nothing; it moves with
training; 0 says no scan is in the step.  None on a program whose events lack
the field or on another architecture's cell."""

META = dict(source="program_counter", layer="kernels", unit="nats", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _ssmdense

    if not _ssmdense.is_mine(sources):
        return None
    values = [e["decay_min"] for e in _ssmdense.route_events(sources) if isinstance(e.get("decay_min"), float)]
    return min(values) if values else None
