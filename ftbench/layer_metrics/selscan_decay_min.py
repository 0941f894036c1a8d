"""Kernels: the most negative exponent ``dt_t[c] A[c, n]`` a step's selective
scans were fed, the minimum over the window's step events (``decay_min``, taken
on the device inside the compiled step; ``models/sambay.py`` ``summary_stats``).
-10 to -25 over a window of the cell on the chip (PERF.md section 6, PR 63: what
``W_dt`` adds under the softplus carries a step of at most 0.1 to 0.4-0.6
against ``A`` down to -16, and the first AdamW steps carry it on), moving with
training; 0 says no scan is in the step.  None on a
program whose events lack the field."""

META = dict(source="program_counter", layer="kernels", unit="nats", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _sambay

    values = [e["decay_min"] for e in _sambay.route_events(sources) if isinstance(e.get("decay_min"), float)]
    return min(values) if values else None
