"""Compiled step: the mean cross-entropy of the head's slices AFTER the first
(the bytes at ``t + 2`` to ``t + 8``), the mean of ``multibyte_nll`` over the
window's step events (one a committed step, ``HSDPTrainer``; ``models/eva.py``
``summary_stats``).  Near ``ln 320 = 5.77`` at seeded weights; far from it, or
absent, the seven further slices are not in the step.  None on a program whose
events lack the field."""

META = dict(source="program_counter", layer="compiled step", unit="nats", moves="tokens_per_s_per_chip")


def read(sources):
    import statistics

    from ftbench.layer_metrics._eva import route_events

    values = [e["multibyte_nll"] for e in route_events(sources) if "multibyte_nll" in e]
    return statistics.fmean(values) if values else None
