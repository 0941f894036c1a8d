"""Compiled step: the mean cross-entropy of the FIRST pass's head, the mean of
``pass_nll[0]`` over the window's step events (one a committed step,
``HSDPTrainer``; ``models/looped.py`` ``summary_stats``).  ``ln 49,152 = 10.80``
at the seeded start and falling with the steps on one fixed batch, as the last
pass's does; absent, or still at 10.80 while the last pass's falls, pass 1's
head is not in what the step differentiates.  None on a program whose events
lack the field."""

META = dict(source="program_counter", layer="compiled step", unit="nats", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _loop

    return _loop.event_mean(sources, "pass_nll", lambda per_pass: per_pass[0])
