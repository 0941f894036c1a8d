"""Compiled step: the mean entropy of a position's exit distribution over the
passes, the mean of ``exit_entropy`` over the window's step events
(``models/looped.py`` ``summary_stats``).  1.0 to 1.2 nats at the seeded start
of ``ln 4 = 1.386``; 0 says the gate is not in the step or has collapsed onto
one pass, over 1.386 is impossible.  None on a program whose events lack the
field."""

META = dict(source="program_counter", layer="compiled step", unit="nats", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _loop

    return _loop.event_mean(sources, "exit_entropy")
