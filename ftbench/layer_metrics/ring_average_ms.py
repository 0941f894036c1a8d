"""Host data plane, milliseconds a step: the op thread's time in the owner's
division between the phases (``average_buffer`` over the half it owns, PR 40),
summed over a step's rings: the ring's own word for what
``normalize_in_ring_pct`` asserts, above 0 where the ring divides and 0 where
it hands back sums.  DDP_SYNC's ``ring_average_s`` (``_ring.py`` says where it
is counted and which events are read); None on a program whose events carry no
such field."""

from ftbench.layer_metrics._ring import META, field_ms


def read(sources):
    return field_ms(sources, "ring_average_s")
