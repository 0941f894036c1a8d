"""Host data plane, milliseconds a step: in a phase's step, from the op
thread's OWN part of the receive returning to the other lanes' parts and its
own send having landed (``run_lane_parts``' latch and the send latch), summed
over a step's rings: it lies inside the two phases, and is the most that a ring
which starts its next frame before the last one's tail has landed can win.
DDP_SYNC's ``ring_tail_s`` (``_ring.py`` says where it is counted and which
events are read); None on a program whose events carry no such field."""

from ftbench.layer_metrics._ring import META, field_ms


def read(sources):
    return field_ms(sources, "ring_tail_s")
