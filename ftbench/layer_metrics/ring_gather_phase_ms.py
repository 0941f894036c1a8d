"""Host data plane, milliseconds a step: the op thread's wall time in the
rings' allgather phase (``ring_allgather_phase``: the reduced halves sent
round, received straight into the bucket), summed over a step's rings.
DDP_SYNC's ``ring_gather_s`` (``_ring.py`` says where it is counted and which
events are read); None on a program whose events carry no such field."""

from ftbench.layer_metrics._ring import META, field_ms


def read(sources):
    return field_ms(sources, "ring_gather_s")
