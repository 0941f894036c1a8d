"""Host data plane, milliseconds a step: the op thread's wall time in the
rings' reduce-scatter phase (``ring_reduce_phase``: a send on the tx workers
beside a receive that adds as it lands, and in its last step divides),
summed over a step's rings.  With ``ring_gather_phase_ms`` it is what the
rings took less the binding's own time (the span a collective that
``comm_op_ms`` read until PR 66 is in no trace of a session).  DDP_SYNC's ``ring_reduce_s`` (``_ring.py`` says
where it is counted and which events are read); None on a program whose events
carry no such field."""

from ftbench.layer_metrics._ring import META, field_ms


def read(sources):
    return field_ms(sources, "ring_reduce_s")
