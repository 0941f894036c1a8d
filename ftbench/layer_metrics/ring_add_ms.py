"""Host data plane, milliseconds a step: a lane's thread inside the reduce's
add (``view.reduce_in`` of a 4 MiB quantum the lane has just received,
``recv_framed_reduce``), the MEAN over the lanes that sent bytes in the round
trip: what the add costs the wall while it runs on the receiving lane's thread
and nowhere else.  Since PR 57 the reduce phase's LAST step divides inside
it (``reduce_buffer`` with the divisor: the owner's quotient rides the add on
the lanes' threads, and no pass between the phases is left), so the division's
time lies here.  DDP_SYNC's ``ring_add_s`` (``_ring.py`` says where it is
counted and which events are read); None on a program whose events carry no
such field."""

from ftbench.layer_metrics._ring import META, field_ms


def read(sources):
    return field_ms(sources, "ring_add_s")
