"""Host data plane, milliseconds a step: a lane's sender inside ``sendmsg`` and
its pacing (``send_framed_iov`` on the lane's tx worker, beside the receiving
thread), the MEAN over the lanes that sent bytes in the round trip.  DDP_SYNC's
``ring_tx_s`` (``_ring.py`` says where it is counted and which events are
read); None on a program whose events carry no such field."""

from ftbench.layer_metrics._ring import META, field_ms


def read(sources):
    return field_ms(sources, "ring_tx_s")
