"""Host data plane, milliseconds a step: a lane's thread inside ``::recv`` of
the ring's frames (a frame's header and payload: waiting for the peer AND the
kernel's copy out of the socket, one syscall, not told apart), the MEAN over
the lanes that sent bytes in the round trip: lanes run beside each other on
equal parts, so the mean is a lane's share of the op thread's wall time.  With
``ring_add_ms`` at most ``ring_reduce_phase_ms + ring_gather_phase_ms``.
DDP_SYNC's ``ring_rx_s`` (``_ring.py`` says where it is counted and which
events are read); None on a program whose events carry no such field."""

from ftbench.layer_metrics._ring import META, field_ms


def read(sources):
    return field_ms(sources, "ring_rx_s")
