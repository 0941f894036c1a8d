"""Shared by the six readers of where the ring says its time goes (no metric
itself: ``BENCHMARK.json`` names no ``_ring``).  The communicator counts
seconds beneath ``tpuft/comm/session`` (a round trip's rings are ONE call of
the op thread since PR 60; ``tpuft/comm/op`` a collective on the per-call
path), where no span can be opened: a lane's inside recv, inside the reduce's
add and inside send, the op thread's in the ring's two phases and in the
steps' tails (``native/comm.h`` ``EpochIO``, ``lane_stats()``; also in a
stand-alone division between the phases, which since PR 57 only a ring of one
member takes: ``ring_average_s``, read by nobody);
``Manager.ring_counters()`` is read before a round trip's
first submit and after its last ring, and ``ddp.allreduce_pytree`` puts the
differences on the span ``tpuft/ddp/allreduce_pytree`` and so on its flight
event DDP_SYNC.  A reader takes replica (or group) 0's DDP_SYNC events of the
window, as ``ring_striped_pct`` does."""

META = dict(source="program_counter", layer="host data plane", unit="ms", moves="ddp_tokens_per_s_per_chip")


def field_ms(sources, field):
    """Milliseconds a step of DDP_SYNC's ``field`` (seconds a round trip): the
    mean over the window's events that carry it, None where none does (a
    program from before the counters, a round trip whose epoch changed under
    it, no event in the window)."""
    import statistics

    window = (sources.get("window") or [None])[0]
    if not window or not sources.get("flight"):
        return None
    t0, t1 = window[0]["t_enter"], window[-1]["t_exit"]
    values = [
        e[field] for e in sources["flight"][0] or []
        if e.get("name") == "DDP_SYNC" and e.get("bytes") and t0 <= e.get("t", 0.0) <= t1 and field in e
    ]
    return 1000.0 * statistics.fmean(values) if values else None
