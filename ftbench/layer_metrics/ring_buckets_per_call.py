"""Host data plane: how many of a round trip's buckets one ring call of the
communicator's op thread carries: the sum of DDP_SYNC's ``buckets`` over the
sum of its ``ring_calls`` (the native ring calls the op thread made for the
round trip: one a bucket where every ring is a call of its own, ONE where the
round trip's rings are a session the op thread stays inside, PR 60), over
replica (or group) 0's DDP_SYNC events of the window, as ``ring_striped_pct``
takes them.  1 on the per-call path, the step's buckets (58 on one chip a
group, 89 on two) with the session; what a ring pays once a call (the way back
into Python and out again beside the train threads, the fd lists, the scratch)
is paid that many times less.  None where no event carries ``ring_calls`` (a
program from before the counter, a round trip whose epoch changed under it, no
event in the window)."""

META = dict(source="program_counter", layer="host data plane", unit="buckets/call", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    window = (sources.get("window") or [None])[0]
    if not window or not sources.get("flight"):
        return None
    t0, t1 = window[0]["t_enter"], window[-1]["t_exit"]
    events = [
        e for e in sources["flight"][0] or []
        if e.get("name") == "DDP_SYNC" and e.get("bytes") and t0 <= e.get("t", 0.0) <= t1 and e.get("ring_calls")
    ]
    calls = sum(e["ring_calls"] for e in events)
    return sum(e.get("buckets", 0) for e in events) / calls if calls else None
