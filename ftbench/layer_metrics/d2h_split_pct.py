"""Device-host boundary: the share of a step's gradient bytes that crossed to
the host, and round the ring, as PIECES of a leaf over the bucket cap
(``split_bytes`` beside ``bytes`` on the span ``tpuft/ddp/allreduce_pytree``
and so on its flight event DDP_SYNC): 100 x the sum of the one over the sum
of the other, over replica (or group) 0's DDP_SYNC events of the window.
Since PR 46 the cap is the most one transfer and one ring carry, so a leaf
over it is as many buckets as it has pieces; before, such a leaf was one
bucket, one transfer and one ring, and the first large ring waited for the
largest leaf's landing (``sync_second_submit_ms``).  What is left of 100 is
the leaves at or under the cap.  0 where the events carry no such counter (a
program from before PR 46), None where there is no event in the window."""

META = dict(source="program_counter", layer="device-host boundary", unit="%", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    window = (sources.get("window") or [None])[0]
    if not window or not sources.get("flight"):
        return None
    t0, t1 = window[0]["t_enter"], window[-1]["t_exit"]
    events = [
        e for e in sources["flight"][0] or []
        if e.get("name") == "DDP_SYNC" and e.get("bytes") and t0 <= e.get("t", 0.0) <= t1
    ]
    total = sum(e["bytes"] for e in events)
    return 100.0 * sum(e.get("split_bytes", 0) for e in events) / total if total else None
