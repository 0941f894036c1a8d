"""Device-host boundary: the share of a step's gradient buckets that
``ddp.allreduce_pytree`` filled in host memory kept from an earlier step
(``warm_buckets`` beside ``buckets`` on the span
``tpuft/ddp/allreduce_pytree`` and so on its flight event DDP_SYNC): 100 x
the sum of the one over the sum of the other, over replica 0's DDP_SYNC
events of the window.  0 in a life's first round trip and after one that
failed; a bucket filled in fresh memory pays the first touch of every page
(``bucket_copy_ms``).  None where no event carries the counter (a program
from before PR 30)."""

META = dict(source="program_counter", layer="device-host boundary", unit="%", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    window = (sources.get("window") or [None])[0]
    if not window or not sources.get("flight"):
        return None
    t0, t1 = window[0]["t_enter"], window[-1]["t_exit"]
    events = [
        e for e in sources["flight"][0] or []
        if e.get("name") == "DDP_SYNC" and "warm_buckets" in e and t0 <= e.get("t", 0.0) <= t1
    ]
    buckets = sum(e["buckets"] for e in events)
    return 100.0 * sum(e["warm_buckets"] for e in events) / buckets if buckets else None
