"""Host data plane: the share of a round trip's ring bytes that crossed on
lanes other than lane 0 (``striped_bytes`` beside ``ring_bytes`` on the span
``tpuft/ddp/allreduce_pytree`` and so on its flight event DDP_SYNC, both read
from the communicator's counts of payload bytes a lane,
``lane_stats()['lane_tx_bytes']``, before the round trip's first submit and
after its last ring): 100 x the sum of the one over the sum of the other, over
replica (or group) 0's DDP_SYNC events of the window.  Since PR 47 ``auto``
stripes a ring's frames over several connections a peer where no link is
emulated, one thread a lane and direction, and a frame of at least two stripe
floors (128 KiB) crosses in equal parts: some 75 at four lanes, less the
frames under two floors, which ride lane 0 whole.  0 where the events carry no
such counter (a program from before PR 47, whose ``auto`` is one lane) or the
ring ran at one lane, None where there is no event in the window."""

META = dict(source="program_counter", layer="host data plane", unit="%", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    window = (sources.get("window") or [None])[0]
    if not window or not sources.get("flight"):
        return None
    t0, t1 = window[0]["t_enter"], window[-1]["t_exit"]
    events = [
        e for e in sources["flight"][0] or []
        if e.get("name") == "DDP_SYNC" and e.get("bytes") and t0 <= e.get("t", 0.0) <= t1
    ]
    total = sum(e.get("ring_bytes") or e["bytes"] for e in events)
    return 100.0 * sum(e.get("striped_bytes", 0) for e in events) / total if total else None
