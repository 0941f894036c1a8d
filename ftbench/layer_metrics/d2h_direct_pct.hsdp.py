"""Device-host boundary: the share of a step's gradient bytes that
``ddp.allreduce_pytree`` wrote from each chip's shard straight into its
bucket, with no whole-leaf host array in between (``direct_bytes`` beside
``bytes`` on the span ``tpuft/ddp/allreduce_pytree`` and so on its flight
event DDP_SYNC): 100 x the sum of the one over the sum of the other, over
replica 0's DDP_SYNC events of the window.  A leaf that lies in shards on
several chips of one process goes that way since PR 44; before, jax
assembled the landed shards in a second host array of the leaf's size, made
anew every step (``d2h_wait_ms``).  What is left of 100 is the leaves every
chip holds whole (Mistral's float32 norms), which have no shards to write.
0 where the events carry no such counter (a program from before PR 44 makes
every leaf whole on the host), None where there is no event in the window.

It keeps the suffix ``.hsdp`` that PR 43 to 57 gave the four-chip cell's twins
(README.md, "On four chips"): only a group of several chips has sharded leaves
to read, so it has no namesake to fold into, and two tests under ``tests/``
load it by this name."""

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
    return 100.0 * sum(e.get("direct_bytes", 0) for e in events) / total if total else None
