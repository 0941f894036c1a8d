"""Heal: bytes a restarted life received:
``transport.last_heal_metrics.bytes_total`` where the transport fills it (a
striped heal), else the bytes of the state it holds after the heal
(parameters and optimizer state, from their shapes and types).  Mean over
the kills that healed."""

META = dict(source="program_counter", layer="heal", unit="MB", moves="resume_s")


def read(sources):
    import statistics

    kill = sources.get("kill")
    if not kill:
        return None
    healed = [k for k in kill["kills"] if k.get("timings", {}).get("heal_recv_s") is not None]
    if not healed:
        return None
    return statistics.fmean(
        (k["heal"][0] if k.get("heal") else kill["state_bytes"]) / 1e6 for k in healed
    )
