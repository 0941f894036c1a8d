"""Heal: seconds a restarted life spent receiving the survivor's state:
``transport.last_heal_metrics.duration_s`` where the transport fills it (a
striped heal from several sources), else the Manager's own
``last_quorum_timings['heal_recv_s']`` (one source).  Mean over the kills."""

META = dict(source="program_span", layer="heal", unit="ms", moves="resume_s")


def read(sources):
    from ftbench.sources import mean_ms

    kill = sources.get("kill")
    if not kill:
        return None
    seconds = [
        k["heal"][1] if k.get("heal") else k.get("timings", {}).get("heal_recv_s")
        for k in kill["kills"]
    ]
    return mean_ms([s for s in seconds if s is not None])
