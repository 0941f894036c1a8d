"""Control plane: from a kill to the survivor's first quorum after it
(flight event QUORUM_ADOPT on the survivor): that quorum is without the
victim or carries its restarted life.  Mean over the run's kills."""

META = dict(source="program_span", layer="control plane", unit="ms", moves="resume_s")


def read(sources):
    from ftbench.accounting import detect_s
    from ftbench.sources import mean_ms

    kill = sources.get("kill")
    if not kill:
        return None
    found = [detect_s(k["t_kill"], kill["survivor_events"]) for k in kill["kills"]]
    return mean_ms([s for s in found if s is not None])
