"""Entry points: from the kill to the restarted life's first QUORUM_START
(flight event): the new Manager, its servers, the weights and the
optimizer state made anew.  Mean over the kills."""

META = dict(source="program_span", layer="entry points", unit="ms", moves="resume_s")


def read(sources):
    from ftbench import program_spans
    from ftbench.sources import mean_ms

    kill = sources.get("kill")
    if not kill:
        return None
    spans = []
    for k in kill["kills"]:
        starts = program_spans.flight_events(k.get("events"), "QUORUM_START")
        if starts:
            spans.append(starts[0]["t"] - k["t_kill"])
    return mean_ms(spans)
