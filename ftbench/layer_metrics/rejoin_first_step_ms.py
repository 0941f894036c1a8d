"""Entry points: from a heal's end (flight event HEAL_RECV_END of the
restarted life) to that life's first committed step.  Mean over the kills."""

META = dict(source="program_span", layer="entry points", unit="ms", moves="resume_s")


def read(sources):
    from ftbench.sources import mean_ms

    kill = sources.get("kill")
    if not kill:
        return None
    spans = []
    for k in kill["kills"]:
        ends = [e["t"] for e in k.get("events", []) if e.get("name") == "HEAL_RECV_END"]
        if ends and "first_commit" in k:
            spans.append(k["first_commit"] - ends[0])
    return mean_ms(spans)
