"""Heal: span ``tpuft/heal/apply`` on the restarted life's train thread
(flight event HEAL_APPLY): the fetched state put back on the device in the
trainer's layout.  Mean over the kills."""

META = dict(source="program_span", layer="heal", unit="ms", moves="resume_s")


def read(sources):
    from ftbench import program_spans

    return program_spans.kill_mean(sources, "HEAL_APPLY", "duration_s", 1000.0, survivor=False)
