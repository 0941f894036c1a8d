"""Heal: span ``tpuft/heal/snapshot`` on the survivor's quorum thread (flight
event HEAL_SEND_END): its state dict and the on-device snapshot the
transport stages.  Mean over the kills."""

META = dict(source="program_span", layer="heal", unit="ms", moves="resume_s")


def read(sources):
    from ftbench import program_spans

    return program_spans.kill_mean(sources, "HEAL_SEND_END", "duration_s", 1000.0, survivor=True)
