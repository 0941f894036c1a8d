"""Heal: seconds the survivor's HTTP handler was blocked writing the
checkpoint to the socket (``write_s`` of flight event HEAL_SERVE_END, span
``tpuft/heal/serve``).  Mean over the kills."""

META = dict(source="program_span", layer="heal", unit="ms", moves="resume_s")


def read(sources):
    from ftbench import program_spans

    return program_spans.kill_mean(sources, "HEAL_SERVE_END", "write_s", 1000.0, survivor=True)
