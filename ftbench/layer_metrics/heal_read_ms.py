"""Heal: seconds the restarted life was blocked reading the checkpoint off
the socket (``read_s`` of flight event HEAL_RECV_END, span
``tpuft/heal/fetch``).  Mean over the kills."""

META = dict(source="program_span", layer="heal", unit="ms", moves="resume_s")


def read(sources):
    from ftbench import program_spans

    return program_spans.kill_mean(sources, "HEAL_RECV_END", "read_s", 1000.0, survivor=False)
