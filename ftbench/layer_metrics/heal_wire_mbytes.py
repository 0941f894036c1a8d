"""Heal: bytes the restarted life read off the wire (``bytes`` of flight
event HEAL_RECV_END, from ``transport.last_heal_metrics``), where
``heal_mbytes`` counts them from shapes.  Mean over the kills."""

META = dict(source="program_counter", layer="heal", unit="MB", moves="resume_s")


def read(sources):
    from ftbench import program_spans

    return program_spans.kill_mean(sources, "HEAL_RECV_END", "bytes", 1e-06, survivor=False)
