"""Heal: the share of the bytes the survivor's HTTP handler served whose
device-to-host transfer had been started BEFORE the handler came to wait for
their leaf (``ahead_bytes`` beside ``bytes`` on flight event HEAL_SERVE_END,
span ``tpuft/heal/serve``): 100 x the one over the other, each the mean over
the kills.  Near 100 the handler waits for the first leaf alone
(``heal_serve_d2h_ms`` is the exposed wait) and the rest cross while the
leaf before them is on the wire; 0 where every leaf was fetched when its
turn came.  None where no event carries the counter (a program from before
PR 36)."""

META = dict(source="program_counter", layer="heal", unit="%", moves="resume_s")


def read(sources):
    from ftbench import program_spans

    ahead = program_spans.kill_mean(sources, "HEAL_SERVE_END", "ahead_bytes", 1.0, survivor=True)
    served = program_spans.kill_mean(sources, "HEAL_SERVE_END", "bytes", 1.0, survivor=True)
    return 100.0 * ahead / served if ahead is not None and served else None
