"""Control plane: seconds inside ``manager.should_commit`` (the fence and
the vote's round trip), host clock around the call."""

META = dict(source="host_clock", layer="control plane", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.sources import all_steps, mean_ms

    return mean_ms([r["commit"][1] - r["commit"][0] for r in all_steps(sources)])
