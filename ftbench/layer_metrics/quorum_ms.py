"""Control plane: the lighthouse quorum round trip of a step, as the Manager
times it (``manager.last_quorum_timings['quorum_rpc_s']``).  It runs beside
the forward pass, so it costs a step only what it outlasts the device by."""

META = dict(source="program_span", layer="control plane", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.sources import all_steps, mean_ms

    return mean_ms([r["quorum_rpc_s"] for r in all_steps(sources)])
