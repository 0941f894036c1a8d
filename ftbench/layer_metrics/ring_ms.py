"""Host data plane: seconds of a step in which a ring allreduce of this
replica was in flight (union of submit-to-done of ``manager.allreduce``'s
works, host clock)."""

META = dict(source="host_clock", layer="host data plane", unit="ms", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    from ftbench.accounting import union_seconds
    from ftbench.sources import all_steps, mean_ms

    rings = [r["ring"] for r in all_steps(sources) if r["ring"]]
    return mean_ms([union_seconds([(a, b) for a, b in ring if b]) for ring in rings])
