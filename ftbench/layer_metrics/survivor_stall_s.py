"""Control plane and heal, from the survivor's side: its longest gap
between two commits around a kill, less its median step time (the gap that
holds the kill and one on each side are the neighbourhood: the next kill's
gap lies three further on).  Mean over the run's kills.  About ``resume_s`` less one step: the
survivor's step that holds the kill ends when the healed life commits."""

META = dict(source="host_clock", layer="control plane", unit="s", moves="resume_s")


def read(sources):
    import statistics

    from ftbench.accounting import survivor_stall_s

    kill = sources.get("kill")
    if not kill:
        return None
    return statistics.fmean(
        survivor_stall_s(kill["survivor_commits"], k["t_kill"], around=1) for k in kill["kills"]
    )
