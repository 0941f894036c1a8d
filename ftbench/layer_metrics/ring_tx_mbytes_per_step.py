"""Host data plane: bytes replica 0's communicator sent per step
(``CppCommunicator.lane_stats()['lane_tx_bytes']``, window's opening to
the last step)."""

META = dict(source="program_counter", layer="host data plane", unit="MB", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    tx = sources["lane_tx"][0]
    steps = (sources["final_step"] or 0) - (sources["open_step"] or 0)
    if "open" not in tx or "final" not in tx or steps <= 0 or tx["final"] <= tx["open"]:
        return None
    return (tx["final"] - tx["open"]) / steps / 1e6
