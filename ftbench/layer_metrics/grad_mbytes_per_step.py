"""Device-host boundary: bytes of gradients one replica group hands to the
replica-dimension average each step (the parameters' own sizes and types)."""

META = dict(source="program_counter", layer="device-host boundary", unit="MB", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    return sources["grad_bytes_per_replica"] / 1e6
