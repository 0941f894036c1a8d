"""Compiled step: own device time a step of every operation, Mosaic kernels
INCLUDED, whose scope path holds ``rematted_computation``: what running the
forward pass again inside the backward pass costs (``jax.checkpoint``).  None on
a program without scopes."""

META = dict(source="device_trace", layer="compiled step", unit="ms", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import device_scopes

    return device_scopes.own_ms_per_step(sources, lambda op: op["remat"])
