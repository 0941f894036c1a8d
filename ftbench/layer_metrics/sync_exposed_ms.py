"""Device-host boundary: how long the chip waits between the end of the
gradient program and the start of the update program: D2H, the host ring,
H2D and the commit vote, as far as nothing hides them."""

META = dict(source="device_trace", layer="device-host boundary", unit="ms", moves="ddp_tokens_per_s_per_chip")

# hsdp.py's jitted functions as the trace's "XLA Modules" line names them
GRAD_PROGRAM, UPDATE_PROGRAM = r"jit__step", r"jit__update"


def read(sources):
    from ftbench import trace_reduce
    from ftbench.sources import mean_ms, traced_stretch

    stretch = traced_stretch(sources)
    if stretch is None:
        return None
    a, b, _ = stretch
    device = sources["trace"]["per_device"]
    modules = trace_reduce.clip(device[min(device)]["modules"], a, b)
    waits = trace_reduce.transitions(modules, GRAD_PROGRAM, UPDATE_PROGRAM)
    return mean_ms([end - start for start, end in waits])
