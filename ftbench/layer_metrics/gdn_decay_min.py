"""Kernels: the most negative log decay ``g`` a token and value head had, over
a step's Gated DeltaNet layers and the window's MOE_ROUTE flight events
(``decay_min``, taken on the device from the ``g`` the kernels were fed).  The
counter that says the cell really runs decays ``ops/kda.py``'s factorised form
could not (it leaves float32 below -5.5 a token): -20 to -60 at the seeded
start, and a reading above -5.5 means the initialisation is not the stated
one.  None on a program whose events lack the field."""

META = dict(source="program_counter", layer="kernels", unit="nats", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench.layer_metrics import _gdn

    events = [e for e in _gdn.route_events(sources) if e.get("decay_min")]
    return min(min(e["decay_min"]) for e in events) if events else None
