"""Compiled step: own device time a step of the operations that are
COLLECTIVES over ICI (FSDP inside a replica group: all-gathers of the
parameters, reduce-scatters and all-reduces of the gradients, collective
permutes, with the ``-start`` / ``-done`` halves of an asynchronous one), on
the traced chip (replica 0's first), in whatever program they stand
(``jit__step``, ``jit__update``).  Own time as ``device_scopes.py`` takes it:
an event's duration less the events nested in it, so a collective that runs
beside a product costs here only what the device WAITED for it (its ``-done``
half).  Beside ``step_device_ms.ddp`` it says how much of a group's step is
the exchange.  An operation is a collective by its OWN name (the trace names
an operation by its whole HLO line, ``%fusion.200 = ... fusion(...,
%all-gather.269)``: what stands before `` = ``, never an operand's) or by
the ``hlo_category`` the profiler gives it.  On the v5e the compiler fuses a
gradient's reduce-scatter WITH the product that makes the gradient (category
``all-reduce-scatter fusion``): such a fusion is a collective here and counts
whole, the product's time with it, so the metric is the exchange's cost from
above (14.99 of 18.45 ms a step in the cell's first traced run were such
fusions: PERF.md section 6, PR 43).  None where the traced chip ran no such
operation (a group of one chip)."""

import re

META = dict(source="device_trace", layer="compiled step", unit="ms", moves="ddp_tokens_per_s_per_chip")

COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|collective-broadcast", re.IGNORECASE
)


def is_collective(op):
    own_name = op["name"].split(" = ", 1)[0]
    return bool(COLLECTIVE.search(own_name) or COLLECTIVE.search(op.get("category") or ""))


def read(sources):
    from ftbench import device_scopes

    found = device_scopes.in_stretch(sources)
    if found is None:
        return None
    ops, steps = found
    own = [op["own_s"] for op in ops if is_collective(op)]
    return 1000.0 * sum(own) / steps if own else None
