"""Host data plane: the share of replica 0's collectives whose AVERAGE the
communicator's ring made itself: of the step's ``tpuft/manager/normalize``
spans (one a collective, on the op thread: ``Manager.allreduce``'s
done-callback), 100 x those that carry ``in_ring=1`` over all of them, over
the traced steps.  With ``in_ring=1`` the rank that owns a chunk at the end of
the ring's reduce phase divided it by the participant count before the
allgather phase sent it round, and the callback divides nothing
(``sync_normalize_ms`` is then its few microseconds); with ``in_ring=0`` the
callback divided the ring's sum in numpy (the quantized ring).  0 where the
spans carry no such attribute (a program from before PR 40 divides every sum
in the callback), None where there is no such span.

The attribute is the MANAGER's record of the branch it took (it handed the
communicator the divisor and its callback divided nothing), not the ring's
word that it divided: a communicator that ignored the divisor and returned
sums would read 100 here all the same.  That the tiers honour the divisor is
what ``tests/test_allreduce_divisor.py`` holds, bit for bit; in a cell a
tier that did not would show in ``correct`` (sums where averages belong)."""

META = dict(source="program_counter", layer="host data plane", unit="%", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    from ftbench import program_spans

    found = program_spans.in_stretch(sources)
    if found is None:
        return None
    spans = [s for s in found[0] if s["name"] == "tpuft/manager/normalize"]
    if not spans:
        return None
    return 100.0 * sum(1 for s in spans if int(s.get("in_ring") or 0) == 1) / len(spans)
