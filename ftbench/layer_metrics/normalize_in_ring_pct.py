"""Host data plane: the share of replica 0's collectives whose AVERAGE the
communicator's ring made itself, over the traced steps.  A collective is
counted where the Manager records it, on either of its two paths:

- the per-call path (the Python tier, every wrapper, the quantized ring): one
  ``tpuft/manager/normalize`` span a collective, on the op thread
  (``Manager.allreduce``'s done-callback), counted as the ring's where it
  carries ``in_ring=1``.  With ``in_ring=1`` the rank that owns a chunk at the
  end of the ring's reduce phase divided it by the participant count before the
  allgather phase sent it round, and the callback divides nothing; with
  ``in_ring=0`` the callback divided the ring's sum in numpy (the quantized
  ring).  A span that carries no such attribute (a program from before PR 40
  divides every sum in the callback) counts as the callback's;
- the session (PR 60: a round trip's rings are ONE call of the op thread, span
  ``tpuft/comm/session`` with ``pieces=``): ``Manager.ring_session`` opens it
  with ``divisor=`` and in no other way, so each of its ``pieces`` is a
  collective whose average the ring made, and no ``tpuft/manager/normalize``
  opens beside it.  Both two-group cells run this path since PR 60.

100 x the ring's over all of them; None where neither span is there.

A session's pieces are counted only inside the traced stretch of a device's
trace (``sources.traced_stretch``).  A trace with no device plane (the CPU
rehearsal) has no stretch, and there this reader finds nothing under a session:
tier-1 holds that a traced CPU walk of either two-group cell reports none of
five names, this one among them (``tests/_ftbench_view.py``
``SILENT_IN_A_SESSION``, written when the session was new and no reader read
it), and a ``benchmark`` PR edits nothing under ``tests/``.  The PR that may
drops the name from that set and the condition here (PERF.md section 7 (cn)).

The attribute and the span are the MANAGER's record of the branch it took (it
handed the communicator the divisor and divided nothing itself), not the
ring's word that it divided: a communicator that ignored the divisor and
returned sums would read 100 here all the same.  That the tiers honour the
divisor is what ``tests/test_allreduce_divisor.py`` holds, bit for bit; in a
cell a tier that did not would show in ``correct`` (sums where averages
belong)."""

META = dict(source="program_counter", layer="host data plane", unit="%", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    from ftbench import program_spans
    from ftbench.sources import traced_stretch

    found = program_spans.in_stretch(sources)
    if found is None:
        return None
    calls = [s for s in found[0] if s["name"] == "tpuft/manager/normalize"]
    in_ring = sum(1 for s in calls if int(s.get("in_ring") or 0) == 1)
    total = len(calls)
    if traced_stretch(sources) is not None:
        pieces = sum(int(s.get("pieces") or 1) for s in found[0] if s["name"] == "tpuft/comm/session")
        in_ring, total = in_ring + pieces, total + pieces
    return 100.0 * in_ring / total if total else None
