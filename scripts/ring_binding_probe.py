"""What is left of ``comm_op_ms`` beside the ring's three phases: one cell
of the benchmark run as ``ftbench/run.py`` runs it, with the native ring's
ctypes call timed from Python's side and held against the ring's own word.

    chiprun -- python3 scripts/ring_binding_probe.py --workload \\
        mistral7b-ddp2-steady --seed 3000054111 --seconds 51 --trace 0

Every argument is ``ftbench/run.py``'s (``--rehearse`` with
``JAX_PLATFORMS=cpu`` walks it here).  The result line is the run's own;
after it, on stderr, one line ``PROBE {...}`` over the WHOLE process (every
replica, every epoch, warm-up included):

- ``calls``, ``call_s``: the op thread's native ring calls,
  ``tpuft_comm_allreduce_iov`` (a ring each) and, since PR 60,
  ``tpuft_ring_session_run`` (a round trip's rings in one), and the seconds
  from Python handing over to Python holding the interpreter lock again, by
  ``time.monotonic`` on the op thread; ``session_calls`` of them are
  sessions;
- ``phases_s``: ``ring_reduce_s + ring_average_s + ring_gather_s`` of every
  epoch, and ``wait_push_s``: ``ring_wait_push_s``, a session's op thread
  waiting INSIDE the call for the train thread's next bucket; both read from
  ``lane_stats()`` once when an epoch ends (a reconfigure or the shutdown),
  so the probe adds NO call a ring: one that read the counters after every
  ring committed 5.6 % fewer tokens (PERF.md section 5, PR 54);
- ``binding_ms_a_call``: ``(call_s - phases_s - wait_push_s) / calls``: what
  is left outside the phases and the push wait.  It holds the C function's
  own time outside them too (a ring's set-up and, in a session, a piece's
  bookkeeping between two rings), which a C-side clock around the whole
  function put at 0.04 % of the call (PR 54's probe checkout, PERF.md
  section 5): the rest is the wait for the interpreter lock beside the
  process's other threads, paid once a ring before PR 60 and once a round
  trip since.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PHASES = ("ring_reduce_s", "ring_average_s", "ring_gather_s")


def main() -> int:
    import json

    from ftbench import run as ftbench_run  # stamps the process's start
    from torchft_tpu import native

    lib = native._load()
    assert lib is not None, "native runtime unavailable"
    calls = []  # a call's seconds; list.append is atomic under the lock
    sessions = []  # those of them that were a session's run
    epochs = []  # an ended epoch's three phases, summed
    waits = []  # an ended epoch's wait for pushes

    def timed(ring, also=None):
        def timed_ring(*args):
            t0 = time.monotonic()
            rc = ring(*args)
            calls.append(time.monotonic() - t0)
            if also is not None:
                also.append(calls[-1])
            return rc

        return timed_ring

    lib.tpuft_comm_allreduce_iov = timed(lib.tpuft_comm_allreduce_iov)
    if hasattr(lib, "tpuft_ring_session_run"):  # (a parent's library has none)
        lib.tpuft_ring_session_run = timed(lib.tpuft_ring_session_run, sessions)

    def reads_the_epoch_first(method):
        def wrapped(self, *args, **kwargs):
            stats = self.lane_stats()
            epochs.append(sum(float(stats.get(k, 0.0)) for k in PHASES))
            waits.append(float(stats.get("ring_wait_push_s", 0.0)))
            return method(self, *args, **kwargs)

        return wrapped

    comm = native.CppCommunicator
    comm.configure = reads_the_epoch_first(comm.configure)
    comm.shutdown = reads_the_epoch_first(comm.shutdown)

    rc = ftbench_run.main()
    call_s, phases_s, wait_push_s = sum(calls), sum(epochs), sum(waits)
    print(
        "PROBE "
        + json.dumps(
            {
                "calls": len(calls),
                "session_calls": len(sessions),
                "call_s": call_s,
                "phases_s": phases_s,
                "wait_push_s": wait_push_s,
                "binding_ms_a_call": 1e3 * (call_s - phases_s - wait_push_s) / max(len(calls), 1),
            }
        ),
        file=sys.stderr,
    )
    return rc


if __name__ == "__main__":
    sys.exit(main())
