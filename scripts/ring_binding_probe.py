"""What is left of ``comm_op_ms`` beside the ring's three phases: one cell
of the benchmark run as ``ftbench/run.py`` runs it, with the native ring's
ctypes call timed from Python's side and held against the ring's own word.

    chiprun -- python3 scripts/ring_binding_probe.py --workload \\
        mistral7b-ddp2-steady --seed 3000054111 --seconds 51 --trace 0

Every argument is ``ftbench/run.py``'s (``--rehearse`` with
``JAX_PLATFORMS=cpu`` walks it here).  The result line is the run's own;
after it, on stderr, one line ``PROBE {...}`` over the WHOLE process (every
replica, every epoch, warm-up included):

- ``calls``, ``call_s``: the calls of ``tpuft_comm_allreduce_iov`` and the
  seconds from Python handing over to Python holding the interpreter lock
  again, by ``time.monotonic`` on the op thread;
- ``phases_s``: ``ring_reduce_s + ring_average_s + ring_gather_s`` of every
  epoch, read from ``lane_stats()`` once when an epoch ends (a reconfigure
  or the shutdown), so the probe adds NO call a ring: one that read the
  counters after every ring committed 5.6 % fewer tokens (PERF.md section 5,
  PR 54);
- ``binding_ms_a_call``: ``(call_s - phases_s) / calls``.  It holds the C
  function's own time outside the phases too, which a C-side clock around
  the whole function put at 0.04 % of the call (PR 54's probe checkout,
  PERF.md section 5): the rest is the wait for the interpreter lock beside
  the process's other threads.
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
    epochs = []  # an ended epoch's three phases, summed
    ring = lib.tpuft_comm_allreduce_iov

    def timed_ring(*args):
        t0 = time.monotonic()
        rc = ring(*args)
        calls.append(time.monotonic() - t0)
        return rc

    lib.tpuft_comm_allreduce_iov = timed_ring

    def reads_the_epoch_first(method):
        def wrapped(self, *args, **kwargs):
            stats = self.lane_stats()
            epochs.append(sum(float(stats.get(k, 0.0)) for k in PHASES))
            return method(self, *args, **kwargs)

        return wrapped

    comm = native.CppCommunicator
    comm.configure = reads_the_epoch_first(comm.configure)
    comm.shutdown = reads_the_epoch_first(comm.shutdown)

    rc = ftbench_run.main()
    call_s, phases_s = sum(calls), sum(epochs)
    print(
        "PROBE "
        + json.dumps(
            {
                "calls": len(calls),
                "call_s": call_s,
                "phases_s": phases_s,
                "binding_ms_a_call": 1e3 * (call_s - phases_s) / max(len(calls), 1),
            }
        ),
        file=sys.stderr,
    )
    return rc


if __name__ == "__main__":
    sys.exit(main())
