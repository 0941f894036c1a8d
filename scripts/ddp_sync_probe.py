"""Where a round trip's time goes, from the checkout it is run in: runs
``scripts/bucket_pack_probe.py`` (every other argument is its own; ``--stress
--ring real`` is the mode this is for) and, as each Manager shuts down, prints
the MEDIANS of its last 40 DDP_SYNC events' numeric fields, seconds as
milliseconds: ``SYNC <replica> {...}`` with ``duration_s``, the train
thread's ``d2h_s`` and ``pack_s``, the gather thread's ``ring_wait_s`` and
``h2d_s``, the ring's seven counters, and since PR 60 ``ring_calls`` and
``ring_wait_push_s``.  ``--per-call`` keeps a ring an op on a tree that has
the ring session (the Manager of THIS process offers none), for telling the
session's effect from the rest of a change's.

Beside them ``THREADS {...}``: what the kernel says of the process's threads
over ten of the run's last seconds, grouped by the thread's name (the lanes'
workers are ``tpuft-rx<lane>`` / ``tpuft-tx<lane>``, Python's threads go by
their ``threading`` name): ``cpu`` the cores a group kept busy
(``/proc/self/task/<tid>/stat``), ``pre`` how often a second its threads were
taken off a core while they still wanted it (``nonvoluntary_ctxt_switches``)
and ``stood`` the cores' worth of time they stood runnable with no core to
run on (``schedstat``; 0 where the kernel keeps no such count, as the chip
machines' does not).  A host whose cores the rings oversubscribe shows
``all.cpu`` near ``cores`` and ``pre`` in the thousands; one with cores to
spare reads both low.  Two checkouts side by side in ONE chip call (the machines
differ by more than most changes)::

    chiprun [--chips 4] -- bash -c 'for d in _parent _checkout _checkout _parent; do
      (cd $d && PYTHONFAULTHANDLER=1 python3 scripts/ddp_sync_probe.py --stress --ring real \\
         [--sharded] --rounds 50 --check-every 25 | grep "SYNC\\|THREADS\\|rounds"); done'
"""

import json
import os
import re
import runpy
import statistics
import sys
import threading
import time

sys.path.insert(0, os.getcwd())

from torchft_tpu.manager import Manager  # noqa: E402

if "--per-call" in sys.argv:
    sys.argv.remove("--per-call")
    Manager.ring_session = lambda self, pieces: None

_shutdown = Manager.shutdown
_SKIP = ("t", "seq", "ev", "step", "t0")
_WINDOW_S = (12.0, 2.0)  # the scheduler's numbers between so long and so long before the end
_samples: list = []


_TICK = os.sysconf("SC_CLK_TCK")


def _threads_now() -> dict:
    """{tid: (name, seconds on a core, times taken off one, seconds stood on a run queue)}"""
    names = {t.native_id: t.name for t in threading.enumerate()}
    out = {}
    for tid in os.listdir("/proc/self/task"):
        try:
            with open(f"/proc/self/task/{tid}/stat") as f:
                comm, rest = f.read().rsplit(")", 1)
            with open(f"/proc/self/task/{tid}/status") as f:
                pre = int(re.search(r"nonvoluntary_ctxt_switches:\s+(\d+)", f.read()).group(1))
            with open(f"/proc/self/task/{tid}/schedstat") as f:
                stood = int(f.read().split()[1]) / 1e9
        except (OSError, AttributeError, IndexError):
            continue  # the thread has ended
        comm, fields = comm.split("(", 1)[1], rest.split()
        name = comm if comm.startswith("tpuft-") else names.get(int(tid), comm)
        ran = (int(fields[11]) + int(fields[12])) / _TICK  # utime + stime
        out[int(tid)] = (re.sub(r"[-_ ]?\d+$", "", name), ran, pre, stood)
    return out


def _sample() -> None:
    while True:
        _samples.append((time.monotonic(), _threads_now()))
        del _samples[:-40]
        time.sleep(0.5)


threading.Thread(target=_sample, name="probe-sampler", daemon=True).start()


def _threads_line() -> str:
    now = time.monotonic()
    first = min(_samples, key=lambda s: abs(now - _WINDOW_S[0] - s[0]))
    last = min(_samples, key=lambda s: abs(now - _WINDOW_S[1] - s[0]))
    wall = max(last[0] - first[0], 1e-9)
    groups: dict = {}
    for tid, (name, *now) in last[1].items():
        if tid in first[1]:
            g = groups.setdefault(name, [0, 0.0, 0.0, 0.0])
            g[0] += 1
            for i, (a, b) in enumerate(zip(first[1][tid][1:], now)):
                g[i + 1] += (b - a) / wall
    total = [round(sum(g[i] for g in groups.values()), 2) for i in (1, 2, 3)]
    line = {"seconds": round(wall, 1), "cores": len(os.sched_getaffinity(0)), "all": dict(zip(("cpu", "pre", "stood"), total))}
    for name, (n, cpu, pre, stood) in sorted(groups.items(), key=lambda kv: -kv[1][1]):
        if cpu >= 0.05:
            line[name] = {"threads": n, "cpu": round(cpu, 2), "pre": round(pre), "stood": round(stood, 2)}
    return "THREADS " + json.dumps(line)


_printed = threading.Lock()


def shutdown(self):
    if _printed.acquire(blocking=False) and len(_samples) > 1:
        print(_threads_line(), flush=True)
    events = [e for e in self._flight.snapshot() if e["name"] == "DDP_SYNC"][-40:]
    keys = sorted({k for e in events for k, v in e.items() if isinstance(v, (int, float)) and k not in _SKIP})
    line = {
        k: round(statistics.median([e[k] for e in events if k in e]) * (1e3 if k.endswith("_s") else 1), 2)
        for k in keys
    }
    print("SYNC " + self._flight.replica_id[:10] + " " + json.dumps(line), flush=True)
    return _shutdown(self)


Manager.shutdown = shutdown
sys.argv = ["scripts/bucket_pack_probe.py"] + sys.argv[1:]
runpy.run_path(os.path.join(os.getcwd(), "scripts", "bucket_pack_probe.py"), run_name="__main__")
