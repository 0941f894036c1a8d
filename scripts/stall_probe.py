"""Where does a stalled step of ftbench's ws1-steady cell spend its seconds?

    chiprun -- python3 scripts/stall_probe.py --seed 7 --seconds 300 --out chiprun_out/probe

Runs the cell as ``ftbench/run.py`` does (one long window tells as much as
six short ones, for a third of the chip time), with three things added:

- ``HSDPTrainer.train_step`` is replaced by a copy that stamps every phase
  (quorum, grad dispatch, allreduce, commit vote, update dispatch, the wait
  for the loss);
- a watchdog thread that only sleeps 50 ms: while a phase is overdue it
  writes every thread's Python frames, the threads that used CPU, and the
  host's counters to ``<out>/<tag>.watch.txt``.  WHEN its first dump comes
  says whether the process stood still (PERF.md section 6, PR 26: it came at
  the stall's end, 4.6 and 9.8 s late);
- a 10 Hz sampler of ``/proc``: a gap in its timestamps beside a long step
  is a freeze of the whole process, not of the device.

``--annotate-always`` makes every span a ``TraceAnnotation`` with no session
on (ISSUE 26's first wording, which went with the stalls); ``--extra N`` adds
N more a step (``--extra-mode kw | bare | enabled``).  The last stdout line
is ``PROBE {...}``: the long steps by phase; ``<out>/<tag>.steps.json`` has
every step and, for the long ones, the sampler's last 2.5 s.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LONG_S = 0.128  # ws1-steady's step is 113 ms
OVERDUE_S = 0.35
PID = os.getpid()

cur = ["idle", time.monotonic(), 0]  # phase, since, step
steps = []
ring = []  # host samples, the last 30 s
tring = []  # (t, per-thread CPU ticks), the last 30 s


def read(path):
    try:
        with open(path) as f:
            return f.read()
    except OSError as e:
        return f"<{type(e).__name__}>"


def host_sample():
    st = read(f"/proc/{PID}/stat").rsplit(")", 1)[-1].split()
    mem = read("/proc/meminfo")
    return {
        "t": time.monotonic(),
        "cpu": read("/proc/stat").split("\n", 1)[0],
        "self": dict(utime=int(st[11]), stime=int(st[12]), threads=int(st[17])),
        "load": read("/proc/loadavg").strip(),
        "mem": {
            line.split(":")[0]: line.split()[1]
            for line in mem.splitlines()
            if line.split(":")[0] in ("MemFree", "Cached", "AnonPages")
        },
    }


def thread_sample():
    out = {}
    base = f"/proc/{PID}/task"
    for tid in os.listdir(base):
        s = read(f"{base}/{tid}/stat")
        if s.startswith("<"):
            continue
        f = s.rsplit(")", 1)[-1].split()
        out[tid] = dict(
            comm=s[s.index("(") + 1 : s.rindex(")")],
            state=f[0], utime=int(f[11]), stime=int(f[12]),
        )
    return out


def busy_threads(before, after):
    rows = []
    for tid, d in after.items():
        p = before.get(tid, {"utime": 0, "stime": 0})
        ticks = d["utime"] - p["utime"] + d["stime"] - p["stime"]
        if ticks > 0:
            rows.append((ticks, tid, d["comm"], d["state"]))
    return sorted(rows, reverse=True)[:25]


def sampler():
    n = 0
    while True:
        time.sleep(0.1)
        n += 1
        ring.append(host_sample())
        del ring[:-300]
        if n % 5 == 0:
            tring.append((time.monotonic(), thread_sample()))
            del tring[:-60]


def watchdog(log):
    dumped_for, n_dumps = None, 0
    while True:
        time.sleep(0.05)
        phase, since, step = cur
        over = time.monotonic() - since
        if phase == "idle" or step <= 20 or over <= OVERDUE_S:
            continue
        if dumped_for != (phase, since):
            dumped_for, n_dumps = (phase, since), 0
        if over > OVERDUE_S + n_dumps:  # at 0.35 s, then every second
            n_dumps += 1
            log.write(f"\n==== OVERDUE step={step} phase={phase} over={over:.2f}s dump#{n_dumps}\n")
            log.flush()
            faulthandler.dump_traceback(file=log, all_threads=True)
            before = tring[-3][1] if len(tring) >= 3 else {}
            log.write("-- CPU ticks since about 1.5 s ago (ticks, tid, comm, state)\n")
            for row in busy_threads(before, thread_sample()):
                log.write(f"   {row}\n")
            log.write(f"-- host now: {json.dumps(host_sample())}\n")
            log.flush()


def install(args):
    from jax.profiler import TraceAnnotation

    from torchft_tpu.ddp import ft_allreduce
    from torchft_tpu.obs import spans
    from torchft_tpu.obs.spans import span as obs_span
    from torchft_tpu.parallel import hsdp

    if args.annotate_always:
        spans._session_on()  # loads the annotation class
        spans._session_on = lambda: True

    def extra(step):
        for _ in range(args.extra):
            if args.extra_mode == "kw":
                with TraceAnnotation("probe/extra", r="ftbench_0", step=step):
                    pass
            elif args.extra_mode == "bare":
                with TraceAnnotation("probe/extra"):
                    pass
            else:
                TraceAnnotation.is_enabled()

    def mark(phase):
        cur[0], cur[1] = phase, time.monotonic()
        return cur[1]

    def train_step(self, batch):
        rec = {}
        cur[2] += 1
        t0 = mark("quorum")
        self.manager.start_quorum()
        extra(cur[2])
        t1 = mark("grad")
        with obs_span("tpuft/step/grad"):
            loss, grads = self._grad_step(self.holder["params"], batch)
        t2 = mark("allreduce")
        grads = ft_allreduce(self.manager, grads, should_quantize=self.quantize_outer)
        t3 = mark("commit")
        committed = self.manager.should_commit()
        t4 = mark("update")
        if committed:
            with obs_span("tpuft/step/update"):
                params, opt_state = self._update_step(
                    self.holder["params"], self.holder["opt_state"], grads
                )
            self.holder["params"] = params
            self.holder["opt_state"] = opt_state
        t5 = mark("loss")
        out = float(loss)
        t6 = mark("idle")
        rec.update(
            step=cur[2], t=t0, wall=t6 - t0, quorum=t1 - t0, grad=t2 - t1,
            allreduce=t3 - t2, commit=t4 - t3, update=t5 - t4, loss=t6 - t5,
        )
        if rec["wall"] > LONG_S and len(steps) > 20:
            rec["host"] = ring[-25:] + [host_sample()]
            if tring:
                rec["busy"] = busy_threads(tring[-1][1], thread_sample())
        steps.append(rec)
        return out, committed

    hsdp.HSDPTrainer.train_step = train_step


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--tag", default="probe")
    parser.add_argument("--annotate-always", action="store_true")
    parser.add_argument("--extra", type=int, default=0)
    parser.add_argument("--extra-mode", choices=("kw", "bare", "enabled"), default="kw")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()
    os.makedirs(args.out, exist_ok=True)

    install(args)
    log = open(os.path.join(args.out, f"{args.tag}.watch.txt"), "w")
    threading.Thread(target=sampler, name="probe_sampler", daemon=True).start()
    threading.Thread(target=watchdog, args=(log,), name="probe_watchdog", daemon=True).start()

    from ftbench import harness, spec

    cell = spec.load_cell("mistral7b-ws1-steady")
    rc = harness.run_cell(cell, args.seed, args.seconds, False, args.rehearse, T_PROCESS)

    body = steps[20:]
    long_steps = [s for s in body if s["wall"] > LONG_S]
    summary = dict(
        tag=args.tag, annotate_always=args.annotate_always, extra=args.extra,
        extra_mode=args.extra_mode, steps=len(steps), n_long=len(long_steps),
        long=[{k: v for k, v in s.items() if k not in ("host", "busy")} for s in long_steps],
    )
    for key in ("wall", "quorum", "grad", "allreduce", "commit", "update", "loss"):
        values = sorted(s[key] for s in body)
        summary["median_" + key] = values[len(values) // 2] if values else None
    with open(os.path.join(args.out, f"{args.tag}.steps.json"), "w") as f:
        json.dump(dict(summary=summary, steps=steps), f)
    print("PROBE", json.dumps(summary)[:8000])
    return rc


if __name__ == "__main__":
    sys.exit(main())
