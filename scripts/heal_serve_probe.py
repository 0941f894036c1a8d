"""How far AHEAD of the leaf it writes a survivor should start the next leaves'
device-to-host transfers while it serves a heal (PERF.md section 6, PR 36).

One process, as the two thread replicas of ``mistral7b-ddp2-kill``: the
survivor's ``HTTPTransport`` stages the cell's state (parameters and the two
moments of ``Llama.init`` at the cell's configuration and a step count, in
the order the trainer holds them: 37 leaves of 0.02-268 MB, fresh device
arrays every round, so no host value is kept from the round before), a second transport fetches ``/full`` from it over loopback, as the
new life's quorum thread does.  ``--windows`` is a list of ``leaves[:MB]``
(``0``: nothing asked ahead, what the program did before PR 36; ``all``:
every leaf of the request up front), set on
``serialization._D2H_AHEAD_LEAVES`` / ``_D2H_AHEAD_BYTES`` for the rounds of
that config; the list is walked forth and back so that a drifting host
shows.  One JSON line a config: the fetch (the cell's ``heal_ms``), the
reader's blocked seconds (``heal_read_ms``), the handler's exposed wait for
leaves (``heal_serve_d2h_ms``), its socket writes (``heal_serve_write_ms``)
and the share of the bytes whose transfer was under way before the handler
came to them (``heal_serve_ahead_pct``), medians over the rounds.

``--rss`` reads instead what the survivor's host HOLDS: the process's
resident bytes before a send, after it with the plan still staged, and after
``disallow_checkpoint`` dropped the plan.

``chiprun -- python3 scripts/heal_serve_probe.py --rounds 3``; ``--rehearse``
walks it on the CPU at toy widths.
"""

from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def _rss_mb() -> float:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * 4096 / 1e6


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--windows", default="0,1,2,4,all")
    ap.add_argument("--workload", default="mistral7b-ddp2-kill")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--rss", action="store_true")
    ap.add_argument("--rehearse", action="store_true", help="toy widths, for a walk on the CPU")
    args = ap.parse_args()

    from ftbench import spec
    from torchft_tpu.checkpointing import serialization
    from torchft_tpu.checkpointing.http_transport import HTTPTransport
    from torchft_tpu.obs.flight import FlightRecorder

    cell = spec.load_cell(args.workload)
    config = dict(cell.config, **cell.architecture.TOY["config"]) if args.rehearse else cell.config
    model = cell.architecture.model(config)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    print("device", jax.devices()[0].device_kind, file=sys.stderr)

    @jax.jit
    def make(key):
        tree = lambda k: jax.tree_util.tree_map(  # noqa: E731
            lambda s: jax.random.normal(k, s.shape, jnp.float32).astype(s.dtype), shapes
        )
        p, m, v = jax.random.split(key, 3)
        return {"params": tree(p), "mu": tree(m), "nu": tree(v), "count": jnp.zeros((), jnp.int32)}

    def as_the_trainer_holds_it(s):
        # a plan lists the leaves in the dict's own order: the parameters
        # first (jit returns their keys sorted), then the optimizer's state
        return {"params": s["params"], "opt_state": {"count": s["count"], "mu": s["mu"], "nu": s["nu"]}}

    bump = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x * 1.0009765625 + 1, t))
    state = make(jax.random.PRNGKey(0))
    jax.block_until_ready(state)
    sizes = [l.nbytes for l in jax.tree_util.tree_leaves(state)]
    print("leaves", len(sizes), "MB", round(sum(sizes) / 1e6, 2), file=sys.stderr)

    survivor, new_life = HTTPTransport(timeout=120.0), HTTPTransport(timeout=120.0)
    survivor.flight = FlightRecorder("probe", cap=64)
    where = f"http://localhost:{survivor.port}"
    step = 0

    def heal(keep_staged: bool = False) -> dict:
        nonlocal state, step
        step += 1
        state = bump(state)
        jax.block_until_ready(state)
        survivor.send_checkpoint([1], step, as_the_trainer_holds_it(state), 120.0)
        t0 = time.perf_counter()
        got = new_life.recv_checkpoint(0, where, step, 120.0)
        heal_s = time.perf_counter() - t0
        if step == 1:  # the bytes are the state's
            want = jax.tree_util.tree_leaves(as_the_trainer_holds_it(state))
            have = jax.tree_util.tree_leaves(got)
            assert len(want) == len(have)
            assert all(np.array_equal(np.asarray(w), h) for w, h in zip(want[:4], have[:4]))
        del got
        if not keep_staged:
            survivor.disallow_checkpoint()
        # the handler writes its event after the reader has its last byte
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            served = [e for e in survivor.flight.snapshot() if e["name"] == "HEAL_SERVE_END" and e["step"] == step]
            if served:
                break
            time.sleep(0.01)
        e = served[-1]
        return dict(
            heal_ms=heal_s * 1e3,
            heal_read_ms=new_life.last_heal_metrics.read_s * 1e3,
            heal_serve_d2h_ms=e["d2h_s"] * 1e3,
            heal_serve_write_ms=e["write_s"] * 1e3,
            heal_serve_ahead_pct=100.0 * e["ahead_bytes"] / e["bytes"],
        )

    try:
        heal()  # the first fetch pays the sockets' and the allocator's first use
        if args.rss:
            gc.collect()
            before = _rss_mb()
            heal(keep_staged=True)
            gc.collect()
            staged = _rss_mb()
            survivor.disallow_checkpoint()
            gc.collect()
            print(json.dumps(dict(rss_mb_before=round(before, 1), rss_mb_plan_staged=round(staged, 1),
                                  rss_mb_plan_dropped=round(_rss_mb(), 1), state_mb=round(sum(sizes) / 1e6, 1))))
            return
        windows = args.windows.split(",")
        runs: dict = {w: [] for w in windows}
        for window in windows + windows[::-1]:
            leaves, _, mbytes = window.partition(":")
            serialization._D2H_AHEAD_LEAVES = len(sizes) if leaves == "all" else int(leaves)
            serialization._D2H_AHEAD_BYTES = int(float(mbytes) * (1 << 20)) if mbytes else sum(sizes)
            for _ in range(args.rounds):
                runs[window].append(heal())
                print(window, {k: round(v, 1) for k, v in runs[window][-1].items()}, file=sys.stderr)
        for window in windows:
            line = {k: round(statistics.median(r[k] for r in runs[window]), 1) for k in runs[window][0]}
            print(json.dumps(dict(window=window, rounds=len(runs[window]), **line,
                                  heal_ms_all=[round(r["heal_ms"]) for r in runs[window]])))
    finally:
        survivor.shutdown()
        new_life.shutdown()


if __name__ == "__main__":
    main()
