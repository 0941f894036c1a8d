"""What the train thread's share of the replica-dimension round trip costs on
this host, stage by stage, WITHOUT the ring (PERF.md section 6, PR 30).

Two threads, as the two thread replicas of ``mistral7b-ddp2-steady``, each
with the cell's gradient tree (the shapes of ``Llama.init`` at the cell's
configuration, fresh device arrays every round), bucketed as
``ddp.allreduce_pytree`` buckets them.  Three modes, ``--rounds`` rounds each:

(i)   ``d2h``: ``copy_to_host_async`` on all leaves, then ``np.asarray`` each;
(ii)  ``fresh``: the same and the pack into ``np.empty`` a bucket a round;
(iii) ``kept``: the same and the pack into buffers made in the first round.

Prints one JSON line a mode: seconds a round (thread 0), each bucket's wait
for its leaves and each bucket's pack, medians over the rounds after the
first.  ``chiprun -- python3 scripts/bucket_pack_probe.py``.

``--ordered`` (PERF.md section 6, PR 32 and PR 46) reads instead what ORDER,
WINDOW and CAP the transfers should be started in so that the ring runs
beside them: each thread has a stand-in for the communicator's op thread
(copies of each landed bucket and an in-place division, ``--ring-ms-per-mb``
a megabyte in all: the ledger's ``comm_op_ms`` + ``sync_normalize_ms`` over
973 MB), packs into kept buffers through the program's own
``ddp._start_copies``, ``ddp._land`` and ``ddp._pack`` (so a leaf over the cap crosses in
the program's pieces), and starts a bucket's copies only ``window`` ahead of
the one it waits for: ``<n>MB`` bytes in flight, as the program counts them
since PR 46 (the bucket it waits for and the next ones until the sum passes
n MiB), a bare number that many BUCKETS (PR 32's window), ``all`` every
bucket up front (the program before PR 32).  ``--configs`` is a list of
``window:order[:cap]``; the orders are ``tree`` (as the tree lists them),
``asc``, ``desc`` (by bytes) and ``flow`` (the smallest first, then by
falling size: the program's); ``cap`` is the bucket cap in MiB (the
program's own where it is left out).  One JSON line a config: the round
(start to the last bucket's stand-in done), the transfer stretch (start to
the last bucket landed), when the first and the SECOND bucket were handed
over, and the share of the stand-in's seconds that lie before the last
landing.  With ``--sharded`` the same on the four-chip cell's layout: a
thread a group, the leaves in shards on the group's chips.

``--stress`` (PERF.md section 6, PR 46) runs the round trip ITSELF, not a
stand-in: ``--rounds`` calls of ``ddp.allreduce_pytree`` a thread on the
cell's tree (``--sharded``: the four-chip cell's), a fresh tree every round
whose program is dispatched and not waited for, kept buffers, the gather
thread's restore and give-back included.  ``--ring real``: a Manager a
thread behind one lighthouse, the tier's own communicators over loopback,
so every bucket is rung in place while the train thread packs the next
piece of the same buffer; every ``--check-every`` rounds the threads'
averages must have one digest.  ``--ring none``: a stand-in for the Manager
whose ``allreduce`` hands the bucket back as it came, so the round trip is
the slices, the transfers, the pack and the restore alone, and what comes
back must be the tree bit for bit.  One JSON line every ``--check-every``
rounds (rounds done, the median round, the process's resident memory), on
stdout and, flushed, in ``--out``: a process that dies says how far it got.
Run it with ``PYTHONFAULTHANDLER=1``.
"""

from __future__ import annotations

import argparse
import json
import queue
import statistics
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

def _run_threads(run, threads: int, barrier: threading.Barrier) -> None:
    """``run(t)`` on a thread each; one that raises breaks the barrier the
    others wait at, and the error ends the process (a hang would cost the
    chip's whole time limit)."""
    errors: list = []

    def guarded(t: int) -> None:
        try:
            run(t)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)
            barrier.abort()

    workers = [threading.Thread(target=guarded, args=(t,)) for t in range(threads)]
    for th in workers:
        th.start()
    for th in workers:
        th.join()
    for e in errors:
        if not isinstance(e, threading.BrokenBarrierError):
            raise e


def _window_of(spec: str):
    """``(buckets, bytes)`` of a window: one of them None (``all``: both)."""
    if spec == "all":
        return None, None
    if spec.upper().endswith("MB"):
        return None, int(float(spec[:-2]) * (1 << 20))
    return int(spec), None


def ordered(args, trees, bump) -> None:
    """The ``--ordered`` mode: see the module's docstring."""
    from torchft_tpu import ddp

    by_size = lambda sign: lambda sizes: sorted(range(len(sizes)), key=lambda b: (sign * sizes[b], b))  # noqa: E731
    orders = {
        "tree": lambda sizes: list(range(len(sizes))),
        "asc": by_size(1),
        "desc": by_size(-1),
        "flow": ddp._pipeline_order,  # the one the program keeps
    }
    threads = len(trees)
    med = lambda xs: round(statistics.median(xs) * 1e3, 1)  # noqa: E731

    def stand_in(scratch: np.ndarray, jobs: "queue.Queue", done: list) -> None:
        while True:
            job = jobs.get()
            if job is None:
                return
            b, flat = job
            t0 = time.perf_counter()
            want = args.ring_ms_per_mb * 1e-3 * flat.nbytes / 1e6
            if want <= 0.0:  # no stand-in: the transfer with nothing beside it
                done.append((b, t0, t0))
                continue
            view = scratch[: flat.nbytes].view(flat.dtype)
            view[:] = flat
            flat[:] = view
            np.true_divide(flat, 2, out=flat, casting="unsafe")
            while time.perf_counter() - t0 < want:  # the ring's other passes
                view[:] = flat
            done.append((b, t0, time.perf_counter()))

    for config in args.configs.split(","):
        window_s, order_name, *cap_s = config.split(":")
        cap = int(float(cap_s[0]) * (1 << 20)) if cap_s else ddp._bucket_cap_bytes()
        plans = [ddp._make_plan(jax.tree_util.tree_leaves(tree), cap) for tree in trees]
        # as the tree lists them (a dtype's buckets together, a leaf's pieces
        # in a row), whatever order the plan keeps; then the config's order
        in_tree = sorted(range(len(plans[0].buckets)), key=lambda b: (plans[0].buckets[b].buffer, plans[0].buckets[b].offset))
        sizes = [plans[0].buckets[b].nbytes for b in in_tree]
        order = [in_tree[b] for b in orders[order_name](sizes)]
        ahead_buckets, ahead_bytes = _window_of(window_s)
        kept = [[np.zeros(size, dtype) for dtype, size in plan.buffers] for plan in plans]
        scratch = [np.zeros(max(b.nbytes for b in plan.buckets), np.uint8) for plan in plans]
        out = [[] for _ in range(threads)]
        barrier = threading.Barrier(threads)

        def run(t: int) -> None:
            tree, buckets = trees[t], [plans[t].buckets[b] for b in order]
            for _ in range(args.rounds):
                tree = bump(tree)
                jax.block_until_ready(tree)
                leaves = jax.tree_util.tree_leaves(tree)
                jobs: "queue.Queue" = queue.Queue()
                done: list = []
                op = threading.Thread(target=stand_in, args=(scratch[t], jobs, done))
                op.start()
                barrier.wait(timeout=300)
                t0 = time.perf_counter()
                asked, ahead, flying, waits, lands, hands = 0, 0, {}, [], [], []
                for at, bucket in enumerate(buckets):
                    while asked < len(buckets) and (
                        asked <= at
                        or (ahead_buckets is None and ahead_bytes is None)
                        or (ahead_buckets is not None and asked < at + ahead_buckets)
                        or (ahead_bytes is not None and ahead < ahead_bytes)
                    ):
                        flying[asked] = ddp._start_copies(leaves, buckets[asked])
                        ahead += buckets[asked].crossing
                        asked += 1
                    t1 = time.perf_counter()
                    hosts = ddp._land(leaves, bucket, flying.pop(at))
                    ahead -= bucket.crossing
                    t2 = time.perf_counter()
                    waits.append(t2 - t1)
                    lands.append(t2 - t0)
                    flat = kept[t][bucket.buffer][bucket.offset : bucket.offset + bucket.size]
                    ddp._pack(bucket, flat, hosts)
                    jobs.put((at, flat))
                    hands.append(time.perf_counter() - t0)
                jobs.put(None)
                op.join()
                last_land = t0 + lands[-1]
                busy = sum(e - s for _, s, e in done)
                beside = sum(max(0.0, min(e, last_land) - s) for _, s, e in done)
                out[t].append(
                    dict(
                        round=done[-1][2] - t0,
                        stretch=lands[-1],
                        first_hand=hands[0],
                        second_hand=hands[min(1, len(hands) - 1)],
                        waits=waits,
                        lands=lands,
                        op=busy,
                        beside=beside / busy if busy else 0.0,
                    )
                )
            trees[t] = tree

        _run_threads(run, threads, barrier)
        rounds = out[0][1:]
        brief = len(order) > 16  # a line of 47 buckets three times over is too long for the end of the output
        print(
            json.dumps(
                {
                    "window": window_s,
                    "order": order_name,
                    "cap_mb": round(cap / (1 << 20), 2),
                    "threads": threads,
                    "buckets": len(order),
                    "split_pct": round(100 * plans[0].split_nbytes / plans[0].nbytes, 3),
                    "direct_pct": round(100 * plans[0].direct_nbytes / plans[0].nbytes, 3),
                    "buckets_mb": [round(plans[0].buckets[b].nbytes / 1e6, 1) for b in order][: 6 if brief else None],
                    "round_ms": med([r["round"] for r in rounds]),
                    "round_ms_all": [round(r["round"] * 1e3, 1) for r in out[0]],
                    "round_ms_last_thread": med([r["round"] for r in out[-1][1:]]),
                    "transfer_stretch_ms": med([r["stretch"] for r in rounds]),
                    "first_hand_ms": med([r["first_hand"] for r in rounds]),
                    "second_hand_ms": med([r["second_hand"] for r in rounds]),
                    "stand_in_ms": med([r["op"] for r in rounds]),
                    "stand_in_beside_transfer_pct": round(
                        100 * statistics.median(r["beside"] for r in rounds), 1
                    ),
                    "d2h_wait_ms": med([sum(r["waits"]) for r in rounds]),
                    "lands_ms_in_order": [med([r["lands"][k] for r in rounds]) for k in range(len(order))][:: 4 if brief else 1],
                }
            ),
            flush=True,
        )


class _NoRing:
    """All that ``ddp.allreduce_pytree`` asks of a Manager, and no ring: a
    bucket comes back as it went, at once."""

    def __init__(self, name: str) -> None:
        from torchft_tpu.obs.flight import FlightRecorder
        from torchft_tpu.work import DummyWork

        self._flight = FlightRecorder(name)
        self._host_buckets = None
        self._done = DummyWork
        self.errors: list = []

    def errored(self):
        return None

    def allreduce_is_identity(self) -> bool:
        return False

    def ring_counters(self):
        return {"epoch": 0}

    def allreduce(self, flat, **_kw):
        return self._done(flat)

    def report_error(self, e: BaseException) -> None:
        self.errors.append(e)

    def _register_pending(self, _work) -> None:
        pass


def stress(args, trees, bump) -> None:
    """The ``--stress`` mode: see the module's docstring."""
    import hashlib

    from torchft_tpu import ddp, tier as tier_mod
    from torchft_tpu.manager import Manager

    threads = len(trees)
    real = args.ring == "real"
    if args.window_mb is not None:  # the program has no setting for it: the probe writes the constant
        ddp._D2H_AHEAD_BYTES = int(args.window_mb * (1 << 20))
    lighthouse = tier = None
    if real:
        tier = tier_mod.default_tier()
        lighthouse = tier_mod.make_lighthouse(
            bind="127.0.0.1:0", min_replicas=threads, join_timeout_ms=100, quorum_tick_ms=100,
            heartbeat_timeout_ms=5000, tier=tier,
        )
        managers = []
        for t in range(threads):
            state = {"w": np.zeros(3, np.float32)}
            managers.append(
                Manager(
                    comm=tier_mod.make_communicator(timeout_s=60.0, tier=tier),
                    load_state_dict=state.update,
                    state_dict=lambda state=state: dict(state),
                    min_replica_size=threads,
                    replica_id=f"probe_{t}",
                    lighthouse_addr=lighthouse.local_address(),
                    timeout=60.0, quorum_timeout=60.0, connect_timeout=60.0,
                    server_cls=tier_mod.manager_server_cls(tier),
                )
            )
    else:
        managers = [_NoRing(f"probe_{t}") for t in range(threads)]
    same = jax.jit(
        lambda a, b: jnp.all(jnp.stack(jax.tree_util.tree_leaves(jax.tree_util.tree_map(jnp.array_equal, a, b))))
    )
    plan = ddp._make_plan(jax.tree_util.tree_leaves(trees[0]), ddp._bucket_cap_bytes())
    head = dict(
        ring=args.ring, threads=threads, sharded=args.sharded, cap_mb=ddp._bucket_cap_bytes() / (1 << 20),
        window_mb=ddp._D2H_AHEAD_BYTES / (1 << 20), buckets=len(plan.buckets), mbytes=round(plan.nbytes / 1e6, 3),
        split_pct=round(100 * plan.split_nbytes / plan.nbytes, 3), direct_pct=round(100 * plan.direct_nbytes / plan.nbytes, 3),
        tier=tier,
    )
    sink = open(args.out, "a") if args.out else None

    def say(line: dict) -> None:
        text = json.dumps(line)
        print(text, flush=True)
        if sink is not None:
            sink.write(text + "\n")
            sink.flush()

    say(head)
    barrier = threading.Barrier(threads)
    digests = [None] * threads
    seconds = [[] for _ in range(threads)]

    def rss_gb() -> float:
        with open("/proc/self/status") as f:
            return next(int(l.split()[1]) for l in f if l.startswith("VmRSS")) / 1e6

    def run(t: int) -> None:
        manager, tree = managers[t], trees[t]
        devices = sorted(jax.tree_util.tree_leaves(tree)[0].sharding.device_set, key=lambda d: d.id)
        with jax.default_device(devices[0]):  # as the harness runs a replica's thread
            for n in range(1, args.rounds + 1):
                t0 = time.perf_counter()
                if real:
                    manager.start_quorum()
                tree = bump(tree)  # dispatched: the first copy waits for it, as for the gradient program
                avg = ddp.allreduce_pytree(manager, tree).wait(timeout=120.0)
                if real and not manager.should_commit():
                    raise RuntimeError(f"thread {t} round {n}: the step did not commit")
                if not real and manager.errors:
                    raise manager.errors[0]
                jax.block_until_ready(avg)
                seconds[t].append(time.perf_counter() - t0)
                if n % args.check_every and n != args.rounds:
                    continue
                if real:
                    sha = hashlib.sha256()
                    for leaf in jax.tree_util.tree_leaves(avg):
                        sha.update(np.asarray(leaf).tobytes())
                    digests[t] = sha.hexdigest()
                elif not bool(same(tree, avg)):
                    raise RuntimeError(f"thread {t} round {n}: what came back is not what went")
                barrier.wait(timeout=300)
                if t == 0:
                    if real and len(set(digests)) != 1:
                        raise RuntimeError(f"round {n}: the averages differ: {digests}")
                    say(
                        dict(
                            rounds=n,
                            round_ms=round(statistics.median(seconds[0][-args.check_every :]) * 1e3, 1),
                            round_ms_max=round(max(seconds[0][-args.check_every :]) * 1e3, 1),
                            rss_gb=round(rss_gb(), 2),
                            digest=digests[0][:16] if real else "same",
                        )
                    )
                barrier.wait(timeout=300)
        trees[t] = tree

    try:
        _run_threads(run, threads, barrier)
    finally:
        for manager in managers:
            if real:
                manager.shutdown()
        if lighthouse is not None:
            lighthouse.shutdown()
    alive = [th.name for th in threading.enumerate() if th.name == "tpuft_ddp_gather"]
    say(dict(done=True, ring=args.ring, rounds_a_thread=args.rounds, gather_threads_alive=alive))


def sharded_trees(cell, model, groups: int):
    """A tree a group as the four-chip cell lays it: the leaves in shards on
    the group's own chips; and the program that makes the next round's."""
    from torchft_tpu.parallel.hsdp import fsdp_shardings
    from torchft_tpu.parallel.mesh import make_mesh

    # a program over a group's mesh that the persistent compile cache hands
    # back halts a group that is not the host's first (PERF.md section 6, PR 43)
    jax.config.update("jax_enable_compilation_cache", False)
    per_group = cell.config["layout"]["chips_per_group"]
    devices = jax.devices()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def make(t: int):
        mesh = make_mesh(fsdp=per_group, devices=devices[t * per_group : (t + 1) * per_group])
        params_sh = fsdp_shardings(cell.architecture.model(cell.config), mesh)[0]
        with mesh:
            return jax.jit(
                lambda key: jax.tree_util.tree_map(
                    lambda s: jax.random.normal(key, s.shape, jnp.float32).astype(s.dtype), shapes
                ),
                out_shardings=params_sh,
            )(jax.random.PRNGKey(t))

    trees = [make(t) for t in range(groups)]
    jax.block_until_ready(trees)
    return trees, jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x * 1.0009765625 + 1, t))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ordered", action="store_true")
    ap.add_argument("--sharded", action="store_true", help="--ordered on the four-chip cell's layout: a thread a group")
    ap.add_argument(
        "--configs",
        default="all:tree,2:flow,64MB:flow,128MB:flow,256MB:flow,512MB:flow,all:flow,256MB:flow:32",
        help="--ordered: window:order[:cap], ...; window '<n>MB' in flight, a count of buckets or 'all'; "
        "cap in MiB (the program's own where left out)",
    )
    ap.add_argument("--ring-ms-per-mb", type=float, default=0.97)
    ap.add_argument("--workload", default=None, help="mistral7b-ddp2-steady; --sharded: mistral7b-hsdp2x2-steady")
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--threads", type=int, default=2)
    ap.add_argument("--toy", action="store_true", help="the architecture's toy widths: a walk on the CPU")
    ap.add_argument("--stress", action="store_true", help="--rounds round trips of ddp.allreduce_pytree itself, a thread")
    ap.add_argument("--ring", choices=("real", "none"), default="real", help="--stress: through real communicators, or none")
    ap.add_argument("--check-every", type=int, default=100, help="--stress: rounds between two checks and lines")
    ap.add_argument("--out", default=None, help="--stress: a file the lines are appended to, flushed")
    ap.add_argument("--window-mb", type=float, default=None, help="--stress: ddp._D2H_AHEAD_BYTES for this process (the program's own where left out)")
    args = ap.parse_args()
    if args.workload is None:
        args.workload = "mistral7b-hsdp2x2-steady" if args.sharded else "mistral7b-ddp2-steady"

    from ftbench import spec
    from torchft_tpu import ddp

    cell = spec.load_cell(args.workload)
    if args.toy:
        cell.config.update(cell.architecture.TOY["config"])
    model = cell.architecture.model(cell.config)
    print("device", jax.devices()[0].device_kind, file=sys.stderr)
    if args.sharded:
        (stress if args.stress else ordered)(args, *sharded_trees(cell, model, args.threads))
        return
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    @jax.jit
    def make(key):
        return jax.tree_util.tree_map(
            lambda s: jax.random.normal(key, s.shape, jnp.float32).astype(s.dtype), shapes
        )

    bump = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x * 1.0009765625 + 1, t))
    trees = [make(jax.random.PRNGKey(t)) for t in range(args.threads)]
    jax.block_until_ready(trees)
    if args.stress or args.ordered:
        (stress if args.stress else ordered)(args, trees, bump)
        return
    leaves0 = jax.tree_util.tree_leaves(trees[0])
    # the cell's buckets as the plan before PR 46 cut them (between leaves
    # alone), as the tree lists them: this mode starts every copy up front
    plan = ddp._make_plan(leaves0, ddp._bucket_cap_bytes())
    groups = sorted({b.buffer: [slot.index for slot in b.slots] for b in plan.buckets}.values(), key=lambda g: g[0])
    mbytes = sum(l.nbytes for l in leaves0) / 1e6
    print(
        "buckets",
        [[round(sum(leaves0[i].nbytes for i in g) / 1e6, 1)] for g in groups],
        "MB",
        round(mbytes, 2),
        file=sys.stderr,
    )

    for mode in ("d2h", "fresh", "kept", "fresh", "kept", "d2h"):
        out = [[] for _ in range(args.threads)]
        barrier = threading.Barrier(args.threads)

        def run(t: int) -> None:
            tree = trees[t]
            kept = None
            for _ in range(args.rounds):
                tree = bump(tree)
                jax.block_until_ready(tree)
                leaves = jax.tree_util.tree_leaves(tree)
                barrier.wait(timeout=300)
                t0 = time.perf_counter()
                for leaf in leaves:
                    leaf.copy_to_host_async()
                waits, packs = [], []
                fresh_set = []
                for b, group in enumerate(groups):
                    t1 = time.perf_counter()
                    arrs = [np.asarray(leaves[i]).reshape(-1) for i in group]
                    t2 = time.perf_counter()
                    waits.append(t2 - t1)
                    if mode != "d2h":
                        total = sum(a.size for a in arrs)
                        if mode == "kept" and kept is not None:
                            flat = kept[b]
                        else:
                            flat = np.empty(total, arrs[0].dtype)
                        off = 0
                        for a in arrs:
                            flat[off : off + a.size] = a
                            off += a.size
                        fresh_set.append(flat)
                    packs.append(time.perf_counter() - t2)
                if mode == "kept" and kept is None:
                    kept = fresh_set
                out[t].append((time.perf_counter() - t0, waits, packs))
            trees[t] = tree

        _run_threads(run, args.threads, barrier)
        rounds = out[0][1:]
        med = lambda xs: round(statistics.median(xs) * 1e3, 1)  # noqa: E731
        print(
            json.dumps(
                {
                    "mode": mode,
                    "threads": args.threads,
                    "mbytes": round(mbytes, 2),
                    "round_ms": med([r[0] for r in rounds]),
                    "round_ms_all": [round(r[0] * 1e3, 1) for r in out[0]],
                    "round_ms_thread1": med([r[0] for r in out[-1][1:]]),
                    "d2h_wait_ms": med([sum(r[1]) for r in rounds]),
                    "pack_ms": med([sum(r[2]) for r in rounds]),
                    "d2h_wait_ms_by_bucket": [med([r[1][b] for r in rounds]) for b in range(len(groups))],
                    "pack_ms_by_bucket": [med([r[2][b] for r in rounds]) for b in range(len(groups))],
                }
            ),
            flush=True,
        )


if __name__ == "__main__":
    main()
