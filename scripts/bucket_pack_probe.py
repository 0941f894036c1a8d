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

``--ordered`` (PERF.md section 6, PR 32) reads instead what ORDER and WINDOW
the transfers should be started in so that the ring runs beside them: each
thread has a stand-in for the communicator's op thread (copies of each
landed bucket and an in-place division, ``--ring-ms-per-mb`` a megabyte in
all: the ledger's ``comm_op_ms`` + ``sync_normalize_ms`` over 973 MB), packs
into kept buffers, and starts a bucket's ``copy_to_host_async`` only
``window`` buckets ahead of the one it waits for (``all``: every leaf up
front, as the program did before PR 32).  ``--configs`` is a list of
``window:order``; the orders are ``tree`` (as the tree lists them), ``asc``,
``desc`` (by bytes) and ``flow`` (the smallest first, then by falling size).
One JSON line a config: the round (start to the last bucket's stand-in
done), the transfer stretch (start to the last bucket landed), when the
first bucket was handed over, and the share of the stand-in's seconds that
lie before the last landing.
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

def ordered(args, trees, bump, groups) -> None:
    """The ``--ordered`` mode: see the module's docstring."""
    from torchft_tpu import ddp

    by_size = lambda sign: lambda sizes: sorted(range(len(sizes)), key=lambda b: (sign * sizes[b], b))  # noqa: E731
    orders = {
        "tree": lambda sizes: list(range(len(sizes))),
        "asc": by_size(1),
        "desc": by_size(-1),
        "flow": ddp._pipeline_order,  # the one the program keeps
    }
    leaves0 = jax.tree_util.tree_leaves(trees[0])
    sizes = [sum(leaves0[i].nbytes for i in g) for g in groups]
    kept = [
        [np.zeros(sum(leaves0[i].size for i in g), leaves0[g[0]].dtype) for g in groups]
        for _ in range(args.threads)
    ]
    scratch = [np.zeros(max(k.size for k in ks), ks[0].dtype) for ks in kept]
    med = lambda xs: round(statistics.median(xs) * 1e3, 1)  # noqa: E731

    def stand_in(t: int, jobs: "queue.Queue", done: list) -> None:
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
            view = scratch[t][: flat.size]
            view[:] = flat
            flat[:] = view
            np.true_divide(flat, 2, out=flat, casting="unsafe")
            while time.perf_counter() - t0 < want:  # the ring's other passes
                view[:] = flat
            done.append((b, t0, time.perf_counter()))

    for config in args.configs.split(","):
        window_s, order_name = config.split(":")
        order = orders[order_name](sizes)
        window = len(order) if window_s == "all" else int(window_s)
        out = [[] for _ in range(args.threads)]
        barrier = threading.Barrier(args.threads)

        def run(t: int) -> None:
            tree = trees[t]
            for _ in range(args.rounds):
                tree = bump(tree)
                jax.block_until_ready(tree)
                leaves = jax.tree_util.tree_leaves(tree)
                jobs: "queue.Queue" = queue.Queue()
                done: list = []
                op = threading.Thread(target=stand_in, args=(t, jobs, done))
                op.start()
                barrier.wait()
                t0 = time.perf_counter()
                asked, waits, lands, hands = 0, [], [], []
                for at, b in enumerate(order):
                    while asked < min(at + window, len(order)):
                        for i in groups[order[asked]]:
                            leaves[i].copy_to_host_async()
                        asked += 1
                    t1 = time.perf_counter()
                    arrs = [np.asarray(leaves[i]).reshape(-1) for i in groups[b]]
                    t2 = time.perf_counter()
                    waits.append(t2 - t1)
                    lands.append(t2 - t0)
                    flat, off = kept[t][b], 0
                    for a in arrs:
                        flat[off : off + a.size] = a
                        off += a.size
                    jobs.put((b, flat))
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
                        waits=waits,
                        lands=lands,
                        op=busy,
                        beside=beside / busy if busy else 0.0,
                    )
                )
            trees[t] = tree

        threads = [threading.Thread(target=run, args=(t,)) for t in range(args.threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        rounds = out[0][1:]
        print(
            json.dumps(
                {
                    "window": window_s,
                    "order": order_name,
                    "buckets_mb": [round(sizes[b] / 1e6, 1) for b in order],
                    "round_ms": med([r["round"] for r in rounds]),
                    "round_ms_all": [round(r["round"] * 1e3, 1) for r in out[0]],
                    "round_ms_thread1": med([r["round"] for r in out[-1][1:]]),
                    "transfer_stretch_ms": med([r["stretch"] for r in rounds]),
                    "first_hand_ms": med([r["first_hand"] for r in rounds]),
                    "stand_in_ms": med([r["op"] for r in rounds]),
                    "stand_in_beside_transfer_pct": round(
                        100 * statistics.median(r["beside"] for r in rounds), 1
                    ),
                    "d2h_wait_ms_in_order": [med([r["waits"][k] for r in rounds]) for k in range(len(order))],
                    "lands_ms_in_order": [med([r["lands"][k] for r in rounds]) for k in range(len(order))],
                }
            ),
            flush=True,
        )


def sharded(args, cell, model) -> None:
    """The ``--sharded`` mode: see the module's docstring."""
    from torchft_tpu import ddp
    from torchft_tpu.parallel.hsdp import fsdp_shardings
    from torchft_tpu.parallel.mesh import make_mesh

    per_group = cell.config["layout"]["chips_per_group"]
    devices = jax.devices()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    bump = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x * 1.0009765625 + 1, t))
    med = lambda xs: round(statistics.median(xs) * 1e3, 1)  # noqa: E731

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

    most = max(int(c.split(":")[0]) for c in args.configs.split(","))
    trees = [make(t) for t in range(most)]
    jax.block_until_ready(trees)
    plans = [ddp._make_plan(jax.tree_util.tree_leaves(tree), ddp._bucket_cap_bytes()) for tree in trees]
    print(
        "buckets_mb", [round(b.size * b.dtype.itemsize / 1e6, 1) for b in plans[0].buckets],
        "direct_mb", round(plans[0].direct_nbytes / 1e6, 1), "of", round(plans[0].nbytes / 1e6, 1),
        file=sys.stderr,
    )
    for config in args.configs.split(","):
        n_threads, path = config.split(":")
        n_threads = int(n_threads)
        out = [[] for _ in range(n_threads)]
        barrier = threading.Barrier(n_threads)

        def run(t: int) -> None:
            tree, plan = trees[t], plans[t]
            kept = [np.zeros(b.size, b.dtype) for b in plan.buckets]
            for _ in range(args.rounds):
                tree = bump(tree)
                jax.block_until_ready(tree)
                leaves = jax.tree_util.tree_leaves(tree)
                barrier.wait()
                t0 = time.perf_counter()
                asked, waits, packs, by_shard = 0, [], [], []
                for b, bucket in enumerate(plan.buckets):
                    while asked < min(b + ddp._D2H_AHEAD, len(plan.buckets)):
                        ddp._start_copies(leaves, plan.buckets[asked])
                        asked += 1
                    t1 = time.perf_counter()
                    places: dict = {}
                    hosts = []
                    for slot in bucket.slots:
                        leaf = leaves[slot.index]
                        if path == "whole" or slot.direct is None:
                            hosts.append([np.asarray(leaf).reshape(-1)])
                            continue
                        parts = []
                        for place, shard in enumerate(ddp._unique_local_shards(leaf).values()):
                            t3 = time.perf_counter()
                            parts.append(np.asarray(shard.data))
                            places[place] = places.get(place, 0.0) + time.perf_counter() - t3
                        hosts.append(parts)
                    t2 = time.perf_counter()
                    flat = kept[b]
                    for slot, parts in zip(bucket.slots, hosts):
                        if path == "direct" and slot.direct is not None:
                            whole = flat[slot.offset : slot.offset + slot.size].reshape(slot.shape)
                            for index, block in zip(slot.direct, parts):
                                whole[index] = block
                        else:
                            flat[slot.offset : slot.offset + slot.size] = parts[0]
                    waits.append(t2 - t1)
                    packs.append(time.perf_counter() - t2)
                    by_shard.append([places.get(k, 0.0) for k in range(per_group)])
                out[t].append((time.perf_counter() - t0, waits, packs, by_shard))
            trees[t] = tree

        threads = [threading.Thread(target=run, args=(t,)) for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        rounds = out[0][1:]
        n = len(plans[0].buckets)
        print(
            json.dumps(
                {
                    "threads": n_threads,
                    "path": path,
                    "mbytes": round(plans[0].nbytes / 1e6, 2),
                    "round_ms": med([r[0] for r in rounds]),
                    "round_ms_all": [round(r[0] * 1e3, 1) for r in out[0]],
                    "round_ms_last_thread": med([r[0] for r in out[-1][1:]]),
                    "d2h_wait_ms": med([sum(r[1]) for r in rounds]),
                    "pack_ms": med([sum(r[2]) for r in rounds]),
                    "d2h_wait_ms_by_bucket": [med([r[1][b] for r in rounds]) for b in range(n)],
                    "pack_ms_by_bucket": [med([r[2][b] for r in rounds]) for b in range(n)],
                    "wait_ms_by_shard": [
                        [med([r[3][b][k] for r in rounds]) for k in range(per_group)] for b in range(n)
                    ],
                }
            ),
            flush=True,
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ordered", action="store_true")
    ap.add_argument("--sharded", action="store_true")
    ap.add_argument(
        "--configs",
        default=None,
        help="--ordered: window:order, ...; window a count of buckets or 'all'.  "
        "--sharded: threads:path, ...; path 'whole' or 'direct'",
    )
    ap.add_argument("--ring-ms-per-mb", type=float, default=0.97)
    ap.add_argument("--workload", default=None, help="mistral7b-ddp2-steady; --sharded: mistral7b-hsdp2x2-steady")
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--threads", type=int, default=2)
    ap.add_argument("--toy", action="store_true", help="the architecture's toy widths: a walk on the CPU")
    args = ap.parse_args()
    if args.configs is None:
        args.configs = (
            "2:whole,2:direct,2:direct,2:whole,1:whole,1:direct"
            if args.sharded
            else "all:tree,2:asc,2:desc,2:flow,1:flow,3:flow,all:flow,all:tree"
        )
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
        sharded(args, cell, model)
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
    leaves0 = jax.tree_util.tree_leaves(trees[0])
    plan = ddp._make_plan(leaves0, ddp._bucket_cap_bytes())  # the cell's own buckets
    groups = [[slot.index for slot in bucket.slots] for bucket in plan.buckets]
    # as the tree lists them (a dtype's buckets together), whatever order the plan keeps
    dtypes = [l.dtype.name for l in leaves0]
    groups.sort(key=lambda g: (dtypes.index(dtypes[g[0]]), g[0]))
    mbytes = sum(l.nbytes for l in leaves0) / 1e6
    print(
        "buckets",
        [[round(sum(leaves0[i].nbytes for i in g) / 1e6, 1)] for g in groups],
        "MB",
        round(mbytes, 2),
        file=sys.stderr,
    )
    if args.ordered:
        ordered(args, trees, bump, groups)
        return

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
                barrier.wait()
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

        threads = [threading.Thread(target=run, args=(t,)) for t in range(args.threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
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
