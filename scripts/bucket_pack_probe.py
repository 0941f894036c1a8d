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
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="mistral7b-ddp2-steady")
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--threads", type=int, default=2)
    args = ap.parse_args()

    from ftbench import spec
    from torchft_tpu import ddp

    cell = spec.load_cell(args.workload)
    model = cell.architecture.model(cell.config)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    print("device", jax.devices()[0].device_kind, file=sys.stderr)

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
