"""LocalSGD training example with periodic durable checkpoints.

Each replica trains locally and averages *parameters* every ``--sync-every``
steps (communication-reduced DP, the precursor to DiLoCo), saving a durable
checkpoint (model + Manager state) after each sync so the whole job can be
restored after total loss — live peer healing covers single-replica loss.

    python -m torchft_tpu.launcher --replicas 2 -- \
        python examples/train_localsgd.py --total-syncs 10 --platform cpu
"""

from __future__ import annotations

import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from torchft_tpu.tier import default_tier, make_communicator, manager_server_cls
from torchft_tpu.local_sgd import LocalSGD
from torchft_tpu.manager import Manager
from torchft_tpu.models.cnn import SimpleCNN
from torchft_tpu.utils.checkpoint import (
    latest_step,
    load_checkpoint,
    save_checkpoint,
)

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s: %(message)s")
logger = logging.getLogger("train_localsgd")


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--total-syncs", type=int, default=10)
    parser.add_argument("--sync-every", type=int, default=8)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--ckpt-dir", default=None)
    parser.add_argument(
        "--replica-group-id",
        type=int,
        default=int(os.environ.get("REPLICA_GROUP_ID", 0)),
    )
    parser.add_argument("--min-replicas", type=int, default=1)
    parser.add_argument("--platform", default=None)
    args = parser.parse_args()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from torchft_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    rng = np.random.default_rng(args.replica_group_id)
    x = rng.normal(size=(1024, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 10, size=1024).astype(np.int32)

    model = SimpleCNN()
    params = model.init(jax.random.PRNGKey(0))
    tx = optax.adam(1e-3)
    holder = {"params": params, "opt_state": tx.init(params)}

    tier = default_tier()  # C++ plane when native/libtpuft.so loads
    manager = Manager(
        comm=make_communicator(timeout_s=60.0),  # data-plane tier dispatch
        load_state_dict=lambda s: holder.update(s),
        state_dict=lambda: dict(holder),
        min_replica_size=args.min_replicas,
        replica_id=f"train_localsgd_{args.replica_group_id}",
        quorum_timeout=120.0,
        server_cls=manager_server_cls(tier),
    )

    # restore from the latest durable checkpoint (job-level resume)
    if args.ckpt_dir:
        step = latest_step(args.ckpt_dir)
        if step is not None:
            state = load_checkpoint(args.ckpt_dir, step)
            holder.update(state["model"])
            manager.load_state_dict(state["torchft"])
            logger.info("restored durable checkpoint at step %d", step)

    local_sgd = LocalSGD(manager, holder, sync_every=args.sync_every)
    loss_and_grad = jax.jit(jax.value_and_grad(model.loss))

    syncs = 0
    with local_sgd:
        while syncs < args.total_syncs:
            idx = rng.integers(0, len(x), size=args.batch_size)
            batch = (jnp.asarray(x[idx]), jnp.asarray(y[idx]))
            loss, grads = loss_and_grad(holder["params"], batch)
            # optimizer state lives IN the holder so heals and durable
            # checkpoints always carry the trained moments
            updates, holder["opt_state"] = tx.update(
                grads, holder["opt_state"], holder["params"]
            )
            holder["params"] = optax.apply_updates(holder["params"], updates)
            result = local_sgd.step()
            if result is not None:
                syncs += 1
                logger.info("sync %d committed=%s loss %.4f", syncs, result, float(loss))
                # one writer per checkpoint dir: the participating rank-0
                # replica (see utils/checkpoint.py docstring)
                if args.ckpt_dir and result and manager.participating_rank() == 0:
                    save_checkpoint(
                        args.ckpt_dir,
                        manager.current_step(),
                        {"model": dict(holder), "torchft": manager.state_dict()},
                    )

    import hashlib

    digest = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(holder["params"]):
        digest.update(np.ascontiguousarray(np.asarray(leaf, dtype=np.float32)))
    print(f"FINAL syncs={syncs} params_sha={digest.hexdigest()[:16]}")
    manager.shutdown()


if __name__ == "__main__":
    main()
