"""Fault-tolerant data-parallel training example (BASELINE config 1).

The torchft_tpu analog of the reference's ``train_ddp.py``: an ordinary
jax/optax train loop on a toy CNN where fault tolerance is two extra verbs —
``opt.start_step()`` and ``opt.step()`` — plus a gradient allreduce.  Run one
process per replica group::

    python -m torchft_tpu.lighthouse --min_replicas 1 --bind 0.0.0.0:29510 &
    TORCHFT_LIGHTHOUSE=localhost:29510 REPLICA_GROUP_ID=0 python examples/train_ddp.py &
    TORCHFT_LIGHTHOUSE=localhost:29510 REPLICA_GROUP_ID=1 python examples/train_ddp.py &

Kill any replica mid-run and restart it: it heals from a healthy peer's live
weights and training continues without a global restart.
"""

from __future__ import annotations

import argparse
import hashlib
import logging
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from torchft_tpu.tier import default_tier, make_communicator, manager_server_cls
from torchft_tpu.data import DistributedSampler, batch_indices
from torchft_tpu.ddp import ft_allreduce
from torchft_tpu.manager import Manager
from torchft_tpu.models.cnn import SimpleCNN
from torchft_tpu.optim import OptimizerWrapper

logging.basicConfig(
    level=logging.INFO, format="%(asctime)s %(name)s: %(message)s"
)
logger = logging.getLogger("train_ddp")


def synthetic_cifar(n: int = 2048, seed: int = 0):
    """Deterministic synthetic CIFAR-10-shaped dataset (no downloads in a
    zero-egress environment)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 10, size=n).astype(np.int32)
    return x, y


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=50)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument(
        "--replica-group-id",
        type=int,
        default=int(os.environ.get("REPLICA_GROUP_ID", 0)),
    )
    parser.add_argument(
        "--num-replica-groups",
        type=int,
        default=int(os.environ.get("NUM_REPLICA_GROUPS", 2)),
    )
    parser.add_argument("--min-replicas", type=int, default=1)
    parser.add_argument(
        "--comm-timeout",
        type=float,
        default=30.0,
        help="per-op userspace timeout; a wedged peer is evicted after this",
    )
    parser.add_argument(
        "--step-time",
        type=float,
        default=0.0,
        help="minimum seconds per step (paces chaos-test scenarios)",
    )
    parser.add_argument(
        "--platform",
        default=None,
        help="force a jax platform (e.g. cpu) — useful when several replica "
        "processes share one host",
    )
    args = parser.parse_args()

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from torchft_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    x, y = synthetic_cifar()
    model = SimpleCNN(num_classes=10)
    params = model.init(jax.random.PRNGKey(0))
    tx = optax.adam(args.lr)
    holder = {"params": params, "opt_state": tx.init(params)}

    tier = default_tier()  # C++ plane when native/libtpuft.so loads
    manager = Manager(
        # comm tier resolves separately (data_plane_tier): auto downgrades
        # to python under forced-hierarchical topologies, with a loud log
        comm=make_communicator(timeout_s=args.comm_timeout),
        load_state_dict=lambda s: holder.update(s),
        state_dict=lambda: dict(holder),
        min_replica_size=args.min_replicas,
        replica_id=f"train_ddp_{args.replica_group_id}",
        server_cls=manager_server_cls(tier),
        # manager RPCs (should_commit vote, checkpoint fetch) must detect a
        # wedged/dissolved peer on the same clock as the data plane: with
        # the 60 s default, a replica thawing from a freeze burned a full
        # minute in a doomed vote against a quorum that no longer existed
        # while its healthy peer trained to completion and exited
        timeout=args.comm_timeout,
    )
    opt = OptimizerWrapper(manager, tx)

    sampler = DistributedSampler(
        len(x),
        replica_rank=args.replica_group_id,
        num_replica_groups=args.num_replica_groups,
        shuffle=True,
    )

    loss_and_grad = jax.jit(jax.value_and_grad(model.loss))

    batches = list(batch_indices(sampler, args.batch_size))
    import time

    while manager.current_step() < args.steps:
        if args.step_time > 0:
            time.sleep(args.step_time)
        step = manager.current_step()
        idxs = batches[step % len(batches)]
        batch = (jnp.asarray(x[idxs]), jnp.asarray(y[idxs]))

        opt.start_step()  # quorum overlaps the forward pass
        loss, grads = loss_and_grad(holder["params"], batch)
        grads = ft_allreduce(manager, grads)
        committed = opt.step(holder, grads)
        logger.info(
            "step %d loss %.4f committed=%s participants=%d",
            step,
            float(loss),
            committed,
            manager.num_participants(),
        )

    # content hash of final params so separate replicas can be compared
    leaves = jax.tree_util.tree_leaves(holder["params"])
    digest = hashlib.sha256()
    for leaf in leaves:
        digest.update(np.ascontiguousarray(np.asarray(leaf, dtype=np.float32)))
    print(f"FINAL step={manager.current_step()} params_sha={digest.hexdigest()[:16]}")
    manager.shutdown()


if __name__ == "__main__":
    main()
