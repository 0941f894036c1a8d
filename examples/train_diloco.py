"""Streaming DiLoCo training example (BASELINE config 4, reference
``train_diloco.py``).

Each replica group ("island") trains locally with an inner optimizer and
synchronizes averaged pseudogradients through an outer optimizer every
``--sync-every`` steps, with the model split into fragments whose syncs are
staggered and overlapped (Streaming DiLoCo).  Communication cost over DCN is
O(model/sync_every), which is what makes cross-datacenter training viable.

    python -m torchft_tpu.lighthouse --min_replicas 2 --bind 0.0.0.0:29520 &
    TORCHFT_LIGHTHOUSE=localhost:29520 REPLICA_GROUP_ID=0 python examples/train_diloco.py &
    TORCHFT_LIGHTHOUSE=localhost:29520 REPLICA_GROUP_ID=1 python examples/train_diloco.py
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
from torchft_tpu.local_sgd import DiLoCo
from torchft_tpu.manager import Manager
from torchft_tpu.optim import OptimizerWrapper  # noqa: F401 (inner loop is plain optax)

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s: %(message)s")
logger = logging.getLogger("train_diloco")


def _mlp_init(key, sizes):
    params = {}
    for i, (fan_in, fan_out) in enumerate(zip(sizes[:-1], sizes[1:])):
        key, sub = jax.random.split(key)
        params[f"layer_{i}"] = {
            "w": jax.random.normal(sub, (fan_in, fan_out)) * np.sqrt(2.0 / fan_in),
            "b": jnp.zeros(fan_out),
        }
    return params


def _mlp_apply(params, x):
    n = len(params)
    for i in range(n):
        p = params[f"layer_{i}"]
        x = x @ p["w"] + p["b"]
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--total-syncs", type=int, default=10)
    parser.add_argument("--sync-every", type=int, default=8)
    parser.add_argument("--num-fragments", type=int, default=2)
    parser.add_argument("--fragment-sync-delay", type=int, default=1)
    parser.add_argument("--batch-size", type=int, default=64)
    parser.add_argument(
        "--replica-group-id",
        type=int,
        default=int(os.environ.get("REPLICA_GROUP_ID", 0)),
    )
    parser.add_argument("--min-replicas", type=int, default=2)
    parser.add_argument(
        "--quantize",
        action="store_true",
        help="1-byte pseudogradient sync (int8 default, fp8 via "
        "TORCHFT_QUANT_KIND) — the reference's DiLoCo wire",
    )
    parser.add_argument("--platform", default=None)
    args = parser.parse_args()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from torchft_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    rng = np.random.default_rng(0)
    x = rng.normal(size=(4096, 64)).astype(np.float32)
    w_true = rng.normal(size=(64, 8)).astype(np.float32)
    y = x @ w_true + 0.01 * rng.normal(size=(4096, 8)).astype(np.float32)

    params = _mlp_init(jax.random.PRNGKey(0), [64, 128, 128, 8])
    inner_tx = optax.adamw(3e-4)
    holder = {"params": params}
    inner_state = inner_tx.init(params)

    tier = default_tier()  # C++ plane when native/libtpuft.so loads
    manager = Manager(
        comm=make_communicator(timeout_s=60.0),  # data-plane tier dispatch
        load_state_dict=lambda s: holder.update(s),
        state_dict=lambda: dict(holder),
        min_replica_size=args.min_replicas,
        use_async_quorum=False,  # DiLoCo requires a synchronous quorum
        replica_id=f"train_diloco_{args.replica_group_id}",
        quorum_timeout=120.0,
        server_cls=manager_server_cls(tier),
    )
    diloco = DiLoCo(
        manager,
        holder,
        outer_tx=optax.sgd(0.7, momentum=0.9, nesterov=True),
        sync_every=args.sync_every,
        num_fragments=args.num_fragments,
        fragment_sync_delay=args.fragment_sync_delay,
        should_quantize=args.quantize,
    )

    def loss_fn(p, batch):
        bx, by = batch
        pred = _mlp_apply(p, bx)
        return jnp.mean((pred - by) ** 2)

    loss_and_grad = jax.jit(jax.value_and_grad(loss_fn))

    syncs = 0
    step = 0
    with diloco:
        while syncs < args.total_syncs:
            idx = rng.integers(0, len(x), size=args.batch_size)
            batch = (jnp.asarray(x[idx]), jnp.asarray(y[idx]))
            loss, grads = loss_and_grad(holder["params"], batch)
            updates, inner_state = inner_tx.update(
                grads, inner_state, holder["params"]
            )
            holder["params"] = optax.apply_updates(holder["params"], updates)
            step += 1
            result = diloco.step()
            if result is not None:
                syncs += 1
                logger.info(
                    "sync %d at inner step %d committed=%s loss %.5f",
                    syncs,
                    step,
                    result,
                    float(loss),
                )

    leaves = jax.tree_util.tree_leaves(holder["params"])
    digest = hashlib.sha256()
    for leaf in leaves:
        digest.update(np.ascontiguousarray(np.asarray(leaf, dtype=np.float32)))
    print(f"FINAL syncs={syncs} params_sha={digest.hexdigest()[:16]}")
    manager.shutdown()


if __name__ == "__main__":
    main()
