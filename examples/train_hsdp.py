"""Fault-tolerant HSDP training example (BASELINE config 3).

FSDP/TP over the replica group's mesh (ICI, inside compiled XLA programs) ×
fault-tolerant DDP over DCN (host-side, elastic membership).  This is the
shape of the north-star workload: Llama over a sharded mesh per replica
group, replica groups joining/leaving without recompilation.

    python -m torchft_tpu.launcher --replicas 2 -- \
        python examples/train_hsdp.py --steps 50 --platform cpu

The mesh defaults to the whole host (``dp=1, fsdp=<devices>, tp=1``); each
replica trains on one fixed synthetic batch of its own, so the loss falls
and a run can be checked.  A process that has touched JAX owns its chips:
on a TPU host each replica group (one process) needs chips of its own.  On
CPU set XLA_FLAGS=--xla_force_host_platform_device_count=8 to give each
process a virtual 8-device mesh.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import logging
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import optax

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s: %(message)s")
logger = logging.getLogger("train_hsdp")

# a replica that loses a peer mid-step votes the step down once or twice and
# carries on; an error that repeats this often is this replica's own (a
# kernel that will not compile, an OOM) and would otherwise spin for ever
# inside the Manager's error funnel
MAX_CONSECUTIVE_UNCOMMITTED = 3


def main() -> None:
    from torchft_tpu.models.llama import CONFIGS

    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=50)
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--seq", type=int, default=128)
    parser.add_argument("--model", choices=sorted(CONFIGS), default="llama_debug")
    parser.add_argument(
        "--n-layers",
        type=int,
        default=None,
        help="cut the model's depth (widths stay as published)",
    )
    parser.add_argument("--fsdp", type=int, default=None)
    parser.add_argument("--tp", type=int, default=None)
    parser.add_argument("--dp", type=int, default=None)
    parser.add_argument(
        "--replica-group-id",
        type=int,
        default=int(os.environ.get("REPLICA_GROUP_ID", 0)),
    )
    parser.add_argument("--min-replicas", type=int, default=1)
    parser.add_argument(
        "--comm-timeout",
        type=float,
        default=60.0,
        help="per-op and per-RPC timeout; at real widths a peer's cold "
        "compile sits inside it",
    )
    parser.add_argument(
        "--quantize-outer",
        action="store_true",
        help="1-byte wire for the replica-dim gradient ring (int8 "
        "default, fp8 via TORCHFT_QUANT_KIND)",
    )
    parser.add_argument("--platform", default=None)
    args = parser.parse_args()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from torchft_tpu.manager import Manager
    from torchft_tpu.models.llama import Llama
    from torchft_tpu.parallel.degraded import (
        plan_surviving,
        startup_surviving_devices,
    )
    from torchft_tpu.parallel.hsdp import HSDPTrainer, fsdp_shardings
    from torchft_tpu.parallel.mesh import make_mesh
    from torchft_tpu.tier import (
        data_plane_tier,
        default_tier,
        make_communicator,
        manager_server_cls,
    )
    from torchft_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    cache_hits = []
    jax.monitoring.register_event_listener(
        lambda event, **_kw: event == "/jax/compilation_cache/cache_hits"
        and cache_hits.append(event)
    )

    all_devices = jax.devices()
    # degraded-mode / chaos: TORCHFT_CHAOS_DEVICE_LOSS hides N devices so
    # this replica comes up wounded — plan the surviving layout and
    # advertise the capacity fraction instead of dying
    devices = startup_surviving_devices(all_devices)
    if args.dp is None and args.fsdp is None and args.tp is None:
        axes = {"dp": 1, "fsdp": len(all_devices), "tp": 1}  # the whole host
    else:
        axes = {"dp": args.dp or 1, "fsdp": args.fsdp or 1, "tp": args.tp or 1}
    wanted = axes["dp"] * axes["fsdp"] * axes["tp"]
    degraded_plan = None
    if len(devices) < wanted:
        degraded_plan = plan_surviving(
            len(devices), original_devices=wanted
        )
        logger.warning(
            "coming up degraded: %s (capacity %.3f)",
            degraded_plan.mesh_axes,
            degraded_plan.capacity,
        )
        mesh = make_mesh(devices=devices, **degraded_plan.mesh_axes)
    else:
        mesh = make_mesh(devices=devices, **axes)

    config = CONFIGS[args.model]()
    published_layers = config.n_layers
    if args.n_layers is not None:
        config = dataclasses.replace(config, n_layers=args.n_layers)
    model = Llama(config)

    tier = default_tier()  # C++ plane when native/libtpuft.so loads
    logger.info(
        "platform=%s device_kind=%s devices=%d mesh=%s model=%s n_layers=%d "
        "(published %d) params=%.1fM tier=%s compile_cache=%s",
        all_devices[0].platform,
        all_devices[0].device_kind,
        len(all_devices),
        dict(mesh.shape),
        args.model,
        config.n_layers,
        published_layers,
        model.num_params() / 1e6,
        tier,
        cache_dir,
    )
    manager = Manager(
        comm=make_communicator(timeout_s=args.comm_timeout),  # data-plane tier dispatch
        load_state_dict=None,  # HSDPTrainer registers its own entry
        state_dict=None,
        min_replica_size=args.min_replicas,
        timeout=args.comm_timeout,
        quorum_timeout=args.comm_timeout,
        replica_id=f"train_hsdp_{args.replica_group_id}",
        server_cls=manager_server_cls(tier),
    )
    if degraded_plan is not None:
        try:
            manager.complete_relower(degraded_plan.capacity)
        except RuntimeError as e:
            # C++ sidecar: no capacity plumbing — run the reduced mesh but
            # register full-width (docs/operations.md §16 fallback matrix)
            logger.warning("cannot advertise degraded capacity: %s", e)
    trainer = HSDPTrainer(
        model,
        optax.adamw(1e-3),
        mesh,
        manager,
        key=jax.random.PRNGKey(0),
        quantize_outer=args.quantize_outer,
    )
    batch_sh = fsdp_shardings(model, mesh)[1]

    # one fixed batch per replica (distinct across replicas, so the
    # replica-dim average does real work)
    rng = np.random.default_rng(args.replica_group_id)
    tokens = rng.integers(
        0, config.vocab_size, size=(args.batch_size, args.seq)
    ).astype(np.int32)
    batch = tuple(
        jax.device_put(jnp.asarray(b), sh)
        for b, sh in zip((tokens, np.roll(tokens, -1, axis=1)), batch_sh)
    )

    losses = []
    attempted = uncommitted_run = 0
    t_start = t_steady = time.perf_counter()
    while manager.current_step() < args.steps:
        loss, committed = trainer.train_step(batch)
        attempted += 1
        logger.info(
            "step %d loss %.4f committed=%s participants=%d",
            manager.current_step() - (1 if committed else 0),
            loss,
            committed,
            manager.num_participants(),
        )
        if attempted == 1:
            # the first step carries the compiles
            jax.block_until_ready(trainer.holder["params"])
            t_steady = time.perf_counter()
        if committed:
            losses.append(loss)
            uncommitted_run = 0
            continue
        uncommitted_run += 1
        if uncommitted_run >= MAX_CONSECUTIVE_UNCOMMITTED:
            sys.exit(
                f"{uncommitted_run} steps in a row did not commit; "
                f"last error: {manager.errored()}"
            )
    jax.block_until_ready(trainer.holder["params"])
    t_end = time.perf_counter()

    memory = all_devices[0].memory_stats() or {}
    print(
        "REPORT "
        + json.dumps(
            {
                "platform": all_devices[0].platform,
                "device_kind": all_devices[0].device_kind,
                "devices": len(all_devices),
                "mesh": dict(mesh.shape),
                "model": args.model,
                "n_layers": config.n_layers,
                "published_n_layers": published_layers,
                "vocab_size": config.vocab_size,
                "seq": args.seq,
                "batch_size": args.batch_size,
                "tier": tier,
                "data_plane_tier": data_plane_tier(),
                "attention": model.attention_path,
                "committed": len(losses),
                "attempted": attempted,
                "losses": [round(l, 4) for l in losses],
                "first_step_s": round(t_steady - t_start, 3),
                "step_s": round(
                    (t_end - t_steady) / max(1, attempted - 1), 3
                ),
                "peak_bytes_in_use": memory.get("peak_bytes_in_use"),
                "compile_cache_hits": len(cache_hits),
            }
        )
    )

    digest = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(trainer.holder["params"]):
        digest.update(
            np.ascontiguousarray(np.asarray(leaf, dtype=np.float32))
        )
    print(f"FINAL step={manager.current_step()} params_sha={digest.hexdigest()[:16]}")
    manager.shutdown()


if __name__ == "__main__":
    main()
