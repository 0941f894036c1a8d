"""Subprocess worker for the multi-host replica-group integration test.

One process = one "host" of a replica group.  Each group is its own
2-process ``jax.distributed`` job (CPU, 2 virtual devices per process →
a 4-device global mesh), so model/optimizer state and gradients are
genuinely **non-fully-addressable** jax Arrays — the v5p-64 reality the
reference reaches with one torchrun per replica group
(``torchft/manager_integ_test.py:484-522``).

The FT ring runs per host: rank r of every group rings with rank r of the
other groups, shipping only shard-local bytes (``ddp._host_contribution``);
heals ship ``ShardedHostArray`` bundles rank-to-rank.
"""

import argparse
import os
import pickle
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--group", type=int, required=True)
    p.add_argument("--rank", type=int, required=True)
    p.add_argument("--coord-port", type=int, required=True)
    p.add_argument("--lighthouse", required=True)
    p.add_argument("--store-port", type=int, required=True)
    p.add_argument("--num-steps", type=int, default=10)
    p.add_argument("--die-at", type=int, default=-1)
    p.add_argument("--step-time", type=float, default=0.05)
    p.add_argument("--result-file", required=True)
    # rendezvous gate: park the survivor at this step until the flag file
    # exists (its manager server keeps heartbeating + answering quorums), so
    # a respawned peer's slow jax.distributed init can't miss the whole run
    p.add_argument("--wait-flag", default="")
    p.add_argument("--wait-at", type=int, default=4)
    # second gate (e.g. park at step 0 until the whole fleet registered,
    # AND at step 4 for the respawn rendezvous)
    p.add_argument("--wait-flag2", default="")
    p.add_argument("--wait-at2", type=int, default=-1)
    args = p.parse_args()

    import logging
    import time as _t

    logging.basicConfig(
        level=logging.INFO,
        format=f"%(asctime)s g{args.group}r{args.rank} %(name)s: %(message)s",
    )
    log = logging.getLogger("multihost_worker")
    t0 = _t.monotonic()

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

    import jax

    jax.config.update("jax_platforms", "cpu")
    log.info("jax imported (+%.1fs)", _t.monotonic() - t0)
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{args.coord_port}",
        num_processes=2,
        process_id=args.rank,
    )
    log.info("jax.distributed initialized (+%.1fs)", _t.monotonic() - t0)

    import time

    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchft_tpu.communicator import TCPCommunicator
    from torchft_tpu.ddp import ft_allreduce, restore_tree_like
    from torchft_tpu.checkpointing.serialization import shard_key
    from torchft_tpu.manager import Manager

    mesh = Mesh(np.array(jax.devices()).reshape(4), ("fsdp",))
    w_sh = NamedSharding(mesh, P("fsdp"))
    b_sh = NamedSharding(mesh, P())  # replicated leaf

    # identical initial state in every group (and every life)
    full_w = np.linspace(-1.0, 1.0, 8 * 3, dtype=np.float32).reshape(8, 3)
    full_b = np.zeros(3, dtype=np.float32)
    params = {
        "w": jax.make_array_from_callback((8, 3), w_sh, lambda i: full_w[i]),
        "b": jax.make_array_from_callback((3,), b_sh, lambda i: full_b[i]),
    }
    from torchft_tpu.parallel.hsdp import sharded_opt_init

    tx = optax.sgd(0.05, momentum=0.9)
    opt_state = sharded_opt_init(tx, params)
    holder = {"params": params, "opt_state": opt_state}

    def _save():
        return dict(holder)

    def _load(state) -> None:
        holder["params"] = restore_tree_like(state["params"], holder["params"])
        holder["opt_state"] = restore_tree_like(
            state["opt_state"], holder["opt_state"]
        )

    # generous deadlines: 4 jax processes boot concurrently and the whole
    # suite may be loading the machine — a quorum RPC timing out here makes
    # the worker exit rc=1 and flakes the kill/heal assertions
    manager = Manager(
        comm=TCPCommunicator(timeout_s=30.0),
        load_state_dict=_load,
        state_dict=_save,
        min_replica_size=1,
        use_async_quorum=True,
        replica_id=f"mh_group_{args.group}",
        lighthouse_addr=args.lighthouse,
        store_addr="127.0.0.1",
        store_port=args.store_port,
        rank=args.rank,
        world_size=2,
        timeout=120.0,
        quorum_timeout=150.0,
        connect_timeout=60.0,
    )

    @jax.jit
    def make_grads(params, scale):
        # a real (deterministic) gradient so outputs inherit the params'
        # sharding: d/dp [scale * sum(p^2)] = 2*scale*p
        def loss(p):
            return scale * sum(
                jnp.sum(leaf**2) for leaf in jax.tree_util.tree_leaves(p)
            )

        return jax.grad(loss)(params)

    @jax.jit
    def update(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    while manager.current_step() < args.num_steps:
        if manager.current_step() == args.die_at:
            os._exit(9)  # whole-host kill: the harness respawns the group
        if args.wait_flag and manager.current_step() == args.wait_at:
            while not os.path.exists(args.wait_flag):
                time.sleep(0.1)
        if args.wait_flag2 and manager.current_step() == args.wait_at2:
            while not os.path.exists(args.wait_flag2):
                time.sleep(0.1)
        time.sleep(args.step_time)
        manager.start_quorum()
        scale = jnp.float32(0.05 * (args.group + 1))
        grads = make_grads(holder["params"], scale)
        assert not grads["w"].is_fully_addressable, "test must exercise multi-host"
        # MH_QUANTIZE exercises the sharded-leaf + quantized-wire combo:
        # every group applies the identical requantized stream, so the
        # cross-group equality assertions still hold bitwise
        grads = ft_allreduce(
            manager,
            grads,
            should_quantize=os.environ.get("MH_QUANTIZE", "")
            not in ("", "0"),
        )
        if manager.should_commit():
            holder["params"], holder["opt_state"] = update(
                holder["params"], holder["opt_state"], grads
            )
        if os.environ.get("MH_DEBUG"):
            w0 = np.asarray(holder["params"]["w"].addressable_shards[0].data)
            print(
                f"MHDBG g{args.group} r{args.rank} step={manager.current_step()} "
                f"qid={manager._quorum_id} np={manager.num_participants()} "
                f"part={manager.is_participating()} comm_ws={manager._comm.size()} "
                f"err={manager.errored() is not None} w0={w0.reshape(-1)[:1]}",
                file=sys.stderr, flush=True,
            )

    # dump THIS host's view: unique addressable shards per leaf
    def host_view(tree):
        out = {}
        leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
        for path, leaf in leaves:
            name = jax.tree_util.keystr(path)
            shards = {}
            for s in leaf.addressable_shards:
                shards[shard_key(s.index, leaf.shape)] = np.asarray(s.data)
            out[name] = shards
        return out

    with open(args.result_file, "wb") as f:
        pickle.dump(
            {"params": host_view(holder["params"]), "step": manager.current_step()},
            f,
        )
    manager.shutdown()
    sys.exit(0)


if __name__ == "__main__":
    main()
