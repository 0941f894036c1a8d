"""Per-step protocol overhead: quorum + commit RPC latency at ws=1.

The per-step fault-tolerance protocol is two RPC exchanges on warm
connections (the reference's fast-quorum path is one round trip,
``src/lighthouse.rs:204-215``):

- ``start_quorum`` → manager server barrier → lighthouse fast quorum
- ``should_commit`` → manager server AND-barrier

This measures the full stack (Manager → ManagerServer → Lighthouse, all
localhost) with no model attached, i.e. the pure protocol tax a train
step pays.  Target: < 10 ms/step.

Usage: python benchmarks/proto_bench.py [--steps N] [--sync-quorum]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=200)
    parser.add_argument(
        "--sync-quorum",
        action="store_true",
        help="use_async_quorum=False (quorum RPC fully on the step path)",
    )
    args = parser.parse_args()

    from torchft_tpu.communicator import TCPCommunicator
    from torchft_tpu.lighthouse import LighthouseServer
    from torchft_tpu.manager import Manager

    lighthouse = LighthouseServer(
        bind="127.0.0.1:0",
        min_replicas=1,
        join_timeout_ms=50,
        quorum_tick_ms=20,
    )
    holder: dict = {}
    manager = Manager(
        comm=TCPCommunicator(timeout_s=30.0),
        load_state_dict=holder.update,
        state_dict=lambda: dict(holder),
        min_replica_size=1,
        replica_id="proto_bench_0",
        lighthouse_addr=lighthouse.local_address(),
        use_async_quorum=not args.sync_quorum,
    )

    for _ in range(10):  # warm connections + first-quorum reconfigure
        manager.start_quorum()
        manager.should_commit()

    start = time.perf_counter()
    for _ in range(args.steps):
        manager.start_quorum()
        manager.should_commit()
    per_step = (time.perf_counter() - start) / args.steps

    mode = "sync" if args.sync_quorum else "async"
    print(
        f"protocol overhead ({mode} quorum): {per_step * 1e3:.2f} ms/step "
        f"over {args.steps} steps (target < 10 ms)"
    )

    manager.shutdown()
    lighthouse.shutdown()


if __name__ == "__main__":
    main()
