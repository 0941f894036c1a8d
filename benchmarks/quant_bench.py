"""Quantized-allreduce throughput: pipelined windows vs single-shot.

Two processes over loopback TCP (the DCN tier), each SUM-allreducing the
same float32 buffer through the int8 wire format.  Compares:

- ``window=none``: one window (round-1 behavior — quantize, one alltoall,
  reduce, one allgather, all serialized)
- ``window=4``:    4 MB pipeline windows (wire ops overlap the reduce)

plus the reduce backend (host numpy vs fused Pallas when a TPU is present;
set TORCHFT_QUANT_DEVICE_REDUCE=1/0 to force).

Usage: python benchmarks/quant_bench.py [--mb 64] [--iters 3]
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _rank_main(rank: int, world: int, port: int, mb: int, iters: int, window_mb: str, out_q) -> None:
    os.environ["TORCHFT_QUANT_WINDOW_MB"] = window_mb
    # host reduce unless explicitly testing the device path: this bench is
    # one PROCESS per rank, and a chip belongs to one process, so a forced
    # device reduce can start on at most one rank of a TPU host
    os.environ.setdefault("TORCHFT_QUANT_DEVICE_REDUCE", "0")
    from torchft_tpu.collectives import allreduce_quantized
    from torchft_tpu.communicator import TCPCommunicator

    comm = TCPCommunicator(timeout_s=120.0)
    comm.configure(
        f"127.0.0.1:{port}/qbench_{window_mb}",
        replica_id=f"r{rank}",
        rank=rank,
        world_size=world,
    )
    n = mb * (1 << 20) // 4
    rng = np.random.default_rng(rank)
    buf = rng.normal(size=n).astype(np.float32)

    allreduce_quantized(comm, buf.copy()).wait(timeout=120.0)  # warm
    start = time.perf_counter()
    for _ in range(iters):
        allreduce_quantized(comm, buf.copy()).wait(timeout=120.0)
    dt = (time.perf_counter() - start) / iters
    comm.shutdown()
    if rank == 0:
        # algorithmic bandwidth: input bytes / wall time
        out_q.put({"window_mb": window_mb, "sec": dt, "gbps": buf.nbytes / dt / 1e9})


def run(mb: int, iters: int, window_mb: str) -> dict:
    from torchft_tpu.store import StoreServer

    store = StoreServer("127.0.0.1:0")
    ctx = mp.get_context("spawn")
    out_q = ctx.Queue()
    procs = [
        ctx.Process(
            target=_rank_main,
            args=(r, 2, store.port, mb, iters, window_mb, out_q),
        )
        for r in range(2)
    ]
    for p in procs:
        p.start()
    result = out_q.get(timeout=300)
    for p in procs:
        p.join(timeout=60)
    store.shutdown()
    return result


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--mb", type=int, default=64)
    parser.add_argument("--iters", type=int, default=3)
    args = parser.parse_args()

    single = run(args.mb, args.iters, "100000")  # one giant window
    piped = run(args.mb, args.iters, "4")
    print(
        json.dumps(
            {
                "buffer_mb": args.mb,
                "single_window": single,
                "pipelined_4mb": piped,
                "speedup": round(single["sec"] / piped["sec"], 3),
            }
        )
    )


if __name__ == "__main__":
    main()
