"""Checkpoint transfer micro-benchmarks.

Analogs of the reference harnesses
(``torchft/checkpointing/http_transport_bench.py`` — 12 GB default workload —
and ``pg_transport_bench.py``): measure live-heal transfer throughput for the
HTTP transport and the communicator transport.

    python benchmarks/checkpoint_bench.py --gb 1 --transport http
    python benchmarks/checkpoint_bench.py --gb 1 --transport comm
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# this bench stages CPU jax arrays by design — pin the cpu platform at
# import time, strictly BEFORE any backend init (post-init the update
# silently no-ops and jax.local_devices would take the host's chip)
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def _state(total_bytes: int, chunk_mb: int = 64, leaf: str = "jax") -> dict:
    """Synthetic state dict.  ``leaf="jax"`` builds immutable jax CPU arrays
    (the real heal case: staging holds references, zero copies); "numpy"
    leaves are mutable so staging snapshots them (the LocalSGD-host-params
    case, +1x state RSS on the sender)."""
    n_chunks = max(1, total_bytes // (chunk_mb << 20))
    per = total_bytes // n_chunks // 4
    rng = np.random.default_rng(0)
    out = {}
    put = None
    if leaf == "jax":
        import jax

        cpu = jax.local_devices(backend="cpu")[0]
        put = lambda a: jax.device_put(a, cpu)  # noqa: E731
    for i in range(n_chunks):
        arr = rng.normal(size=per).astype(np.float32)
        out[f"layer_{i}"] = put(arr) if put else arr
    return out


def bench_http(total_bytes: int, num_chunks: int, leaf: str) -> float:
    from torchft_tpu.checkpointing.http_transport import HTTPTransport

    sender = HTTPTransport(timeout=300.0, num_chunks=num_chunks)
    receiver = HTTPTransport(timeout=300.0, num_chunks=num_chunks)
    state = _state(total_bytes, leaf=leaf)
    try:
        start = time.perf_counter()
        sender.send_checkpoint([1], step=1, state_dict=state, timeout=300.0)
        received = receiver.recv_checkpoint(
            src_rank=0, metadata=sender.metadata(), step=1, timeout=300.0
        )
        elapsed = time.perf_counter() - start
        assert received.keys() == state.keys()
        return elapsed
    finally:
        sender.shutdown()
        receiver.shutdown()


def bench_comm(total_bytes: int, backend: str, leaf: str) -> float:
    from torchft_tpu.checkpointing.comm_transport import CommTransport
    from torchft_tpu.store import StoreServer

    if backend == "cpp":
        from torchft_tpu.native import CppCommunicator as Comm
    else:
        from torchft_tpu.communicator import TCPCommunicator as Comm

    store = StoreServer("127.0.0.1:0")
    state = _state(total_bytes, leaf=leaf)
    times = {}

    def _run(rank: int) -> None:
        comm = Comm(timeout_s=300.0)
        comm.configure(
            f"127.0.0.1:{store.port}/bench",
            replica_id=f"r{rank}",
            rank=rank,
            world_size=2,
        )
        transport = CommTransport(comm, timeout=300.0)
        try:
            start = time.perf_counter()
            if rank == 0:
                transport.send_checkpoint([1], step=1, state_dict=state, timeout=300.0)
            else:
                received = transport.recv_checkpoint(
                    src_rank=0, metadata="<comm>", step=1, timeout=300.0
                )
                assert received.keys() == state.keys()
            times[rank] = time.perf_counter() - start
        finally:
            comm.shutdown()

    try:
        with ThreadPoolExecutor(max_workers=2) as pool:
            list(pool.map(_run, range(2)))
        return max(times.values())
    finally:
        store.shutdown()


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--gb", type=float, default=1.0)
    parser.add_argument(
        "--transport", choices=["http", "comm", "comm-cpp"], default="http"
    )
    parser.add_argument("--num-chunks", type=int, default=8)
    parser.add_argument("--leaf", choices=["jax", "numpy"], default="jax")
    args = parser.parse_args()
    total = int(args.gb * (1 << 30))

    import resource

    rss_before = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    if args.transport == "http":
        elapsed = bench_http(total, args.num_chunks, args.leaf)
    elif args.transport == "comm":
        elapsed = bench_comm(total, "tcp", args.leaf)
    else:
        elapsed = bench_comm(total, "cpp", args.leaf)
    rss_after = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    # both endpoints run in this process: the delta is sender staging +
    # receiver buffers beyond the state itself (streaming sender ≈ receiver
    # arrays + one leaf; the round-1 blob-staging sender added ~2x state)
    print(
        f"{args.transport}: {args.gb:.1f} GB in {elapsed:.2f}s "
        f"= {total / elapsed / 1e9:.2f} GB/s; "
        f"peak RSS growth during transfer: "
        f"{(rss_after - rss_before) / (1 << 20):.2f} GB"
    )


if __name__ == "__main__":
    main()
