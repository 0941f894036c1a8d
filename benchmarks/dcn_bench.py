"""Emulated-DCN data-plane validation (round-4 verdict item 6).

Loopback hides the regime the replica dimension is actually designed for:
cross-datacenter / cross-pod links at ~1-10 Gb/s and 2-10 ms RTT (the
DiLoCo deployment story, ``/root/reference/torchft/local_sgd.py:569-634``).
This harness re-runs the three data-plane patterns that matter for fault
tolerance under the TCP tier's netem-style sender pacer
(``communicator._NetEmu``, env ``TORCHFT_NET_GBPS``/``TORCHFT_NET_RTT_MS``):

- ``f32 ring``:   plain SUM-allreduce of a gradient-sized payload
- ``quant ring``: the int8 windowed pipelined allreduce (4x less wire)
- ``heal``:       a CommTransport checkpoint send/recv (victim rejoin path)
- ``striped heal``: the same heal fetched as disjoint chunk ranges from 1
  vs 2 sources in a 3-replica group (``recv_checkpoint_striped``) — heal
  bandwidth must scale with source count because each sender paces its own
  emulated link (the multi-peer striped-healing claim, PHOENIX-style)

at a set of profiles including unshaped loopback as the control.  The
quantized ring must BEAT the f32 ring at the constrained profiles — that is
the claim that justifies its existence — while on unshaped loopback it may
lose (host quantize cycles the fat link never repays; exactly why the
DiLoCo quantized wire is opt-in and set from this measurement).

Throughput keys are suffixed ``_GBps`` (gigaBYTES/s) — deliberately NOT
``gbps``, so they cannot be misread 8x against the profiles' Gbit/s link
rates (the ``gbps`` profile field).

Usage: python benchmarks/dcn_bench.py [--mb 30] [--iters 3] [--md]
       [--no-striped]
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

# (name, link Gbit/s, RTT ms); 0/0 = unshaped loopback control
PROFILES = [
    ("loopback", 0.0, 0.0),
    ("dcn_10g_2ms", 10.0, 2.0),
    ("wan_1g_10ms", 1.0, 10.0),
]


def _rank_main(rank, world, port, mb, iters, gbps, rtt_ms, out_q):
    os.environ["TORCHFT_NET_GBPS"] = str(gbps)
    os.environ["TORCHFT_NET_RTT_MS"] = str(rtt_ms)
    os.environ.setdefault("TORCHFT_QUANT_DEVICE_REDUCE", "0")
    from torchft_tpu.checkpointing.comm_transport import CommTransport
    from torchft_tpu.collectives import allreduce_quantized
    from torchft_tpu.communicator import TCPCommunicator

    comm = TCPCommunicator(timeout_s=300.0)
    comm.configure(
        f"127.0.0.1:{port}/dcn_{gbps}_{rtt_ms}",
        replica_id=f"r{rank}",
        rank=rank,
        world_size=world,
    )
    n = mb * (1 << 20) // 4
    rng = np.random.default_rng(rank)
    buf = rng.normal(size=n).astype(np.float32)
    results = {}

    # f32 ring
    comm.allreduce(buf.copy()).wait(timeout=300.0)  # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        comm.allreduce(buf.copy()).wait(timeout=300.0)
    results["f32_ring_s"] = (time.perf_counter() - t0) / iters

    # quantized ring
    allreduce_quantized(comm, buf.copy()).wait(timeout=300.0)  # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        allreduce_quantized(comm, buf.copy()).wait(timeout=300.0)
    results["quant_ring_s"] = (time.perf_counter() - t0) / iters

    # heal transfer: rank 0 = survivor sending live weights, rank 1 = victim
    transport = CommTransport(comm, timeout=300.0)
    state = {"params": buf.copy(), "opt": rng.normal(size=n // 2).astype(np.float32)}
    heal_bytes = sum(a.nbytes for a in state.values())
    t0 = time.perf_counter()
    for i in range(max(1, iters // 2)):
        if rank == 0:
            transport.send_checkpoint([1], step=i, state_dict=state, timeout=300.0)
        else:
            got = transport.recv_checkpoint(0, "", step=i, timeout=300.0)
            assert got["params"].nbytes == state["params"].nbytes
    results["heal_s"] = (time.perf_counter() - t0) / max(1, iters // 2)
    results["heal_GBps"] = heal_bytes / results["heal_s"] / 1e9
    comm.barrier().wait(timeout=60.0)

    # lane sweep: the SAME f32 ring at explicit lane counts (fresh mesh per
    # count — lanes are fixed per epoch at configure).  Multi-lane results
    # must be bit-identical to single-lane: striping moves bytes, not math.
    ref = None
    for lanes in (1, 2, 4):
        os.environ["TORCHFT_RING_LANES"] = str(lanes)
        comm.configure(
            f"127.0.0.1:{port}/dcn_{gbps}_{rtt_ms}_L{lanes}",
            replica_id=f"r{rank}",
            rank=rank,
            world_size=world,
        )
        out = np.asarray(comm.allreduce(buf.copy()).wait(timeout=300.0))  # warm
        if ref is None:
            ref = out
        else:
            assert np.array_equal(ref, out), (
                f"{lanes}-lane ring diverged from 1-lane"
            )
        t0 = time.perf_counter()
        for _ in range(iters):
            comm.allreduce(buf.copy()).wait(timeout=300.0)
        results[f"allreduce_{lanes}lane_s"] = (time.perf_counter() - t0) / iters

    # flaky-link row: the SAME 4-lane ring at 1% injected sub-frame loss
    # (lossy-link retransmit emulation) + rare resets recovered in-epoch by
    # the lane retry machinery.  The acceptance bar: >= ~70% of clean-link
    # throughput, with zero epoch poisons (a poison would fail the op).
    os.environ["TORCHFT_RING_LANES"] = "4"
    comm.arm_faults("loss:0.01,reset:0.002")
    comm.configure(
        f"127.0.0.1:{port}/dcn_{gbps}_{rtt_ms}_flaky",
        replica_id=f"r{rank}",
        rank=rank,
        world_size=world,
    )
    out = np.asarray(comm.allreduce(buf.copy()).wait(timeout=300.0))  # warm
    assert ref is None or np.array_equal(ref, out), (
        "flaky-link ring diverged (recovery must be bit-identical)"
    )
    t0 = time.perf_counter()
    for _ in range(iters):
        comm.allreduce(buf.copy()).wait(timeout=300.0)
    results["flaky_allreduce_s"] = (time.perf_counter() - t0) / iters
    stats = comm.lane_stats()
    results["flaky_lane_reconnects"] = float(stats.get("lane_reconnects", 0))
    results["flaky_faults_injected"] = float(stats.get("faults_injected", 0))
    comm.arm_faults(None)
    os.environ.pop("TORCHFT_RING_LANES", None)

    comm.barrier().wait(timeout=60.0)
    comm.shutdown()
    if rank == 0:
        out_q.put(results)


def _striped_rank_main(rank, world, port, mb, iters, gbps, rtt_ms, out_q):
    """3-replica striped-heal measurement: ranks 0..world-2 are up-to-date
    sources, the last rank is the healer.  Runs the SAME transfer with 1
    source (exactly the legacy single-peer path) and with all sources
    striped, so the speedup column isolates striping from topology."""
    os.environ["TORCHFT_NET_GBPS"] = str(gbps)
    os.environ["TORCHFT_NET_RTT_MS"] = str(rtt_ms)
    from torchft_tpu.checkpointing.comm_transport import CommTransport
    from torchft_tpu.communicator import TCPCommunicator

    comm = TCPCommunicator(timeout_s=300.0)
    comm.configure(
        f"127.0.0.1:{port}/dcn_striped_{gbps}_{rtt_ms}",
        replica_id=f"r{rank}",
        rank=rank,
        world_size=world,
    )
    n = mb * (1 << 20) // 4
    # every source must hold the byte-identical checkpoint (same step, same
    # weights) — that is the striping precondition, so seed independent of
    # rank
    rng = np.random.default_rng(42)
    state = {
        "params": rng.normal(size=n).astype(np.float32),
        "opt": rng.normal(size=n // 2).astype(np.float32),
    }
    heal_bytes = sum(a.nbytes for a in state.values())
    healer = world - 1
    transport = CommTransport(comm, timeout=300.0)
    heal_iters = max(1, iters // 2)
    results = {}

    for num_sources in (1, world - 1):
        comm.barrier().wait(timeout=300.0)
        t0 = time.perf_counter()
        for i in range(heal_iters):
            step = num_sources * 1000 + i  # disjoint tag space per config
            if rank < num_sources:
                transport.send_checkpoint_striped(
                    [healer],
                    step=step,
                    state_dict=state,
                    timeout=300.0,
                    source_index=rank,
                    num_sources=num_sources,
                )
            elif rank == healer:
                got = transport.recv_checkpoint_striped(
                    [(r, "<comm>") for r in range(num_sources)],
                    step=step,
                    timeout=300.0,
                )
                assert got["params"].nbytes == state["params"].nbytes
        comm.barrier().wait(timeout=300.0)
        if rank == healer:
            dt = (time.perf_counter() - t0) / heal_iters
            key = "1src" if num_sources == 1 else f"{num_sources}src"
            results[f"heal_striped_{key}_s"] = dt
            results[f"heal_striped_{key}_GBps"] = heal_bytes / dt / 1e9

    comm.barrier().wait(timeout=60.0)
    comm.shutdown()
    if rank == healer:
        out_q.put(results)


def _diloco_rank_main(rank, world, port, mb, iters, gbps, rtt_ms, out_q):
    """One DiLoCo outer sync per iteration, replicated vs sharded, f32 and
    int8 wires: the replicated leg allreduces the full pseudo-gradient and
    runs the full outer update on every rank (the pre-shard path's shape);
    the sharded leg runs the chunk-pipelined reduce_scatter → 1/world outer
    update → allgather(delta).  Both legs produce params from the same
    seeded pseudo-gradients, asserted allclose in-bench — the speedup
    column can never ride a silent numeric divergence."""
    os.environ["TORCHFT_NET_GBPS"] = str(gbps)
    os.environ["TORCHFT_NET_RTT_MS"] = str(rtt_ms)
    os.environ.setdefault("TORCHFT_QUANT_DEVICE_REDUCE", "0")
    import jax
    import optax

    from torchft_tpu.collectives import (
        allreduce_quantized,
        outer_shard_layout,
        outer_sharded_sync,
    )
    from torchft_tpu.communicator import ReduceOp, TCPCommunicator

    comm = TCPCommunicator(timeout_s=300.0)
    comm.configure(
        f"127.0.0.1:{port}/diloco_{gbps}_{rtt_ms}",
        replica_id=f"r{rank}",
        rank=rank,
        world_size=world,
    )
    n = mb * (1 << 20) // 4
    tx = optax.sgd(0.7, momentum=0.9, nesterov=True)
    psg = np.random.default_rng(100 + rank).normal(size=n).astype(np.float32)
    backup = np.ones(n, dtype=np.float32)
    results = {}
    params = {}

    def _slice_state(state, per, lo, hi):
        return jax.tree_util.tree_map(
            lambda l: l[lo:hi] if getattr(l, "shape", None) == (per,) else l,
            state,
        )

    # long-lived outer state, as the real fragment holds it across syncs
    # (the replicated path replicates the FULL state; the sharded path
    # holds 1/world of it — the ZeRO-1 memory claim, visible right here)
    repl_state = jax.tree_util.tree_map(np.asarray, tx.init(backup))
    _padded_f, per_f, _u = outer_shard_layout(n, world, False)
    _padded_q, per_q, _u = outer_shard_layout(n, world, True)
    shard_state = {
        False: jax.tree_util.tree_map(
            np.asarray, tx.init(np.zeros(per_f, dtype=np.float32))
        ),
        True: jax.tree_util.tree_map(
            np.asarray, tx.init(np.zeros(per_q, dtype=np.float32))
        ),
    }
    backup_pad = np.zeros(max(_padded_f, _padded_q), dtype=np.float32)
    backup_pad[:n] = backup

    def _replicated(quant: bool) -> np.ndarray:
        if quant:
            avg = allreduce_quantized(comm, psg.copy()).wait(timeout=300.0)
        else:
            avg = comm.allreduce(psg.copy(), ReduceOp.SUM).wait(timeout=300.0)
        avg = np.asarray(avg, dtype=np.float32) / world
        updates, _ = tx.update(avg, repl_state, backup)
        return backup + np.asarray(updates, dtype=np.float32)

    def _sharded(quant: bool) -> np.ndarray:
        per = per_q if quant else per_f
        state = shard_state[quant]
        base = comm.rank() * per

        def _cb(lo, hi, avg):
            updates, _ = tx.update(
                avg, _slice_state(state, per, lo - base, hi - base),
                backup_pad[lo:hi],
            )
            return np.asarray(updates, dtype=np.float32)

        delta = outer_sharded_sync(
            comm, psg, _cb, num_participants=world, should_quantize=quant
        )
        return backup + delta

    for quant, wire in ((False, "f32"), (True, "quant")):
        for label, fn in (("replicated", _replicated), ("sharded", _sharded)):
            params[f"{label}_{wire}"] = fn(quant)  # warm
            comm.barrier().wait(timeout=300.0)
            # median-of-iters: one paused scheduler tick on a shared CI box
            # would otherwise swing the mean by 30%+
            dts = []
            for _ in range(max(iters, 5)):
                t0 = time.perf_counter()
                fn(quant)
                dts.append(time.perf_counter() - t0)
            comm.barrier().wait(timeout=300.0)
            results[f"diloco_{label}_{wire}_s"] = sorted(dts)[len(dts) // 2]
        # in-bench numeric gate: the sharded outer step must land on the
        # replicated result.  f32 differs only by reduction order; the two
        # legs quantize at DIFFERENT points (replicated requantizes the
        # reduced pseudo-grad, sharded quantizes the delta), so the
        # quantized bound is a few int8 row grids of the ~N(0,1) payload —
        # far below any real divergence, which would be O(outer lr) ≈ 0.4
        tol = 0.03 if quant else 1e-4
        assert np.allclose(
            params[f"replicated_{wire}"], params[f"sharded_{wire}"],
            rtol=0.0, atol=tol,
        ), (
            f"sharded outer sync diverged from replicated ({wire}): max "
            f"abs diff "
            f"{np.max(np.abs(params[f'replicated_{wire}'] - params[f'sharded_{wire}']))}"
        )

    # ISSUE-15 streamed outer sync (docs/operations.md §18): the same
    # sharded pipeline submitted on a background thread inside an
    # inner-compute window (GIL-releasing numpy work, sized ~1.2x the
    # measured blocking sync like the stall window a real streamed
    # schedule grants), framed in the rotating STREAM_OUTER tag window.
    # Measures the residual barrier wait — the §18 claim is that the wire
    # drains under the window and the residual is ~0 — and hard-asserts
    # the two ISSUE-15 gates: streamed-vs-blocking allclose, and
    # cross-replica bit-identity of the streamed result.
    import hashlib
    import threading

    from torchft_tpu import wire as wire_mod

    stream_tag_base, stream_tag_span = wire_mod.stream_frag_tag_window(0)

    def _streamed(quant: bool, window_s: float):
        per = per_q if quant else per_f
        state = shard_state[quant]
        base = comm.rank() * per

        def _cb(lo, hi, avg):
            updates, _ = tx.update(
                avg, _slice_state(state, per, lo - base, hi - base),
                backup_pad[lo:hi],
            )
            return np.asarray(updates, dtype=np.float32)

        box = {}

        def _bg():
            try:
                box["delta"] = outer_sharded_sync(
                    comm, psg, _cb, num_participants=world,
                    should_quantize=quant,
                    tag_base=stream_tag_base, tag_span=stream_tag_span,
                )
            except BaseException as e:  # noqa: BLE001 — re-raised below
                box["err"] = e

        th = threading.Thread(target=_bg, daemon=True)
        t0 = time.perf_counter()
        th.start()
        m = np.ones((256, 256), dtype=np.float32)
        while time.perf_counter() - t0 < window_s:
            # inner compute: releases the GIL; 1/256 keeps the uniform
            # matrix a fixed point instead of overflowing to inf
            m = m @ m * (1.0 / 256.0)
        wait0 = time.perf_counter()
        th.join()
        residual = time.perf_counter() - wait0
        if "err" in box:
            raise box["err"]
        return backup + box["delta"], residual

    for quant, wire in ((False, "f32"), (True, "quant")):
        sync_s = results[f"diloco_sharded_{wire}_s"]
        window_s = 1.2 * sync_s
        p_stream, _ = _streamed(quant, window_s)  # warm
        comm.barrier().wait(timeout=300.0)
        residuals = []
        for _ in range(3):
            p_stream, resid = _streamed(quant, window_s)
            residuals.append(resid)
        comm.barrier().wait(timeout=300.0)
        residual = sorted(residuals)[len(residuals) // 2]
        results[f"diloco_streamed_{wire}_residual_s"] = residual
        results[f"diloco_stream_overlap_{wire}"] = max(
            0.0, min(1.0, 1.0 - residual / max(sync_s, 1e-9))
        )
        # gate 1 — streamed vs blocking: same pseudo-gradient, same shard
        # state, same wire format, so the delta must match the blocking
        # sharded leg to reduction-order noise (it is byte-identical in
        # practice; the allclose bound is the ISSUE-15 acceptance wording)
        assert np.allclose(
            p_stream, params[f"sharded_{wire}"], rtol=0.0, atol=1e-6
        ), (
            f"streamed outer sync diverged from blocking ({wire}): max "
            f"abs diff "
            f"{np.max(np.abs(p_stream - params[f'sharded_{wire}']))}"
        )
        # gate 2 — cross-replica bit-identity: every rank applied the
        # identical wire-format delta; compare sha256 digests through the
        # (quiet) stream tag window rather than shipping params again
        digest = np.frombuffer(
            hashlib.sha256(np.ascontiguousarray(p_stream).tobytes()).digest(),
            dtype=np.uint8,
        ).astype(np.float32)
        all_digests = comm.allgather(digest, tag=stream_tag_base).wait(
            timeout=300.0
        )
        for r_idx, other in enumerate(all_digests):
            assert np.array_equal(digest, np.asarray(other)), (
                f"streamed params diverged across replicas ({wire}): "
                f"rank {comm.rank()} vs rank {r_idx}"
            )

    comm.barrier().wait(timeout=60.0)
    comm.shutdown()
    if rank == 0:
        out_q.put(results)


def run_diloco_profile(name, gbps, rtt_ms, mb, iters, world=3):
    """Sharded-vs-replicated DiLoCo outer-sync rows at ``world`` replicas.
    The headline ``diloco_sharded_vs_replicated`` is the DEFAULT (f32)
    wire's speedup; the int8 ratio rides alongside as
    ``diloco_sharded_vs_replicated_quant`` (docs/operations.md §11)."""
    from torchft_tpu.store import StoreServer

    store = StoreServer("127.0.0.1:0")
    ctx = mp.get_context("spawn")
    out_q = ctx.Queue()
    procs = [
        ctx.Process(
            target=_diloco_rank_main,
            args=(r, world, store.port, mb, iters, gbps, rtt_ms, out_q),
        )
        for r in range(world)
    ]
    for p in procs:
        p.start()
    try:
        res = out_q.get(timeout=1800)
        for p in procs:
            p.join(timeout=120)
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=10)
        store.shutdown()
    res["diloco_sharded_vs_replicated"] = round(
        res["diloco_replicated_f32_s"] / res["diloco_sharded_f32_s"], 3
    )
    res["diloco_sharded_vs_replicated_quant"] = round(
        res["diloco_replicated_quant_s"] / res["diloco_sharded_quant_s"], 3
    )
    # ISSUE-15 headline: fraction of the blocking sync the streamed
    # schedule hid under the inner-compute window (default wire)
    if "diloco_stream_overlap_f32" in res:
        res["diloco_stream_overlap"] = res["diloco_stream_overlap_f32"]
    return {k: (round(v, 4) if isinstance(v, float) else v) for k, v in res.items()}


def _hier_host_main(proc_idx, hosts, per_host, port, mb, iters, gbps, rtt_ms, out_q):
    """One PROCESS per emulated host, its replicas as THREADS: every rank
    of the host shares the process's emulated NIC (the communicator's
    process-shared link bucket), so the flat ring pays the real co-location
    tax — ``per_host`` full payload streams squeezing through one uplink —
    and the hierarchical schedule's once-per-host wire traffic shows up as
    genuine link relief, not just fewer ring steps."""
    os.environ["TORCHFT_NET_GBPS"] = str(gbps)
    os.environ["TORCHFT_NET_RTT_MS"] = str(rtt_ms)
    os.environ.setdefault("TORCHFT_QUANT_DEVICE_REDUCE", "0")
    from concurrent.futures import ThreadPoolExecutor

    from torchft_tpu.communicator import TCPCommunicator

    world = hosts * per_host
    n = mb * (1 << 20) // 4
    results = {}
    outputs = {}

    def _one_rank(rank, mode, prefix):
        rng = np.random.default_rng(rank)
        buf = rng.normal(size=n).astype(np.float32)
        comm = TCPCommunicator(
            timeout_s=300.0, host_id=f"h{proc_idx}", hierarchical=mode
        )
        comm.configure(
            f"127.0.0.1:{port}/{prefix}",
            replica_id=f"r{rank}",
            rank=rank,
            world_size=world,
        )
        try:
            out = np.asarray(comm.allreduce(buf.copy()).wait(timeout=300.0))
            comm.barrier().wait(timeout=300.0)
            t0 = time.perf_counter()
            for _ in range(iters):
                comm.allreduce(buf.copy()).wait(timeout=300.0)
            comm.barrier().wait(timeout=300.0)
            dt = (time.perf_counter() - t0) / iters
            return out, dt
        finally:
            comm.shutdown()

    for mode, label in (("0", "flat"), ("1", "hier")):
        local_ranks = [proc_idx * per_host + t for t in range(per_host)]
        with ThreadPoolExecutor(max_workers=per_host) as pool:
            got = list(
                pool.map(
                    # bind mode/label now: the lambda must not close
                    # over the live loop variables (ruff B023)
                    lambda r, mode=mode, label=label: _one_rank(
                        r, mode, f"hier_{label}_{per_host}"
                    ),
                    local_ranks,
                )
            )
        if proc_idx == 0:
            out, dt = got[0]
            outputs[label] = out
            results[f"allreduce_{label}_{per_host}perhost_s"] = dt

    if proc_idx == 0:
        # in-bench numeric-equivalence gate: the hierarchical schedule
        # reduces in a different (fixed) order — allclose, never silently
        # divergent values riding a throughput win
        flat, hier = outputs["flat"], outputs["hier"]
        assert np.allclose(flat, hier, rtol=1e-4, atol=1e-3), (
            "hierarchical allreduce diverged from flat ring: "
            f"max abs diff {np.max(np.abs(flat - hier))}"
        )
        out_q.put(results)


def run_hier_profile(name, gbps, rtt_ms, mb, iters, per_host, hosts=2):
    """Hierarchical-vs-flat allreduce rows at an emulated ``hosts`` x
    ``per_host`` topology (one process per host, replicas as threads)."""
    from torchft_tpu.store import StoreServer

    store = StoreServer("127.0.0.1:0")
    ctx = mp.get_context("spawn")
    out_q = ctx.Queue()
    procs = [
        ctx.Process(
            target=_hier_host_main,
            args=(p, hosts, per_host, store.port, mb, iters, gbps, rtt_ms, out_q),
        )
        for p in range(hosts)
    ]
    for p in procs:
        p.start()
    try:
        res = out_q.get(timeout=1800)
        for p in procs:
            p.join(timeout=120)
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=10)
        store.shutdown()
    payload = mb * (1 << 20)
    for label in ("flat", "hier"):
        key = f"allreduce_{label}_{per_host}perhost_s"
        res[f"allreduce_{label}_{per_host}perhost_GBps"] = round(
            payload / res[key] / 1e9, 3
        )
    res[f"hier_{per_host}perhost_speedup"] = round(
        res[f"allreduce_flat_{per_host}perhost_s"]
        / res[f"allreduce_hier_{per_host}perhost_s"],
        3,
    )
    return {k: (round(v, 4) if isinstance(v, float) else v) for k, v in res.items()}


def _tier_rank_main(rank, world, port, mb, iters, gbps, rtt_ms, tier, prefix, out_q):
    """One rank of the tier A/B row: the SAME in-place f32 allreduce on the
    selected data plane (cpp = native/libtpuft.so, python = the select-loop
    _TcpMesh), both shaped by the SAME pacer model (the native tier mirrors
    _NetEmu behind identical env knobs).  Reports the median step time plus
    a digest of the reduced bytes so the driver can assert cross-tier
    bit-identity — the speedup column can never ride a silent divergence."""
    import hashlib

    os.environ["TORCHFT_NET_GBPS"] = str(gbps)
    os.environ["TORCHFT_NET_RTT_MS"] = str(rtt_ms)
    if tier == "cpp":
        from torchft_tpu.native import CppCommunicator as Comm
    else:
        from torchft_tpu.communicator import TCPCommunicator as Comm
    from torchft_tpu.communicator import ReduceOp

    comm = Comm(timeout_s=300.0)
    comm.configure(
        f"127.0.0.1:{port}/{prefix}",
        replica_id=f"r{rank}",
        rank=rank,
        world_size=world,
    )
    n = mb * (1 << 20) // 4
    data = np.random.default_rng(7 + rank).normal(size=n).astype(np.float32)
    buf = data.copy()
    out = np.asarray(
        comm.allreduce(buf, ReduceOp.SUM, in_place=True).wait(timeout=300.0)
    )
    digest = hashlib.sha256(out.tobytes()).hexdigest()
    comm.barrier().wait(timeout=300.0)
    dts = []
    for _ in range(max(iters, 5)):
        np.copyto(buf, data)  # reset outside the timed window
        t0 = time.perf_counter()
        comm.allreduce(buf, ReduceOp.SUM, in_place=True).wait(timeout=300.0)
        dts.append(time.perf_counter() - t0)
    comm.barrier().wait(timeout=300.0)
    stats = comm.lane_stats()
    comm.shutdown()
    if rank == 0:
        out_q.put(
            {
                "dt": sorted(dts)[len(dts) // 2],
                "digest": digest,
                "lanes": stats.get("lanes"),
                "stalls": sum(stats.get("lane_stalls") or [0]),
            }
        )


def _run_tier_pair(tiers, port, mb, iters, gbps, rtt_ms, prefix):
    """Spawn one process per rank (rank r runs tiers[r]) and return rank
    0's measurement dict."""
    ctx = mp.get_context("spawn")
    out_q = ctx.Queue()
    procs = [
        ctx.Process(
            target=_tier_rank_main,
            args=(r, len(tiers), port, mb, iters, gbps, rtt_ms, tiers[r],
                  prefix, out_q),
        )
        for r in range(len(tiers))
    ]
    for p in procs:
        p.start()
    try:
        res = out_q.get(timeout=1200)
        for p in procs:
            p.join(timeout=120)
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=10)
    return res


def run_tier_profile(name, gbps, rtt_ms, mb, iters):
    """Native-vs-python data-plane rows for one profile (ISSUE-8 gate):
    the same 2-rank f32 allreduce on the cpp tier, the python tier, and a
    MIXED mesh (one rank per tier), all under the same pacer profile.

    The in-bench hard gate is cross-tier bit-identity (all three runs must
    produce identical bytes); the headline `native_vs_python_speedup` is
    the acceptance metric — at `dcn_10g` the python select loop's framing,
    not the emulated link, is the ceiling, so the native tier must clear
    >= 2x there on a non-starved host."""
    from torchft_tpu import native
    from torchft_tpu.store import StoreServer

    if not native.available():
        return {"native_tier": "unavailable"}
    store = StoreServer("127.0.0.1:0")
    try:
        cpp = _run_tier_pair(
            ("cpp", "cpp"), store.port, mb, iters, gbps, rtt_ms,
            f"tier_cpp_{name}",
        )
        py = _run_tier_pair(
            ("python", "python"), store.port, mb, iters, gbps, rtt_ms,
            f"tier_py_{name}",
        )
        mixed = _run_tier_pair(
            ("python", "cpp"), store.port, mb, iters, gbps, rtt_ms,
            f"tier_mix_{name}",
        )
    finally:
        store.shutdown()
    assert cpp["digest"] == py["digest"] == mixed["digest"], (
        f"cross-tier allreduce diverged at {name}: cpp={cpp['digest'][:12]} "
        f"py={py['digest'][:12]} mixed={mixed['digest'][:12]}"
    )
    payload = mb * (1 << 20)
    return {
        "native_allreduce_s": cpp["dt"],
        "native_allreduce_GBps": round(payload / cpp["dt"] / 1e9, 3),
        "python_allreduce_s": py["dt"],
        "python_allreduce_GBps": round(payload / py["dt"] / 1e9, 3),
        "mixed_allreduce_s": mixed["dt"],
        "native_vs_python_speedup": round(py["dt"] / cpp["dt"], 3),
        "native_lanes": cpp["lanes"],
        "native_stalls": cpp["stalls"],
        "tier_bit_identical": True,
    }


def run_profile(name, gbps, rtt_ms, mb, iters):
    from torchft_tpu.store import StoreServer

    store = StoreServer("127.0.0.1:0")
    ctx = mp.get_context("spawn")
    out_q = ctx.Queue()
    procs = [
        ctx.Process(
            target=_rank_main,
            args=(r, 2, store.port, mb, iters, gbps, rtt_ms, out_q),
        )
        for r in range(2)
    ]
    for p in procs:
        p.start()
    try:
        res = out_q.get(timeout=1200)
        for p in procs:
            p.join(timeout=120)
    finally:
        # failure path (rank crash -> queue stays empty): never orphan the
        # rank processes or leak the store
        for p in procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=10)
        store.shutdown()
    payload = mb * (1 << 20)
    res.update(
        profile=name,
        gbps=gbps,
        rtt_ms=rtt_ms,
        mb=mb,
        f32_ring_algo_GBps=round(payload / res["f32_ring_s"] / 1e9, 3),
        quant_ring_algo_GBps=round(payload / res["quant_ring_s"] / 1e9, 3),
        quant_speedup=round(res["f32_ring_s"] / res["quant_ring_s"], 3),
    )
    for lanes in (1, 2, 4):
        key = f"allreduce_{lanes}lane_s"
        if key in res:
            res[f"allreduce_{lanes}lane_GBps"] = round(
                payload / res[key] / 1e9, 3
            )
    if "allreduce_1lane_s" in res and "allreduce_4lane_s" in res:
        res["allreduce_4lane_speedup"] = round(
            res["allreduce_1lane_s"] / res["allreduce_4lane_s"], 3
        )
    if "flaky_allreduce_s" in res:
        res["flaky_allreduce_GBps"] = round(
            payload / res["flaky_allreduce_s"] / 1e9, 3
        )
        if "allreduce_4lane_s" in res:
            # fraction of clean-link 4-lane throughput retained at 1%
            # injected loss (acceptance bar: >= ~0.7)
            res["flaky_vs_clean"] = round(
                res["allreduce_4lane_s"] / res["flaky_allreduce_s"], 3
            )
    return {k: (round(v, 4) if isinstance(v, float) else v) for k, v in res.items()}


def run_striped_profile(name, gbps, rtt_ms, mb, iters, world=3):
    """Striped-heal rows for one profile: 1-source vs (world-1)-source heal
    bandwidth in the same 3-replica topology."""
    from torchft_tpu.store import StoreServer

    store = StoreServer("127.0.0.1:0")
    ctx = mp.get_context("spawn")
    out_q = ctx.Queue()
    procs = [
        ctx.Process(
            target=_striped_rank_main,
            args=(r, world, store.port, mb, iters, gbps, rtt_ms, out_q),
        )
        for r in range(world)
    ]
    for p in procs:
        p.start()
    try:
        res = out_q.get(timeout=1200)
        for p in procs:
            p.join(timeout=120)
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=10)
        store.shutdown()
    multi = f"{world - 1}src"
    res["heal_striped_speedup"] = round(
        res[f"heal_striped_1src_s"] / res[f"heal_striped_{multi}_s"], 3
    )
    return {k: (round(v, 4) if isinstance(v, float) else v) for k, v in res.items()}


def main():
    ap = argparse.ArgumentParser("dcn_bench")
    ap.add_argument("--mb", type=int, default=30,
                    help="payload MB (~0.8B-param DiLoCo fragment at 30)")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--md", action="store_true",
                    help="print a markdown table row block")
    ap.add_argument("--no-striped", action="store_true",
                    help="skip the 3-replica striped-heal phase")
    ap.add_argument("--no-hier", action="store_true",
                    help="skip the hierarchical 2-host topology sweep")
    ap.add_argument("--no-diloco", action="store_true",
                    help="skip the 3-replica sharded-vs-replicated outer-sync sweep")
    ap.add_argument("--no-tier", action="store_true",
                    help="skip the native-vs-python data-plane A/B rows")
    args = ap.parse_args()

    rows = []
    for name, gbps, rtt in PROFILES:
        row = run_profile(name, gbps, rtt, args.mb, args.iters)
        if not args.no_tier:
            # tier A/B at every profile: loopback shows the raw framing
            # ceilings, dcn_10g carries the >= 2x native acceptance gate
            row.update(run_tier_profile(name, gbps, rtt, args.mb, args.iters))
        if not args.no_striped:
            row.update(run_striped_profile(name, gbps, rtt, args.mb, args.iters))
        if not args.no_hier and name.startswith("wan_1g"):
            # topology sweep at the constrained profile only: on loopback
            # the flat ring already saturates and hierarchy buys nothing
            for per_host in (2, 4):
                row.update(
                    run_hier_profile(
                        name, gbps, rtt, args.mb, args.iters, per_host
                    )
                )
        if not args.no_diloco and name.startswith("wan_1g"):
            # sharded outer optimizer at the DCN profile the feature targets
            row.update(
                run_diloco_profile(name, gbps, rtt, args.mb, args.iters)
            )
        print(json.dumps(row), flush=True)
        rows.append(row)

    if args.md:
        print()
        print(
            "| profile | link | RTT | f32 ring | quant ring | quant speedup "
            "| heal | striped heal (2 src) |"
        )
        print("|---|---|---|---|---|---|---|---|")
        for r in rows:
            link = "—" if not r["gbps"] else f"{r['gbps']:g} Gb/s"
            rtt = "—" if not r["rtt_ms"] else f"{r['rtt_ms']:g} ms"
            striped = "—"
            if "heal_striped_2src_s" in r:
                striped = (
                    f"{r['heal_striped_2src_s']*1e3:.0f} ms "
                    f"({r['heal_striped_2src_GBps']:.2f} GB/s, "
                    f"**{r['heal_striped_speedup']}x** vs 1 src)"
                )
            print(
                f"| {r['profile']} | {link} | {rtt} "
                f"| {r['f32_ring_s']*1e3:.0f} ms ({r['f32_ring_algo_GBps']} GB/s) "
                f"| {r['quant_ring_s']*1e3:.0f} ms ({r['quant_ring_algo_GBps']} GB/s) "
                f"| **{r['quant_speedup']}x** "
                f"| {r['heal_s']*1e3:.0f} ms ({r['heal_GBps']:.2f} GB/s) "
                f"| {striped} |"
            )
        print()
        print(
            "| profile | 1 lane | 2 lanes | 4 lanes | 4-lane speedup "
            "| flaky 4-lane (1% loss) |"
        )
        print("|---|---|---|---|---|---|")
        for r in rows:
            if "allreduce_1lane_GBps" not in r:
                continue
            flaky = "—"
            if "flaky_allreduce_GBps" in r:
                flaky = (
                    f"{r['flaky_allreduce_GBps']} GB/s "
                    f"({r.get('flaky_vs_clean', 0):.0%} of clean, "
                    f"{r['flaky_lane_reconnects']:.0f} lane reconnects)"
                )
            print(
                f"| {r['profile']} "
                f"| {r['allreduce_1lane_GBps']} GB/s "
                f"| {r['allreduce_2lane_GBps']} GB/s "
                f"| {r['allreduce_4lane_GBps']} GB/s "
                f"| **{r['allreduce_4lane_speedup']}x** "
                f"| {flaky} |"
            )
        print()
        print(
            "| profile | python tier | native tier | native speedup "
            "| bit-identical |"
        )
        print("|---|---|---|---|---|")
        for r in rows:
            if "native_vs_python_speedup" not in r:
                continue
            print(
                f"| {r['profile']} "
                f"| {r['python_allreduce_GBps']} GB/s "
                f"| {r['native_allreduce_GBps']} GB/s "
                f"| **{r['native_vs_python_speedup']}x** "
                f"| {'yes' if r.get('tier_bit_identical') else 'NO'} |"
            )
        print()
        print(
            "| profile | outer sync | replicated | sharded (3 replicas) "
            "| speedup |"
        )
        print("|---|---|---|---|---|")
        for r in rows:
            if "diloco_sharded_quant_s" not in r:
                continue
            for wire in ("f32", "quant"):
                suffix = "" if wire == "f32" else "_quant"
                print(
                    f"| {r['profile']} | {wire} "
                    f"| {r[f'diloco_replicated_{wire}_s']*1e3:.0f} ms "
                    f"| {r[f'diloco_sharded_{wire}_s']*1e3:.0f} ms "
                    f"| **{r[f'diloco_sharded_vs_replicated{suffix}']}x** |"
                )
        print()
        print(
            "| profile | topology | flat ring | hierarchical | speedup |"
        )
        print("|---|---|---|---|---|")
        for r in rows:
            for per_host in (2, 4):
                if f"allreduce_hier_{per_host}perhost_GBps" not in r:
                    continue
                print(
                    f"| {r['profile']} | 2 hosts x {per_host}/host "
                    f"| {r[f'allreduce_flat_{per_host}perhost_GBps']} GB/s "
                    f"| {r[f'allreduce_hier_{per_host}perhost_GBps']} GB/s "
                    f"| **{r[f'hier_{per_host}perhost_speedup']}x** |"
                )


if __name__ == "__main__":
    main()
