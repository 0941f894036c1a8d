"""C++ runtime interop tests: the native servers/communicator must be
drop-in for their Python twins behind the unchanged Python clients."""

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List

import numpy as np
import pytest

from torchft_tpu import native
from torchft_tpu.communicator import ReduceOp
from torchft_tpu.lighthouse import LighthouseClient
from torchft_tpu.manager_server import ManagerClient
from torchft_tpu.store import StoreClient

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native runtime unavailable"
)


class TestCppStore:
    def test_python_client_interop(self) -> None:
        server = native.CppStoreServer("127.0.0.1:0")
        try:
            client = StoreClient(f"127.0.0.1:{server.port}", timeout=5.0)
            client.set("k", b"v")
            assert client.get("k") == b"v"
            assert client.add("n", 5) == 5
            assert client.add("n", 2) == 7
            assert client.exists("k")
            assert not client.exists("zzz")
            client.set("p/a", b"1")
            client.set("p/b", b"2")
            assert client.delete_prefix("p/") == 2
            with pytest.raises(TimeoutError):
                client.get("missing", timeout=0.3)
            client.close()
        finally:
            server.shutdown()

    def test_wait_for_key_across_clients(self) -> None:
        server = native.CppStoreServer("127.0.0.1:0")
        try:
            a = StoreClient(f"127.0.0.1:{server.port}", timeout=5.0)
            b = StoreClient(f"127.0.0.1:{server.port}", timeout=5.0)

            def _late() -> None:
                time.sleep(0.2)
                b.set("late", b"x")

            t = threading.Thread(target=_late)
            t.start()
            assert a.get("late", timeout=5.0) == b"x"
            t.join()
            a.close()
            b.close()
        finally:
            server.shutdown()


class TestCppLighthouse:
    def test_e2e_quorum(self) -> None:
        server = native.CppLighthouseServer(
            bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=50, quorum_tick_ms=20
        )
        try:
            client = LighthouseClient(server.local_address(), connect_timeout=5.0)
            client.heartbeat("foo")
            quorum = client.quorum(replica_id="foo", timeout=5.0, step=3)
            assert len(quorum.participants) == 1
            assert quorum.participants[0].step == 3
            assert quorum.quorum_id == 1
            st = client.status()
            assert st["impl"] == "cpp"
            client.close()
        finally:
            server.shutdown()

    def test_two_replicas_and_commit_failure_bump(self) -> None:
        server = native.CppLighthouseServer(
            bind="127.0.0.1:0", min_replicas=2, join_timeout_ms=500, quorum_tick_ms=20
        )
        addr = server.local_address()
        try:
            out: List = []

            def _ask(rid: str, cf: int) -> None:
                c = LighthouseClient(addr, connect_timeout=5.0)
                out.append(c.quorum(replica_id=rid, timeout=10.0, commit_failures=cf))
                c.close()

            threads = [
                threading.Thread(target=_ask, args=("a", 0)),
                threading.Thread(target=_ask, args=("b", 0)),
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10.0)
            assert all(q.quorum_id == 1 for q in out)
            assert [p.replica_id for p in out[0].participants] == ["a", "b"]

            # commit failures bump the quorum id
            out.clear()
            threads = [
                threading.Thread(target=_ask, args=("a", 0)),
                threading.Thread(target=_ask, args=("b", 2)),
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10.0)
            assert all(q.quorum_id == 2 for q in out)
        finally:
            server.shutdown()

    def test_http_dashboard_and_kill(self) -> None:
        """C++ lighthouse serves the HTTP dashboard + kill on the RPC port
        (parity with the Python server), compatible with punisher."""
        import json
        import urllib.request

        server = native.CppLighthouseServer(
            bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=50, quorum_tick_ms=20
        )
        try:
            client = LighthouseClient(server.local_address(), connect_timeout=5.0)
            client.quorum(replica_id="dash", timeout=5.0, step=4, address="vm:1")
            with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/status.json", timeout=5.0
            ) as resp:
                status = json.loads(resp.read())
            assert status["impl"] == "cpp"
            assert status["quorum_id"] == 1
            assert status["participants"][0]["replica_id"] == "dash"
            assert status["participants"][0]["step"] == 4
            # kill of an unknown replica → 404
            import urllib.error

            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/replica/ghost/kill",
                    timeout=5.0,
                )
            client.close()
        finally:
            server.shutdown()

    def test_timeout_honored(self) -> None:
        server = native.CppLighthouseServer(
            bind="127.0.0.1:0", min_replicas=2, join_timeout_ms=60000
        )
        try:
            client = LighthouseClient(server.local_address(), connect_timeout=5.0)
            start = time.monotonic()
            with pytest.raises(TimeoutError):
                client.quorum(replica_id="lonely", timeout=0.3)
            assert time.monotonic() - start < 2.0
            client.close()
        finally:
            server.shutdown()


class TestCppManager:
    def test_quorum_and_commit(self) -> None:
        lh = native.CppLighthouseServer(
            bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=50, quorum_tick_ms=20
        )
        mgr = native.CppManagerServer(
            replica_id="rep_0",
            lighthouse_addr=lh.local_address(),
            hostname="127.0.0.1",
            bind="127.0.0.1:0",
            store_addr="store_rep0",
            world_size=1,
        )
        try:
            client = ManagerClient(f"127.0.0.1:{mgr.port}")
            resp = client._quorum(
                group_rank=0,
                step=9,
                checkpoint_metadata="meta",
                shrink_only=False,
                timeout=10.0,
            )
            assert resp.quorum_id == 1
            assert resp.replica_rank == 0
            assert resp.max_step == 9
            assert not resp.heal
            assert resp.store_address == "store_rep0"
            assert client._checkpoint_metadata(0, timeout=5.0) == "meta"
            assert client.should_commit(0, 9, True, timeout=5.0) is True
            client.close()
        finally:
            mgr.shutdown()
            lh.shutdown()

    def test_heal_assignment_two_replicas(self) -> None:
        lh = native.CppLighthouseServer(
            bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=100, quorum_tick_ms=20
        )
        mgrs = [
            native.CppManagerServer(
                replica_id=f"rep_{i}",
                lighthouse_addr=lh.local_address(),
                hostname="127.0.0.1",
                bind="127.0.0.1:0",
                store_addr=f"store_{i}",
                world_size=1,
            )
            for i in range(2)
        ]
        try:
            results: List = [None, None]

            def _ask(i: int, step: int) -> None:
                c = ManagerClient(f"127.0.0.1:{mgrs[i].port}")
                results[i] = c._quorum(
                    group_rank=0,
                    step=step,
                    checkpoint_metadata=f"m{i}",
                    shrink_only=False,
                    timeout=10.0,
                )
                c.close()

            threads = [
                threading.Thread(target=_ask, args=(0, 5)),
                threading.Thread(target=_ask, args=(1, 0)),
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10.0)
            assert results[0] is not None and results[1] is not None
            assert not results[0].heal
            assert results[1].heal
            assert results[1].recover_src_replica_rank == results[0].replica_rank
            assert results[0].recover_dst_replica_ranks == [results[1].replica_rank]
            assert results[1].max_step == 5
        finally:
            for m in mgrs:
                m.shutdown()
            lh.shutdown()


@pytest.fixture()
def cpp_store():
    server = native.CppStoreServer("127.0.0.1:0")
    yield server
    server.shutdown()


def _run_ranks(
    store, world_size: int, fn: Callable, timeout_s: float = 30.0
) -> List[object]:
    def _one(rank: int) -> object:
        comm = native.CppCommunicator(timeout_s=timeout_s)
        comm.configure(
            f"127.0.0.1:{store.port}/q0",
            replica_id=f"r{rank}",
            rank=rank,
            world_size=world_size,
        )
        try:
            return fn(comm, rank)
        finally:
            comm.shutdown()

    with ThreadPoolExecutor(max_workers=world_size) as pool:
        return list(pool.map(_one, range(world_size)))


class TestCppCommunicator:
    @pytest.mark.parametrize("world_size", [1, 2, 3, 4])
    def test_allreduce_sum(self, cpp_store, world_size) -> None:
        n = 1000

        def _fn(comm, rank):
            data = np.arange(n, dtype=np.float32) + rank
            return comm.allreduce(data, ReduceOp.SUM).wait(timeout=30.0)

        results = _run_ranks(cpp_store, world_size, _fn)
        expected = sum(np.arange(n, dtype=np.float32) + r for r in range(world_size))
        for res in results:
            np.testing.assert_allclose(res, expected, rtol=1e-6)

    @pytest.mark.parametrize("world_size", [1, 2, 3])
    def test_reduce_scatter(self, cpp_store, world_size) -> None:
        n = 1000  # not divisible by 3 -> uneven chunks

        def _fn(comm, rank):
            data = np.arange(n, dtype=np.float32) + rank
            keep = data.copy()
            out = comm.reduce_scatter(data, ReduceOp.SUM).wait(timeout=30.0)
            np.testing.assert_array_equal(data, keep)  # input untouched
            return out

        results = _run_ranks(cpp_store, world_size, _fn)
        expected = sum(
            np.arange(n, dtype=np.float32) + r for r in range(world_size)
        )
        base, extra = divmod(n, world_size)
        off = 0
        for rank, res in enumerate(results):
            size = base + (1 if rank < extra else 0)
            np.testing.assert_allclose(
                res, expected[off : off + size], rtol=1e-6
            )
            off += size
        assert off == n

    def test_allreduce_bf16_and_avg(self, cpp_store) -> None:
        import ml_dtypes

        def _fn(comm, rank):
            data = np.full(513, float(rank + 1), dtype=ml_dtypes.bfloat16)
            return comm.allreduce(data, ReduceOp.AVG).wait(timeout=30.0)

        results = _run_ranks(cpp_store, 2, _fn)
        for res in results:
            assert res.dtype == ml_dtypes.bfloat16
            np.testing.assert_allclose(
                res.astype(np.float32), np.full(513, 1.5), rtol=1e-2
            )

    def test_broadcast_send_recv(self, cpp_store) -> None:
        def _fn(comm, rank):
            b = comm.broadcast(np.full(7, float(rank), dtype=np.float64), root=1).wait(
                timeout=30.0
            )
            if rank == 0:
                comm.send_bytes(b"ping", dst=1, tag=9).wait(timeout=30.0)
                got = None
            else:
                got = comm.recv_bytes(src=0, tag=9).wait(timeout=30.0)
            return b, got

        results = _run_ranks(cpp_store, 2, _fn)
        np.testing.assert_allclose(results[0][0], np.full(7, 1.0))
        np.testing.assert_allclose(results[1][0], np.full(7, 1.0))
        assert results[1][1] == b"ping"

    def test_alltoall_allgather(self, cpp_store) -> None:
        world_size = 3

        def _fn(comm, rank):
            chunks = [
                np.full(4, 10 * rank + p, dtype=np.float32)
                for p in range(world_size)
            ]
            a2a = comm.alltoall(chunks).wait(timeout=30.0)
            ag = comm.allgather(np.full(3, float(rank), dtype=np.float32)).wait(
                timeout=30.0
            )
            return a2a, ag

        results = _run_ranks(cpp_store, world_size, _fn)
        for rank, (a2a, ag) in enumerate(results):
            for src, arr in enumerate(a2a):
                np.testing.assert_allclose(arr, np.full(4, 10 * src + rank))
            for src, arr in enumerate(ag):
                np.testing.assert_allclose(arr, np.full(3, float(src)))

    def test_barrier_and_large_allreduce(self, cpp_store) -> None:
        n = 2_000_000  # 8 MB per rank

        def _fn(comm, rank):
            comm.barrier().wait(timeout=30.0)
            data = np.full(n, float(rank + 1), dtype=np.float32)
            t0 = time.monotonic()
            out = comm.allreduce(data, ReduceOp.SUM).wait(timeout=60.0)
            return out, time.monotonic() - t0

        results = _run_ranks(cpp_store, 2, _fn, timeout_s=60.0)
        for res, _dt in results:
            np.testing.assert_allclose(res[:5], np.full(5, 3.0))
        # native tier should move 8MB over loopback quickly
        assert results[0][1] < 5.0

    def test_abort_unblocks_and_reconfigure(self, cpp_store) -> None:
        world_size = 2
        barrier = threading.Barrier(world_size)
        errors: List[Exception] = []
        recovered: List[np.ndarray] = []

        def _fn(rank: int) -> None:
            comm = native.CppCommunicator(timeout_s=5.0)
            comm.configure(
                f"127.0.0.1:{cpp_store.port}/qa",
                replica_id=f"r{rank}",
                rank=rank,
                world_size=world_size,
            )
            barrier.wait()
            if rank == 1:
                comm.abort("injected")
                comm.shutdown()
                return
            work = comm.allreduce(np.ones(4096, dtype=np.float32))
            err = work.exception(timeout=30.0)
            assert err is not None
            errors.append(err)
            comm.configure(
                f"127.0.0.1:{cpp_store.port}/qb",
                replica_id=f"r{rank}",
                rank=0,
                world_size=1,
            )
            out = comm.allreduce(np.full(4, 2.0, dtype=np.float32)).wait(timeout=10.0)
            recovered.append(out)
            comm.shutdown()

        threads = [threading.Thread(target=_fn, args=(r,)) for r in range(world_size)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        assert len(errors) == 1
        assert len(recovered) == 1
        np.testing.assert_allclose(recovered[0], np.full(4, 2.0))


def _run_mixed_ranks(
    store,
    world_size: int,
    cpp_ranks: set,
    fn: Callable,
    prefix: str,
    timeout_s: float = 60.0,
) -> List[object]:
    """One rendezvous mixing tiers: ranks in ``cpp_ranks`` run the native
    communicator, the rest the Python one."""
    from torchft_tpu.communicator import TCPCommunicator

    def _one(rank: int) -> object:
        if rank in cpp_ranks:
            comm = native.CppCommunicator(timeout_s=timeout_s)
        else:
            comm = TCPCommunicator(timeout_s=timeout_s)
        comm.configure(
            f"127.0.0.1:{store.port}/{prefix}",
            replica_id=f"r{rank}",
            rank=rank,
            world_size=world_size,
        )
        try:
            return fn(comm, rank)
        finally:
            comm.shutdown()

    with ThreadPoolExecutor(max_workers=world_size) as pool:
        return list(pool.map(_one, range(world_size)))


class TestMixedTierMesh:
    """A cpp-tier rank among python-tier ranks in ONE rendezvous: the data
    plane is one wire contract — results must be BIT-identical to an
    all-python mesh at any lane count and wire kind (the ring schedule,
    lane splits, and reduction order are all mirrored math)."""

    @pytest.mark.parametrize("world_size", [2, 3])
    @pytest.mark.parametrize("lanes", [1, 2, 4])
    def test_f32_collectives_bit_identical(
        self, cpp_store, world_size, lanes, monkeypatch
    ) -> None:
        monkeypatch.setenv("TORCHFT_RING_LANES", str(lanes))
        monkeypatch.setenv("TORCHFT_RING_FRAME_KB", "64")
        n = 100_003  # ~400KB: stripes at 2+ lanes, uneven ring chunks

        def _ops(comm, rank):
            rng = np.random.default_rng(1000 + rank)
            data = rng.normal(size=n).astype(np.float32)
            ar = comm.allreduce(data.copy(), ReduceOp.SUM).wait(timeout=60.0)
            rs = comm.reduce_scatter(data.copy(), ReduceOp.SUM).wait(
                timeout=60.0
            )
            ag = comm.allgather(data[:1001].copy()).wait(timeout=60.0)
            return np.asarray(ar), np.asarray(rs), [np.asarray(g) for g in ag]

        mixed = _run_mixed_ranks(
            cpp_store,
            world_size,
            {world_size - 1},
            _ops,
            f"mix_{world_size}_{lanes}",
        )
        ref = _run_mixed_ranks(
            cpp_store, world_size, set(), _ops, f"ref_{world_size}_{lanes}"
        )
        for rank, (got, want) in enumerate(zip(mixed, ref)):
            np.testing.assert_array_equal(
                got[0], want[0], err_msg=f"allreduce diverged on rank {rank}"
            )
            np.testing.assert_array_equal(
                got[1],
                want[1],
                err_msg=f"reduce_scatter diverged on rank {rank}",
            )
            for src, (g, w) in enumerate(zip(got[2], want[2])):
                np.testing.assert_array_equal(
                    g,
                    w,
                    err_msg=f"allgather[{src}] diverged on rank {rank}",
                )

    @pytest.mark.parametrize("world_size", [2, 3])
    @pytest.mark.parametrize("lanes", [1, 2])
    def test_int8_wire_bit_identical(
        self, cpp_store, world_size, lanes, monkeypatch
    ) -> None:
        """The quantized (int8 wire) pipeline rides alltoall/allgather —
        same bytes through either tier's transport, bit-identical results."""
        from torchft_tpu.collectives import allreduce_quantized

        monkeypatch.setenv("TORCHFT_RING_LANES", str(lanes))
        monkeypatch.setenv("TORCHFT_RING_FRAME_KB", "64")
        monkeypatch.setenv("TORCHFT_QUANT_DEVICE_REDUCE", "0")
        n = 64 * 1024  # whole quantization rows

        def _ops(comm, rank):
            rng = np.random.default_rng(2000 + rank)
            data = rng.normal(size=n).astype(np.float32)
            out = allreduce_quantized(comm, data.copy()).wait(timeout=60.0)
            return np.asarray(out)

        mixed = _run_mixed_ranks(
            cpp_store,
            world_size,
            {world_size - 1},
            _ops,
            f"mixq_{world_size}_{lanes}",
        )
        ref = _run_mixed_ranks(
            cpp_store, world_size, set(), _ops, f"refq_{world_size}_{lanes}"
        )
        for rank, (got, want) in enumerate(zip(mixed, ref)):
            np.testing.assert_array_equal(
                got, want, err_msg=f"int8 allreduce diverged on rank {rank}"
            )


class TestTierDispatch:
    def test_auto_prefers_cpp_for_flat_ring(self, monkeypatch) -> None:
        from torchft_tpu import tier

        monkeypatch.delenv("TORCHFT_TIER", raising=False)
        monkeypatch.delenv("TORCHFT_HIERARCHICAL", raising=False)
        assert tier.data_plane_tier() == "cpp"
        comm = tier.make_communicator(timeout_s=5.0)
        assert type(comm).__name__ == "CppCommunicator"
        comm.shutdown()

    def test_forced_hierarchical_downgrades_loudly(
        self, monkeypatch, caplog
    ) -> None:
        from torchft_tpu import tier

        monkeypatch.delenv("TORCHFT_TIER", raising=False)
        monkeypatch.setenv("TORCHFT_HIERARCHICAL", "1")
        with caplog.at_level("WARNING", logger="torchft_tpu.tier"):
            assert tier.data_plane_tier() == "python"
        assert any("downgraded" in r.message for r in caplog.records)
        comm = tier.make_communicator(timeout_s=5.0)
        assert type(comm).__name__ == "TCPCommunicator"
        comm.shutdown()

    def test_explicit_tier_env_is_honored(self, monkeypatch) -> None:
        from torchft_tpu import tier

        monkeypatch.setenv("TORCHFT_TIER", "python")
        monkeypatch.delenv("TORCHFT_HIERARCHICAL", raising=False)
        assert tier.data_plane_tier() == "python"
        monkeypatch.setenv("TORCHFT_TIER", "cpp")
        monkeypatch.setenv("TORCHFT_HIERARCHICAL", "1")
        # explicit cpp wins even against forced hierarchy (warned)
        assert tier.data_plane_tier() == "cpp"

    def test_manager_defaults_to_tier_factory(self, monkeypatch) -> None:
        """A Manager constructed without a comm rides the tier factory —
        the train loop reaches the native mesh with zero caller wiring."""
        from torchft_tpu.manager import Manager

        monkeypatch.delenv("TORCHFT_TIER", raising=False)
        monkeypatch.delenv("TORCHFT_HIERARCHICAL", raising=False)
        lh = native.CppLighthouseServer(
            bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=50,
            quorum_tick_ms=20,
        )
        manager = None
        try:
            manager = Manager(
                min_replica_size=1,
                replica_id="tier_default_0",
                lighthouse_addr=lh.local_address(),
                timeout=10.0,
                quorum_timeout=10.0,
                use_async_quorum=False,
                server_cls=native.CppManagerServer,
            )
            assert type(manager._comm).__name__ == "CppCommunicator"
        finally:
            if manager is not None:
                manager.shutdown()
            lh.shutdown()


class TestZeroCopyHandoff:
    def test_as_host_array_jax_dlpack_is_zero_copy(self) -> None:
        import jax.numpy as jnp

        a = jnp.arange(1024, dtype=jnp.float32)
        view = native.as_host_array(a)
        assert isinstance(view, np.ndarray)
        # zero copy: the view aliases the jax CPU buffer
        assert view.ctypes.data == np.asarray(a).ctypes.data
        np.testing.assert_array_equal(view, np.arange(1024, dtype=np.float32))

    def test_as_host_array_buffer_protocol(self) -> None:
        raw = bytearray(b"\x01\x02\x03\x04")
        view = native.as_host_array(raw)
        assert view.dtype == np.uint8
        view[0] = 9  # bytearray view is writable and aliases
        assert raw[0] == 9

    def test_multi_array_allreduce_no_concat(self, cpp_store) -> None:
        """A list of arrays rides one ring as scattered iovec segments;
        in_place results alias the caller's buffers (no staging copy)."""

        def _fn(comm, rank):
            bufs = [
                np.full(1000, float(rank + 1), dtype=np.float32),
                np.full((32, 33), float(10 * (rank + 1)), dtype=np.float32),
                np.full(7, rank + 1, dtype=np.int32),
            ]
            out = comm.allreduce(bufs, ReduceOp.SUM, in_place=True).wait(
                timeout=30.0
            )
            # f32 outputs alias the inputs (zero-copy in-place reduce)
            assert out[0].base is bufs[0] or out[0] is bufs[0]
            return [np.asarray(o) for o in out]

        results = _run_ranks(cpp_store, 2, _fn)
        for res in results:
            np.testing.assert_allclose(res[0], np.full(1000, 3.0))
            np.testing.assert_allclose(res[1], np.full((32, 33), 30.0))
            np.testing.assert_array_equal(res[2], np.full(7, 3, np.int32))

    def test_jax_array_allreduce(self, cpp_store) -> None:
        """JAX CPU arrays hand off via dlpack (read-only view → one landing
        copy, never a concatenation stage)."""
        import jax.numpy as jnp

        def _fn(comm, rank):
            bufs = [
                jnp.full(513, float(rank + 1), dtype=jnp.float32),
                jnp.arange(100, dtype=jnp.float32) * (rank + 1),
            ]
            out = comm.allreduce(bufs, ReduceOp.SUM).wait(timeout=30.0)
            return [np.asarray(o) for o in out]

        results = _run_ranks(cpp_store, 2, _fn)
        for res in results:
            np.testing.assert_allclose(res[0], np.full(513, 3.0))
            np.testing.assert_allclose(
                res[1], np.arange(100, dtype=np.float32) * 3
            )

    def test_send_bytes_jax_source(self, cpp_store) -> None:
        import jax.numpy as jnp

        payload = jnp.arange(256, dtype=jnp.int32)

        def _fn(comm, rank):
            if rank == 0:
                comm.send_bytes(payload, dst=1, tag=77).wait(timeout=30.0)
                return None
            out = np.empty(256, dtype=np.int32)
            got = comm.recv_bytes_into(0, out, tag=77).wait(timeout=30.0)
            assert got == out.nbytes
            return out

        results = _run_ranks(cpp_store, 2, _fn)
        np.testing.assert_array_equal(
            results[1], np.arange(256, dtype=np.int32)
        )


class TestNativeLaneStats:
    def test_lane_stats_tier_agnostic_keys(
        self, cpp_store, monkeypatch
    ) -> None:
        """The native counters expose the same core surface the Python
        tier's lane_stats() does, so manager.last_quorum_timings and the
        torchft_quorums extras are tier-agnostic."""
        monkeypatch.setenv("TORCHFT_RING_LANES", "2")
        monkeypatch.setenv("TORCHFT_RING_FRAME_KB", "64")
        n = 200_000  # ~800KB → stripes across both lanes

        def _fn(comm, rank):
            data = np.ones(n, dtype=np.float32) * (rank + 1)
            comm.allreduce(data, ReduceOp.SUM, in_place=True).wait(
                timeout=30.0
            )
            return comm.lane_stats()

        stats = _run_ranks(cpp_store, 2, _fn)[0]
        # key parity with TCPCommunicator.lane_stats() (core counters)
        for key in (
            "lanes",
            "stripe_floor_bytes",
            "lane_tx_bytes",
            "lane_rx_bytes",
            "lane_stalls",
            "lane_reconnects",
            "lane_failovers",
            "faults_injected",
            "dead_lanes",
        ):
            assert key in stats, f"missing lane_stats key {key}"
        assert stats["lanes"] == 2
        assert len(stats["lane_tx_bytes"]) == 2
        # the ring moved the payload: both lanes carried bytes
        assert all(b > 0 for b in stats["lane_tx_bytes"])
        assert all(b > 0 for b in stats["lane_rx_bytes"])

    def test_unconfigured_lane_stats_empty(self) -> None:
        comm = native.CppCommunicator(timeout_s=5.0)
        assert comm.lane_stats() == {}
        comm.shutdown()

    @pytest.mark.parametrize(
        "cpp_ranks", [{0, 1}, set(), {1}], ids=["cpp", "python", "mixed"]
    )
    def test_the_ring_says_where_its_time_went(
        self, cpp_store, monkeypatch, cpp_ranks
    ) -> None:
        """``lane_stats()``'s seven counters of seconds (``RING_TIME_KEYS``),
        the same in both tiers and across a mixed pair: there, monotone,
        the op thread's three phases inside the wall time of the calls, the
        add counted by a reduce alone and the division by a divisor alone: in
        a pass of its own on the Python tier, inside the add on the native."""
        from torchft_tpu.communicator import RING_TIME_KEYS

        monkeypatch.setenv("TORCHFT_RING_LANES", "2")
        n = 400_000  # 1.6 MB: a ring's half stripes across both lanes

        def _fn(comm, rank):
            data = np.ones(n, dtype=np.float32) * (rank + 1)
            seen = [comm.lane_stats()]
            comm.allgather(np.arange(4096, dtype=np.float32)).wait(timeout=30.0)
            seen.append(comm.lane_stats())
            t0 = time.monotonic()
            comm.allreduce(data, ReduceOp.SUM).wait(timeout=30.0)
            seen.append(comm.lane_stats())
            out = comm.allreduce(data, ReduceOp.SUM, divisor=2).wait(timeout=30.0)
            wall = time.monotonic() - t0
            seen.append(comm.lane_stats())
            np.testing.assert_array_equal(np.asarray(out), np.full(n, 1.5, np.float32))
            return seen, wall

        ranks = _run_mixed_ranks(cpp_store, 2, cpp_ranks, _fn, "times")
        for rank, (seen, wall) in enumerate(ranks):
            fresh, gathered, summed, averaged = seen
            phases = ("ring_reduce_s", "ring_average_s", "ring_gather_s")
            for stats in seen:
                assert set(RING_TIME_KEYS) <= set(stats)
                assert all(len(stats[k]) == 2 for k in RING_TIME_KEYS[:3])
            # every counter is cumulative over the epoch
            for before, after in zip(seen, seen[1:]):
                for key in RING_TIME_KEYS:
                    a, b = np.atleast_1d(before[key]), np.atleast_1d(after[key])
                    assert (b >= a).all() and (a >= 0.0).all(), key
            # an allgather is no ring and adds nothing
            assert sum(gathered["lane_add_s"]) == 0.0
            assert all(gathered[k] == 0.0 for k in phases + ("ring_tail_s",))
            assert sum(gathered["lane_rx_s"]) > sum(fresh["lane_rx_s"])
            assert sum(gathered["lane_tx_s"]) > sum(fresh["lane_tx_s"])
            # a ring that sums: both phases, the add, a tail, no division
            assert summed["ring_reduce_s"] > 0.0 and summed["ring_gather_s"] > 0.0
            assert all(v > 0.0 for v in summed["lane_add_s"])
            assert summed["ring_tail_s"] > 0.0 and summed["ring_average_s"] == 0.0
            # a ring that averages: the owner's division, between the phases
            # on the Python tier and in the last reduce step's add on the
            # native one, whose stand-alone pass no ring of two takes
            assert (averaged["ring_average_s"] == 0.0) == (rank in cpp_ranks)
            assert all(a > s for a, s in zip(averaged["lane_add_s"], summed["lane_add_s"]))
            # the op thread's phases lie in the calls, the tail in the phases
            assert sum(averaged[k] for k in phases) <= wall
            assert averaged["ring_tail_s"] <= (
                averaged["ring_reduce_s"] + averaged["ring_gather_s"]
            )


class TestNativePacerParity:
    def test_auto_lane_and_floor_parity_under_emulation(
        self, cpp_store, monkeypatch
    ) -> None:
        """Under TORCHFT_NET_EMU both tiers must derive the SAME auto lane
        count and stripe floor (the rendezvous hello verifies them loudly),
        and a mixed mesh must still produce bit-identical sums — the pacer
        exists on both sides of the wire."""
        monkeypatch.setenv("TORCHFT_NET_EMU", "dcn_10g")
        n = 50_000

        def _ops(comm, rank):
            data = np.arange(n, dtype=np.float32) * (rank + 1)
            out = comm.allreduce(data, ReduceOp.SUM).wait(timeout=60.0)
            return np.asarray(out), comm.lane_stats()

        mixed = _run_mixed_ranks(cpp_store, 2, {1}, _ops, "emu_mix")
        expected = np.arange(n, dtype=np.float32) * 3
        for out, _stats in mixed:
            np.testing.assert_array_equal(out, expected)
        py_stats, cpp_stats = mixed[0][1], mixed[1][1]
        assert py_stats["lanes"] == cpp_stats["lanes"] == 4  # dcn_10g auto
        assert (
            py_stats["stripe_floor_bytes"] == cpp_stats["stripe_floor_bytes"]
        )

    def test_unknown_profile_is_loud(self, monkeypatch) -> None:
        monkeypatch.setenv("TORCHFT_NET_EMU", "wan_9000g")
        comm = native.CppCommunicator(timeout_s=5.0)
        store = native.CppStoreServer("127.0.0.1:0")
        try:
            with pytest.raises(Exception, match="TORCHFT_NET_EMU"):
                comm.configure(
                    f"127.0.0.1:{store.port}/loud",
                    replica_id="r0",
                    rank=0,
                    world_size=2,
                )
        finally:
            comm.shutdown()
            store.shutdown()


def test_full_native_stack_kill_and_heal() -> None:
    """The whole FT protocol on the native runtime: C++ lighthouse, C++
    manager sidecars, C++ communicators — threads-as-replicas with a kill,
    restart, live heal, and final state equality."""
    import jax
    import jax.numpy as jnp
    import optax

    from torchft_tpu.ddp import ft_allreduce
    from torchft_tpu.manager import Manager
    from torchft_tpu.optim import OptimizerWrapper

    lighthouse = native.CppLighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=100, quorum_tick_ms=20,
        heartbeat_timeout_ms=1000,
    )

    class Killed(Exception):
        pass

    kill_once = {"armed": True}
    states = {}

    def replica(idx: int) -> None:
        while True:
            comm = native.CppCommunicator(timeout_s=10.0)
            params = {"w": jnp.ones(32, dtype=jnp.float32)}
            tx = optax.sgd(0.05)
            holder = {"params": params, "opt_state": tx.init(params)}
            manager = Manager(
                comm=comm,
                load_state_dict=lambda s: holder.update(s),
                state_dict=lambda: dict(holder),
                min_replica_size=1,
                replica_id=f"native_{idx}",
                lighthouse_addr=lighthouse.local_address(),
                timeout=10.0,
                quorum_timeout=10.0,
                server_cls=native.CppManagerServer,
            )
            opt = OptimizerWrapper(manager, tx)
            try:
                while manager.current_step() < 10:
                    time.sleep(0.03)
                    if idx == 1 and manager.current_step() == 3 and kill_once["armed"]:
                        kill_once["armed"] = False
                        raise Killed()
                    opt.start_step()
                    grads = jax.tree_util.tree_map(
                        lambda p: jnp.full_like(p, 0.01 * (idx + 1)),
                        holder["params"],
                    )
                    grads = ft_allreduce(manager, grads)
                    opt.step(holder, grads)
                states[idx] = np.asarray(holder["params"]["w"])
                return
            except Killed:
                manager.shutdown()
                continue
            finally:
                if manager.current_step() >= 10:
                    manager.shutdown()

    try:
        threads = [
            threading.Thread(target=replica, args=(i,), daemon=True)
            for i in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
        assert set(states) == {0, 1}
        np.testing.assert_allclose(states[0], states[1], rtol=1e-6)
        assert not kill_once["armed"], "the kill never fired"
    finally:
        lighthouse.shutdown()


def test_cpp_faster_than_python_tier(cpp_store) -> None:
    """The native tier must beat the Python TCP tier on a 16MB allreduce."""
    from torchft_tpu.communicator import TCPCommunicator

    n = 4_000_000

    def _time_tier(make_comm, prefix: str) -> float:
        times = []

        def _fn(rank: int) -> None:
            comm = make_comm()
            comm.configure(
                f"127.0.0.1:{cpp_store.port}/{prefix}",
                replica_id=f"r{rank}",
                rank=rank,
                world_size=2,
            )
            data = np.ones(n, dtype=np.float32)
            comm.allreduce(data).wait(timeout=60.0)  # warm
            t0 = time.monotonic()
            comm.allreduce(data).wait(timeout=60.0)
            times.append(time.monotonic() - t0)
            comm.shutdown()

        threads = [threading.Thread(target=_fn, args=(r,)) for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
        return max(times)

    cpp_t = _time_tier(lambda: native.CppCommunicator(timeout_s=60.0), "perf_cpp")
    py_t = _time_tier(lambda: TCPCommunicator(timeout_s=60.0), "perf_py")
    print(f"16MB allreduce: cpp={cpp_t*1e3:.0f}ms python={py_t*1e3:.0f}ms")
    # Same-process thread-pair benchmarking is noisy (both tiers shuttle the
    # same loopback bytes and this test shares the machine with the rest of
    # the suite); only an order-of-magnitude sanity bound is stable.
    assert cpp_t < 15.0


def test_cross_implementation_rendezvous() -> None:
    """Implementation matrix: a Python TCP communicator rendezvousing on a
    C++ store, paired against a C++ communicator on the same store — the
    wire protocol is one contract regardless of implementation language."""
    from torchft_tpu.communicator import TCPCommunicator

    store = native.CppStoreServer("127.0.0.1:0")
    results = {}

    def _py_rank() -> None:
        comm = TCPCommunicator(timeout_s=30.0)
        comm.configure(
            f"127.0.0.1:{store.port}/xmatrix", replica_id="py", rank=0, world_size=2
        )
        try:
            results[0] = comm.allreduce(
                np.full(64, 1.0, dtype=np.float32), ReduceOp.SUM
            ).wait(timeout=30.0)
        finally:
            comm.shutdown()

    def _cpp_rank() -> None:
        comm = native.CppCommunicator(timeout_s=30.0)
        comm.configure(
            f"127.0.0.1:{store.port}/xmatrix", replica_id="cpp", rank=1, world_size=2
        )
        try:
            results[1] = comm.allreduce(
                np.full(64, 2.0, dtype=np.float32), ReduceOp.SUM
            ).wait(timeout=30.0)
        finally:
            comm.shutdown()

    try:
        threads = [
            threading.Thread(target=_py_rank),
            threading.Thread(target=_cpp_rank),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        np.testing.assert_allclose(results[0], np.full(64, 3.0))
        np.testing.assert_allclose(results[1], np.full(64, 3.0))
    finally:
        store.shutdown()


# ----------------------------------------------------------------------
# a round trip's rings as ONE native call (comm.h RingSession)
# ----------------------------------------------------------------------


def _pieces(rank: int, dtype, sizes) -> List[np.ndarray]:
    """One flat array a piece, values that differ by rank and place."""
    import ml_dtypes  # noqa: F401 — numpy learns bfloat16

    rng = np.random.default_rng(700 + rank)
    return [rng.standard_normal(n).astype(np.float32).astype(dtype) for n in sizes]


def _ring_each(comm, pieces: List[np.ndarray], divisor) -> None:
    """The per-piece path: a ring an op, in place."""
    for a in pieces:
        comm.allreduce(a, ReduceOp.SUM, in_place=True, divisor=divisor).wait(timeout=30.0)


def _ring_session(comm, pieces: List[np.ndarray], divisor, nap: float = 0.0) -> native.RingSession:
    """The same pieces through one session, each waited for as the gather
    thread would."""
    session = comm.ring_session(len(pieces), divisor)
    assert session is not None
    for a in pieces:
        if nap:
            time.sleep(nap)
        assert session.push(a)
    for k in range(len(pieces)):
        session.wait(k, timeout=30.0)
    session.work.wait(timeout=30.0)
    return session


# unequal sizes: one element, one under two stripe floors (it rides lane 0
# whole), one that stripes over every lane, one that no ring size divides
SESSION_SIZES = (1, 40_000, 300_007, 65_536, 13)


class TestRingSession:
    @pytest.mark.parametrize("divided", [False, True], ids=["sum", "average"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("world_size", [2, 3, 4])
    def test_the_session_s_bytes_are_the_per_piece_ring_s(
        self, cpp_store, monkeypatch, world_size, dtype, divided
    ) -> None:
        monkeypatch.setenv("TORCHFT_RING_LANES", "4")
        monkeypatch.setenv("TORCHFT_RING_FRAME_KB", "64")
        divisor = world_size - 1 if divided and world_size > 2 else (2 if divided else None)

        def _fn(through):
            def _one(comm, rank):
                pieces = _pieces(rank, dtype, SESSION_SIZES)
                through(comm, pieces, divisor)
                return [a.tobytes() for a in pieces], comm.lane_stats()
            return _one

        # (a rendezvous of its own each: the store keeps the first one's keys)
        everyone = set(range(world_size))
        each = _run_mixed_ranks(cpp_store, world_size, everyone, _fn(_ring_each), "each")
        once = _run_mixed_ranks(cpp_store, world_size, everyone, _fn(_ring_session), "once")
        for rank in range(world_size):
            assert once[rank][0] == each[rank][0], rank
            assert once[rank][0] == once[0][0]  # and every rank holds the same
            # ONE native ring call, and the same bytes on the same lanes
            assert once[rank][1]["ring_calls"] == 1 and each[rank][1]["ring_calls"] == len(SESSION_SIZES)
            assert once[rank][1]["lane_tx_bytes"] == each[rank][1]["lane_tx_bytes"]

    @pytest.mark.parametrize("lanes", [1, 4])
    def test_a_session_peer_rides_with_a_per_piece_peer_and_a_python_peer(
        self, cpp_store, monkeypatch, lanes
    ) -> None:
        """Rank 0 inside a session, rank 1 on the native per-piece path, rank
        2 on the Python tier: one ring, the bits of an all-Python mesh."""
        import ml_dtypes  # noqa: F401

        monkeypatch.setenv("TORCHFT_RING_LANES", str(lanes))
        monkeypatch.setenv("TORCHFT_RING_FRAME_KB", "64")

        def _fn(comm, rank):
            pieces = _pieces(rank, "bfloat16", SESSION_SIZES)
            if rank == 0 and isinstance(comm, native.CppCommunicator):
                _ring_session(comm, pieces, 3, nap=0.002)
            else:
                _ring_each(comm, pieces, 3)
            return [a.tobytes() for a in pieces]

        mixed = _run_mixed_ranks(cpp_store, 3, {0, 1}, _fn, f"sess_mix_{lanes}")
        ref = _run_mixed_ranks(cpp_store, 3, set(), _fn, f"sess_ref_{lanes}")
        assert mixed == ref

    def test_pushes_that_arrive_late_are_waited_for_outside_the_phases(self, cpp_store, monkeypatch) -> None:
        monkeypatch.setenv("TORCHFT_RING_LANES", "2")
        nap, sizes = 0.03, (50_000,) * 6

        def _fn(comm, rank):
            before = comm.lane_stats()
            t0 = time.monotonic()
            session = _ring_session(comm, _pieces(rank, "float32", sizes), 2, nap=nap)
            wall = time.monotonic() - t0
            return before, comm.lane_stats(), wall, session.times()

        for before, after, wall, times in _run_ranks(cpp_store, 2, _fn):
            assert before["ring_wait_push_s"] == 0.0
            waited = after["ring_wait_push_s"]
            phases = sum(after[k] - before[k] for k in ("ring_reduce_s", "ring_average_s", "ring_gather_s"))
            rings = sum(t1 - t0 for t0, t1 in times)
            # the op thread waited about a nap a piece, and that wait lies in
            # neither phase nor the tail: the three add up inside the wall
            assert waited > 0.5 * nap * len(sizes)
            assert phases <= rings + 1e-4 and after["ring_tail_s"] - before["ring_tail_s"] <= phases
            assert waited + rings <= wall + 1e-3
            # a piece's ring never starts before its predecessor's has ended
            assert all(a1 <= b0 for (_, a1), (b0, _) in zip(times, times[1:]))
            assert len(times) == len(sizes) and all(t0 < t1 for t0, t1 in times)

    def test_close_with_pieces_unpushed(self, cpp_store) -> None:
        def _fn(comm, rank):
            pieces = _pieces(rank, "float32", (1000, 2000))
            session = comm.ring_session(5, 2)
            for a in pieces:
                assert session.push(a)
            session.close()
            assert not session.push(np.zeros(8, np.float32))  # a no-op now
            session.work.wait(timeout=30.0)  # the run ended without an error
            session.wait(1, timeout=5.0)
            with pytest.raises(native.CommunicatorError, match="ended before"):
                session.wait(2, timeout=5.0)
            # the communicator is whole: the next op runs
            out = comm.allreduce(np.ones(4, np.float32), ReduceOp.SUM).wait(timeout=30.0)
            return [a.tobytes() for a in pieces], out.tolist()

        got = _run_ranks(cpp_store, 2, _fn)
        assert got[0] == got[1] and got[0][1] == [2.0] * 4

    @pytest.mark.parametrize("where", ["run_waits_for_a_push", "wait_waits_for_a_piece"])
    @pytest.mark.parametrize("what", ["abort", "peer_death", "timeout"])
    def test_a_failure_wakes_the_run_and_every_wait(self, cpp_store, what, where) -> None:
        """Rank 0's session meets the failure: while its run waits for a push
        that never comes (its peer is mid-ring on a piece it never pushed)
        or while piece 0 is in its ring and the peer never pushes its own.
        The first error fails that piece and every later one, later pushes
        are no-ops, and the next epoch serves."""
        pushes = where == "wait_waits_for_a_piece"
        barrier = threading.Barrier(2)
        seen: dict = {}

        def _fn(rank: int) -> None:
            comm = native.CppCommunicator(timeout_s=1.5 if what == "timeout" else 20.0)
            comm.configure(f"127.0.0.1:{cpp_store.port}/fail_{what}_{where}", f"r{rank}", rank, 2)
            session = comm.ring_session(3, 2)
            assert session is not None
            # who pushes: in the one case rank 0 alone (its ring waits for a
            # peer that never comes), in the other rank 1 alone (rank 0's run
            # waits for a push while its peer's ring waits for it)
            if pushes == (rank == 0):
                assert session.push(np.ones(100_000, np.float32))
            barrier.wait()
            if rank == 1:
                if what == "peer_death":
                    time.sleep(0.2)
                    comm.shutdown()
                elif what == "abort":
                    time.sleep(5.0)
                    comm.shutdown()
                else:
                    with pytest.raises(Exception):
                        session.wait(0, timeout=20.0)
                    comm.shutdown()
                return
            if what == "abort":
                threading.Timer(0.2, comm.abort, args=("injected",)).start()
            t0 = time.monotonic()
            if what == "timeout" and not pushes:
                # nobody is late but the train thread: a run that waits for a
                # push has no deadline (a slow landing is no hanging ring);
                # the waiter's own limit passes and the close lets the run go
                with pytest.raises(TimeoutError):
                    session.wait(0, timeout=0.3)
                session.close()
                assert session.work.exception(timeout=10.0) is None
                with pytest.raises(native.CommunicatorError, match="ended before"):
                    session.wait(0, timeout=5.0)
            else:
                if what == "peer_death" and not pushes:
                    # a run that waits for a push reads no socket: the death
                    # is the next ring's to find, as on the per-piece path
                    with pytest.raises(TimeoutError):
                        session.wait(0, timeout=1.0)
                    assert session.push(np.ones(100_000, np.float32))
                with pytest.raises(native.CommunicatorError):
                    session.wait(0, timeout=15.0)
                assert session.work.exception(timeout=10.0) is not None
                with pytest.raises(native.CommunicatorError):
                    session.wait(2, timeout=5.0)  # and every later one
                assert comm.errored() is not None
            seen["took"] = time.monotonic() - t0
            assert not session.push(np.ones(8, np.float32))  # a no-op
            # a new epoch serves
            comm.configure(f"127.0.0.1:{cpp_store.port}/fail_{what}_{where}_b", "r0", 0, 1)
            seen["next"] = comm.allreduce(np.full(4, 2.0, np.float32)).wait(timeout=10.0).tolist()
            comm.shutdown()

        threads = [threading.Thread(target=_fn, args=(r,)) for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
            assert not t.is_alive()
        assert seen["next"] == [2.0] * 4 and seen["took"] < 10.0

    def test_no_session_where_there_is_no_ring_or_no_op_thread(self, cpp_store) -> None:
        comm = native.CppCommunicator(timeout_s=5.0)
        assert comm.ring_session(3, 2) is None  # not configured: one member
        comm.configure(f"127.0.0.1:{cpp_store.port}/solo", "r0", 0, 1)
        assert comm.ring_session(3, 2) is None  # a ring of one
        comm.shutdown()

    @pytest.mark.parametrize(
        "looks,aborts",
        [
            ([(0, 1), (0, 1)], 1),  # pushed, at the head, and a whole timeout later still not rung
            ([(0, 0), (0, 0), (0, 0)], 0),  # nothing pushed: a slow landing is no ring that hangs
            ([(0, 0), (0, 1), (1, 1)], 0),  # pushed after the last look, rung by the next
            ([(0, 0), (0, 1), (0, 1)], 1),  # pushed after a look: it has had a whole timeout at the next but one
            ([(1, 2), (2, 3), (3, 4)], 0),  # the pieces move
            ([(3, 3), (3, 3)], 0),  # everything pushed is rung: the call waits for a push or a close
        ],
    )
    def test_the_session_s_watch_aborts_a_piece_that_outlives_its_deadline_alone(self, monkeypatch, looks, aborts) -> None:
        """The op watchdog's stand-in over a session's one call looks at the
        pieces' progress once a timeout: only a piece that was pushed and at
        the head at the LAST look and is still not rung is a hang (C's own
        deadline is then overdue)."""
        timers = []

        class _Handle:
            def cancel(self):
                pass

        monkeypatch.setattr(native, "schedule_timeout", lambda delay, fn: timers.append(fn) or _Handle())

        class _Session:
            pushed = 0

            def rung(self):
                return self._rung

        session, heard = _Session(), []
        watch = native._SessionWatch(session, 5.0, heard.append)
        for rung, pushed in looks:
            session._rung, session.pushed = rung, pushed
            if timers:
                timers.pop()()
        assert len(heard) == aborts and (not heard or "piece 0" in heard[0])
        assert len(timers) == (0 if aborts else 1)  # armed anew until it aborts
        watch.cancel()
        if timers:
            timers.pop()()  # a look after the call returned does nothing
        assert len(heard) == aborts and not timers

    def test_one_comm_op_span_a_piece_with_rising_k(self, cpp_store) -> None:
        """The op thread is inside one call (``tpuft/comm/session``); the
        pieces' spans are told when it returns, from the times C kept, in the
        span buffer alone (a profiler's trace: the test below)."""
        from torchft_tpu.obs import spans as obs_spans

        obs_spans.configure(True)
        obs_spans.clear()
        try:
            sizes = (1000, 5000, 70_000, 9)

            def _fn(comm, rank):
                _ring_session(comm, _pieces(rank, "float32", sizes), 2)
                _ring_each(comm, _pieces(rank, "float32", (64,)), 2)
                return comm._op_thread.ident

            idents = _run_ranks(cpp_store, 2, _fn)
            for ident in idents:
                mine = [s for s in obs_spans.snapshot() if s["tid"] == ident]
                ops = [s for s in mine if s["name"] == "tpuft/comm/op"]
                (live,) = [s for s in mine if s["name"] == "tpuft/comm/session"]
                # the session's four pieces, then the op after it: k goes on
                first = live["attrs"]["k"]  # (1 where no recorder tells the op thread the step)
                assert [s["attrs"]["k"] for s in ops] == [first + i for i in range(5)]
                assert live["attrs"]["pieces"] == len(sizes)
                assert all(s["attrs"]["tier"] == "cpp" for s in ops)
                inside = ops[:4]
                assert all(live["t"] <= s["t"] and s["t"] + s["dur"] <= live["t"] + live["dur"] + 1e-6 for s in inside)
                assert all(a["t"] + a["dur"] <= b["t"] + 1e-9 for a, b in zip(inside, inside[1:]))
        finally:
            obs_spans.configure(None)
            obs_spans.clear()

    def test_a_profiler_s_trace_shows_the_session_and_none_of_its_pieces(self, cpp_store, tmp_path) -> None:
        """A span that has already ended cannot be annotated: the trace has
        the op thread's one live ``tpuft/comm/session`` a rank and no
        ``tpuft/comm/op`` (whoever reads those from a trace finds none where
        the session ran)."""
        import glob

        import jax
        from jax.profiler import ProfileData

        sizes = (1000, 5000, 70_000)
        jax.profiler.start_trace(str(tmp_path))
        try:
            _run_ranks(cpp_store, 2, lambda comm, rank: _ring_session(comm, _pieces(rank, "float32", sizes), 2))
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
        names = [
            ev.name
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines
            for ev in line.events
            if ev.name.startswith("tpuft/comm/")
        ]
        assert names.count("tpuft/comm/session") == 2 and "tpuft/comm/op" not in names
