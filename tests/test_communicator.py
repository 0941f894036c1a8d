"""Communicator conformance + resiliency tests.

Analog of the reference's PG harness (``torchft/process_group_test.py``):
every collective exercised across N thread-ranks on one shared store, plus
the resiliency flow — abort a rank, assert survivors error out, reconfigure
to a fresh store prefix, rerun the collective
(``process_group_test.py:891-950``).
"""

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List

import numpy as np
import pytest

from torchft_tpu.communicator import (
    CommunicatorAborted,
    DummyCommunicator,
    FakeCommunicatorWrapper,
    ReduceOp,
    TCPCommunicator,
)
from torchft_tpu.store import StoreServer


@pytest.fixture()
def store():
    server = StoreServer("127.0.0.1:0")
    yield server
    server.shutdown()


def _run_ranks(
    store: StoreServer,
    world_size: int,
    fn: Callable[[TCPCommunicator, int], object],
    prefix: str = "q0",
    timeout_s: float = 30.0,
) -> List[object]:
    comms = [TCPCommunicator(timeout_s=timeout_s) for _ in range(world_size)]

    def _one(rank: int) -> object:
        comm = comms[rank]
        comm.configure(
            f"127.0.0.1:{store.port}/{prefix}",
            replica_id=f"rep_{rank}",
            rank=rank,
            world_size=world_size,
            quorum_id=0,
        )
        try:
            return fn(comm, rank)
        finally:
            comm.shutdown()

    with ThreadPoolExecutor(max_workers=world_size) as pool:
        return list(pool.map(_one, range(world_size)))


@pytest.mark.parametrize("world_size", [1, 2, 3, 4])
def test_allreduce_sum(store, world_size) -> None:
    n = 1000  # not divisible by 3 → exercises uneven ring chunks

    def _fn(comm, rank):
        data = np.arange(n, dtype=np.float32) + rank
        return comm.allreduce(data, ReduceOp.SUM).wait(timeout=30.0)

    results = _run_ranks(store, world_size, _fn)
    expected = sum(np.arange(n, dtype=np.float32) + r for r in range(world_size))
    for res in results:
        np.testing.assert_allclose(res, expected, rtol=1e-6)


@pytest.mark.parametrize("op,reduce_fn", [
    (ReduceOp.AVG, lambda stack: np.mean(stack, axis=0)),
    (ReduceOp.MAX, lambda stack: np.max(stack, axis=0)),
    (ReduceOp.MIN, lambda stack: np.min(stack, axis=0)),
])
def test_allreduce_ops(store, op, reduce_fn) -> None:
    world_size = 3
    rng = np.random.default_rng(0)
    inputs = [rng.normal(size=257).astype(np.float32) for _ in range(world_size)]

    def _fn(comm, rank):
        return comm.allreduce(inputs[rank].copy(), op).wait(timeout=30.0)

    results = _run_ranks(store, world_size, _fn)
    expected = reduce_fn(np.stack(inputs))
    for res in results:
        np.testing.assert_allclose(res, expected, rtol=1e-5)


def test_allreduce_multiple_buffers(store) -> None:
    world_size = 2

    def _fn(comm, rank):
        bufs = [
            np.full((3, 4), float(rank + 1), dtype=np.float32),
            np.full(7, float(rank + 10), dtype=np.float64),
        ]
        return comm.allreduce(bufs, ReduceOp.SUM).wait(timeout=30.0)

    # mixed dtypes flatten per-buffer; use same dtype to share one ring
    def _fn_same(comm, rank):
        bufs = [
            np.full((3, 4), float(rank + 1), dtype=np.float32),
            np.full(7, float(rank + 10), dtype=np.float32),
        ]
        return comm.allreduce(bufs, ReduceOp.SUM).wait(timeout=30.0)

    results = _run_ranks(store, world_size, _fn_same)
    for res in results:
        np.testing.assert_allclose(res[0], np.full((3, 4), 3.0))
        np.testing.assert_allclose(res[1], np.full(7, 21.0))


def test_broadcast(store) -> None:
    world_size = 3

    def _fn(comm, rank):
        data = np.full(11, float(rank), dtype=np.float32)
        return comm.broadcast(data, root=1).wait(timeout=30.0)

    results = _run_ranks(store, world_size, _fn)
    for res in results:
        np.testing.assert_allclose(res, np.full(11, 1.0))


def test_send_recv_bytes(store) -> None:
    world_size = 2

    def _fn(comm, rank):
        if rank == 0:
            comm.send_bytes(b"hello from zero", dst=1, tag=7).wait(timeout=30.0)
            return None
        return comm.recv_bytes(src=0, tag=7).wait(timeout=30.0)

    results = _run_ranks(store, world_size, _fn)
    assert results[1] == b"hello from zero"


def test_send_recv_large(store) -> None:
    world_size = 2
    payload = b"x" * 100_000

    def _fn(comm, rank):
        if rank == 0:
            comm.send_bytes(payload, dst=1, tag=40).wait(timeout=30.0)
            return None
        return comm.recv_bytes(src=0, tag=40).wait(timeout=30.0)

    results = _run_ranks(store, world_size, _fn)
    assert results[1] == payload


def test_allreduce_mixed_dtypes_preserved(store) -> None:
    """Mixed dtypes must NOT promote (f32+i64 would concatenate to f64)."""
    world_size = 2

    def _fn(comm, rank):
        bufs = [
            np.full(5, float(rank + 1), dtype=np.float32),
            np.full(3, rank + 1, dtype=np.int64),
        ]
        return comm.allreduce(bufs, ReduceOp.SUM).wait(timeout=30.0)

    results = _run_ranks(store, world_size, _fn)
    for res in results:
        assert res[0].dtype == np.float32
        assert res[1].dtype == np.int64
        np.testing.assert_allclose(res[0], np.full(5, 3.0))
        np.testing.assert_array_equal(res[1], np.full(3, 3))


def test_barrier(store) -> None:
    world_size = 3
    arrived = []

    def _fn(comm, rank):
        arrived.append(rank)
        comm.barrier().wait(timeout=30.0)
        return len(arrived)

    results = _run_ranks(store, world_size, _fn)
    # nobody exits the barrier before everyone arrived
    assert all(r == world_size for r in results)


def test_large_allreduce(store) -> None:
    world_size = 2
    n = 2_000_000  # 8 MB per rank: forces chunked duplex IO past socket buffers

    def _fn(comm, rank):
        data = np.full(n, float(rank + 1), dtype=np.float32)
        return comm.allreduce(data, ReduceOp.SUM).wait(timeout=60.0)

    results = _run_ranks(store, world_size, _fn, timeout_s=60.0)
    for res in results:
        np.testing.assert_allclose(res[:10], np.full(10, 3.0))
        np.testing.assert_allclose(res[-10:], np.full(10, 3.0))


class TestResiliency:
    def test_abort_unblocks_and_reconfigure_recovers(self, store) -> None:
        """Kill the last rank mid-collective; survivors must error out, then
        reconfigure under a fresh prefix and successfully rerun
        (``process_group_test.py:891-950``)."""
        world_size = 3
        barrier = threading.Barrier(world_size)
        survivors_errors: List[Exception] = []
        second_round: List[np.ndarray] = []

        def _fn(rank: int) -> None:
            comm = TCPCommunicator(timeout_s=5.0)
            comm.configure(
                f"127.0.0.1:{store.port}/q0",
                replica_id=f"rep_{rank}",
                rank=rank,
                world_size=world_size,
            )
            barrier.wait()
            if rank == world_size - 1:
                comm.abort("injected failure")
                # dead rank: does not participate in round 2
                return
            work = comm.allreduce(np.ones(4096, dtype=np.float32), ReduceOp.SUM)
            err = work.exception(timeout=30.0)
            assert err is not None
            survivors_errors.append(err)
            assert comm.errored() is not None or err is not None

            # reconfigure to the survivor set under a fresh prefix
            comm.configure(
                f"127.0.0.1:{store.port}/q1",
                replica_id=f"rep_{rank}",
                rank=rank,
                world_size=world_size - 1,
            )
            assert comm.errored() is None
            res = comm.allreduce(
                np.full(64, float(rank + 1), dtype=np.float32), ReduceOp.SUM
            ).wait(timeout=30.0)
            second_round.append(res)
            comm.shutdown()

        threads = [threading.Thread(target=_fn, args=(r,)) for r in range(world_size)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        assert len(survivors_errors) == world_size - 1
        assert len(second_round) == world_size - 1
        for res in second_round:
            np.testing.assert_allclose(res, np.full(64, 3.0))

    def test_op_timeout_aborts(self, store) -> None:
        """A collective whose peers never show up aborts via the userspace
        timeout instead of hanging (``process_group.py:714-777``)."""
        comms = [TCPCommunicator(timeout_s=2.0) for _ in range(2)]

        def _configure(rank: int) -> None:
            comms[rank].configure(
                f"127.0.0.1:{store.port}/qt",
                replica_id=f"rep_{rank}",
                rank=rank,
                world_size=2,
            )

        with ThreadPoolExecutor(max_workers=2) as pool:
            list(pool.map(_configure, range(2)))

        # only rank 0 issues the collective; rank 1 never joins it
        start = time.monotonic()
        work = comms[0].allreduce(np.ones(8, dtype=np.float32), ReduceOp.SUM)
        err = work.exception(timeout=30.0)
        assert err is not None
        assert time.monotonic() - start < 10.0
        assert comms[0].errored() is not None
        for c in comms:
            c.shutdown()

    def test_poisoned_until_reconfigure(self, store) -> None:
        comm = TCPCommunicator(timeout_s=2.0)
        comm.configure(
            f"127.0.0.1:{store.port}/qp", replica_id="r", rank=0, world_size=1
        )
        comm.abort("poison test")
        work = comm.allreduce(np.ones(3, dtype=np.float32))
        assert isinstance(work.exception(timeout=5.0), CommunicatorAborted)
        # reconfigure clears the poison
        comm.configure(
            f"127.0.0.1:{store.port}/qp2", replica_id="r", rank=0, world_size=1
        )
        res = comm.allreduce(np.ones(3, dtype=np.float32), ReduceOp.SUM).wait(
            timeout=5.0
        )
        np.testing.assert_allclose(res, np.ones(3))
        comm.shutdown()


class TestInflightOpsCounter:
    """Regression pin for the PR-6 third-round ``_inflight_ops`` fix: the
    busy() counter rides its OWN lock because old- and new-epoch op threads
    overlap (teardown queues a sentinel but never joins), and an
    unsynchronized ``+=`` / ``-=`` pair can lose an update either way —
    sticking busy() True forever (spare warm serving waits the full yield
    window on every request) or letting it underflow (warm serving never
    yields to live collectives).  Two threads hammer the exact
    ``_op_started`` / ``_op_finished`` protocol ``_run_ops`` uses; after
    every paired enter/exit the counter must be back at idle."""

    HAMMER = 20_000

    def _hammer(self, comm) -> None:
        barrier = threading.Barrier(2)

        def slam() -> None:
            barrier.wait()
            for _ in range(self.HAMMER):
                comm._op_started()
                comm._op_finished()

        threads = [threading.Thread(target=slam) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert comm._inflight_ops == 0, (
            f"lost update under contention: counter at {comm._inflight_ops} "
            f"after {2 * self.HAMMER} paired ops"
        )
        assert comm.busy() is False

    def test_tcp_counter_survives_contention(self) -> None:
        self._hammer(TCPCommunicator(timeout_s=1.0))

    def test_cpp_counter_survives_contention(self) -> None:
        from torchft_tpu import native

        if not native.available():
            pytest.skip("native runtime unavailable")
        self._hammer(native.CppCommunicator(timeout_s=1.0))


def test_dummy_communicator() -> None:
    comm = DummyCommunicator()
    data = np.arange(5, dtype=np.float32)
    np.testing.assert_allclose(comm.allreduce(data).wait(), data)
    assert comm.errored() is None
    assert comm.size() == 1


def test_fake_wrapper_error_injection() -> None:
    comm = FakeCommunicatorWrapper(DummyCommunicator())
    comm.report_future_error(RuntimeError("injected"))
    work = comm.allreduce(np.ones(2, dtype=np.float32))
    assert isinstance(work.exception(timeout=1.0), RuntimeError)
    assert isinstance(comm.errored(), RuntimeError)
    # only the next op fails
    np.testing.assert_allclose(
        comm.allreduce(np.ones(2, dtype=np.float32)).wait(), np.ones(2)
    )


@pytest.mark.parametrize("world_size", [1, 2, 3])
def test_reduce_scatter(store, world_size) -> None:
    n = 1000  # not divisible by 3 -> uneven chunks

    def _fn(comm, rank):
        data = np.arange(n, dtype=np.float32) + rank
        return comm.reduce_scatter(data, ReduceOp.SUM).wait(timeout=30.0)

    results = _run_ranks(store, world_size, _fn)
    expected = sum(np.arange(n, dtype=np.float32) + r for r in range(world_size))
    base, extra = divmod(n, world_size)
    off = 0
    for rank, res in enumerate(results):
        size = base + (1 if rank < extra else 0)
        np.testing.assert_allclose(res, expected[off : off + size], rtol=1e-6)
        off += size
    assert off == n


def test_reduce_scatter_avg(store) -> None:
    world_size = 2
    n = 64

    def _fn(comm, rank):
        data = np.full(n, float(rank + 1), dtype=np.float32)
        return comm.reduce_scatter(data, ReduceOp.AVG).wait(timeout=30.0)

    results = _run_ranks(store, world_size, _fn)
    for res in results:
        np.testing.assert_allclose(res, 1.5)


def test_reduce_scatter_does_not_mutate_input(store) -> None:
    def _fn(comm, rank):
        data = np.full(10, float(rank), dtype=np.float32)
        keep = data.copy()
        comm.reduce_scatter(data, ReduceOp.SUM).wait(timeout=30.0)
        np.testing.assert_array_equal(data, keep)
        return True

    assert all(_run_ranks(store, 2, _fn))


class TestNetEmu:
    """The netem-style sender pacer behind TORCHFT_NET_GBPS/RTT_MS
    (benchmarks/dcn_bench.py drives it end-to-end)."""

    def test_rate_cap_and_idle_burst_bound(self):
        from torchft_tpu.communicator import _NetEmu

        emu = _NetEmu(gbps=1.0, rtt_ms=0.0)
        # idle credit must be capped at the burst size, not accrue forever
        time.sleep(0.05)
        assert emu.allow(10 << 20) <= emu.burst
        # draining the bucket throttles the next allowance
        emu.consume(emu.allow(emu.burst))
        assert emu.allow(1 << 20) < (1 << 20)

    def test_zero_length_frames_never_gated(self, store) -> None:
        """ws=2 rings carry a zero-size chunk (1-element barrier payload
        split over 2 ranks); the pacer must not park on the empty frame —
        this wedged the first dcn_bench run."""
        import os

        def _fn(comm, rank):
            comm.barrier().wait(timeout=30.0)
            out = comm.allreduce(
                np.ones(1, dtype=np.float32), ReduceOp.SUM
            ).wait(timeout=30.0)
            return float(np.asarray(out).reshape(-1)[0])

        os.environ["TORCHFT_NET_GBPS"] = "1.0"
        os.environ["TORCHFT_NET_RTT_MS"] = "1.0"
        try:
            results = _run_ranks(store, 2, _fn)
        finally:
            os.environ.pop("TORCHFT_NET_GBPS", None)
            os.environ.pop("TORCHFT_NET_RTT_MS", None)
        assert results == [2.0, 2.0]


# ----------------------------------------------------------------------
# the per-call path stays where no ring session is offered (PR 60)
# ----------------------------------------------------------------------


@pytest.mark.parametrize(
    "comm",
    [TCPCommunicator(timeout_s=5.0), DummyCommunicator(), FakeCommunicatorWrapper(DummyCommunicator())],
    ids=["tcp", "dummy", "wrapper"],
)
def test_no_tier_but_the_native_one_offers_a_ring_session(comm) -> None:
    """The choice between the session and a ring an op is by what the
    communicator offers: no class of this file does, and none is told to by a
    knob, an environment variable or an argument."""
    import inspect

    from torchft_tpu import communicator, knobs

    assert not hasattr(comm, "ring_session")
    assert "ring_session" not in inspect.getsource(communicator)
    assert "ring_session" not in inspect.getsource(knobs) and "RING_SESSION" not in inspect.getsource(knobs)
    comm.shutdown()


def test_the_python_tier_counts_its_ring_calls_and_no_wait_for_a_push(store) -> None:
    """``lane_stats()`` carries the native tier's two new keys with this
    tier's meaning: a call a ring, and an op thread that never waits for a
    push (every buffer is an op of its own)."""

    def _fn(comm, rank):
        before = comm.lane_stats()
        for _ in range(3):
            comm.allreduce(np.ones(1000, np.float32), ReduceOp.SUM, divisor=2).wait(timeout=30.0)
        # two dtypes: a ring each
        comm.allreduce(
            [np.ones(10, np.float32), np.ones(10, np.float64)], ReduceOp.SUM
        ).wait(timeout=30.0)
        return before, comm.lane_stats()

    for before, after in _run_ranks(store, 2, _fn):
        assert before["ring_calls"] == 0 and after["ring_calls"] == 5
        assert before["ring_wait_push_s"] == after["ring_wait_push_s"] == 0.0
