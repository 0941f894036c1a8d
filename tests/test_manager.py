"""Manager state machine unit tests with a mocked control plane.

Mirrors the reference's ``torchft/manager_test.py``: the ManagerClient is
replaced with a stub returning hand-built quorum results, so every state
transition (heal, spares, commit failures, errors, timeouts) is exercised
without servers.
"""

from typing import Dict, List, Optional

import numpy as np
import pytest

from torchft_tpu.checkpointing.transport import CheckpointTransport
from torchft_tpu.communicator import DummyCommunicator, FakeCommunicatorWrapper
from torchft_tpu.manager import Manager, WorldSizeMode
from torchft_tpu.wire import ManagerQuorumResult


class StubClient:
    """Programmable ManagerClient double."""

    def __init__(self) -> None:
        self.quorum_results: List[ManagerQuorumResult] = []
        self.commit_responses: List[bool] = []
        self.quorum_calls: List[dict] = []
        self.commit_calls: List[dict] = []

    def _quorum(self, **kwargs) -> ManagerQuorumResult:
        self.quorum_calls.append(kwargs)
        return self.quorum_results.pop(0)

    def should_commit(self, group_rank, step, should_commit, timeout) -> bool:
        self.commit_calls.append(
            dict(group_rank=group_rank, step=step, should_commit=should_commit)
        )
        if self.commit_responses:
            return self.commit_responses.pop(0)
        return should_commit

    def _checkpoint_metadata(self, rank, timeout) -> str:
        return "stub-metadata"

    def close(self) -> None:
        pass


class MemoryTransport(CheckpointTransport):
    """In-memory transport double with a shared exchange slot."""

    exchange: Dict[int, object] = {}

    def __init__(self) -> None:
        self.sent: List[dict] = []
        self.disallowed = 0

    def metadata(self) -> str:
        return "memory://"

    def send_checkpoint(self, dst_ranks, step, state_dict, timeout) -> None:
        self.sent.append(dict(dst_ranks=dst_ranks, step=step))
        MemoryTransport.exchange[step] = state_dict

    def disallow_checkpoint(self) -> None:
        self.disallowed += 1

    def recv_checkpoint(self, src_rank, metadata, step, timeout):
        return MemoryTransport.exchange[step]

    def shutdown(self, wait: bool = True) -> None:
        pass


def _quorum_result(
    quorum_id: int = 1,
    replica_rank: int = 0,
    replica_world_size: int = 2,
    heal: bool = False,
    max_step: int = 0,
    max_replica_rank: Optional[int] = 0,
    max_world_size: int = 2,
    recover_src: Optional[int] = None,
    recover_dst: Optional[List[int]] = None,
    store_address: str = "127.0.0.1:0",
) -> ManagerQuorumResult:
    return ManagerQuorumResult(
        quorum_id=quorum_id,
        replica_rank=replica_rank,
        replica_world_size=replica_world_size,
        recover_src_manager_address="stub://src" if recover_src is not None else "",
        recover_src_replica_rank=recover_src,
        recover_dst_replica_ranks=recover_dst or [],
        store_address=store_address,
        max_step=max_step,
        max_replica_rank=max_replica_rank,
        max_world_size=max_world_size,
        heal=heal,
        commit_failures=0,
        replica_ids=[f"rep_{i}" for i in range(replica_world_size)],
    )


def _make_manager(
    client: StubClient,
    comm=None,
    use_async_quorum: bool = True,
    min_replica_size: int = 1,
    world_size_mode: WorldSizeMode = WorldSizeMode.DYNAMIC,
    max_retries: Optional[int] = None,
    state: Optional[dict] = None,
) -> Manager:
    state = state if state is not None else {"w": np.zeros(3)}

    def _load(s) -> None:
        state.clear()
        state.update(s)

    manager = Manager(
        comm=comm or DummyCommunicator(),
        load_state_dict=_load,
        state_dict=lambda: dict(state),
        min_replica_size=min_replica_size,
        use_async_quorum=use_async_quorum,
        world_size_mode=world_size_mode,
        max_retries=max_retries,
        checkpoint_transport=MemoryTransport(),
        _manager_client=client,  # mocked control plane
        _peer_client_factory=lambda addr: client,
        rank=0,
        world_size=1,
    )
    manager._test_state = state  # type: ignore[attr-defined]
    return manager


class TestQuorum:
    def test_happy_path_commit(self) -> None:
        client = StubClient()
        client.quorum_results.append(_quorum_result())
        manager = _make_manager(client)

        manager.start_quorum()
        manager.wait_quorum()
        assert manager.is_participating()
        assert manager.num_participants() == 2
        assert manager.current_step() == 0

        assert manager.should_commit()
        assert manager.current_step() == 1
        assert manager.batches_committed() == 2
        assert client.commit_calls[0]["should_commit"] is True

    def test_comm_reconfigured_only_on_quorum_change(self) -> None:
        client = StubClient()
        comm = DummyCommunicator()
        client.quorum_results.append(_quorum_result(quorum_id=1))
        client.quorum_results.append(_quorum_result(quorum_id=1, max_step=1))
        client.quorum_results.append(_quorum_result(quorum_id=2, max_step=2))
        manager = _make_manager(client, comm=comm)

        manager.start_quorum()
        manager.wait_quorum()
        assert comm.configure_count == 1
        manager.should_commit()

        manager.start_quorum()
        manager.wait_quorum()
        assert comm.configure_count == 1  # same quorum id: no reconfigure
        manager.should_commit()

        manager.start_quorum()
        manager.wait_quorum()
        assert comm.configure_count == 2

    def test_healing_async_quorum(self) -> None:
        """Healer stages the peer checkpoint, skips participation, applies at
        commit time, and jumps to max_step."""
        client = StubClient()
        MemoryTransport.exchange[5] = {
            "user": {"default": {"w": np.full(3, 42.0)}},
            "torchft": {"step": 5, "batches_committed": 10},
        }
        client.quorum_results.append(
            _quorum_result(
                replica_rank=1,
                heal=True,
                max_step=5,
                max_replica_rank=None,
                max_world_size=1,
                recover_src=0,
            )
        )
        manager = _make_manager(client)

        manager.start_quorum()
        manager.wait_quorum()
        assert manager._healing
        assert not manager.is_participating()
        assert manager.num_participants() == 1
        # non-participants contribute zeros to the collective
        g = np.ones(4)
        out = manager.allreduce(g).wait(timeout=5.0)
        np.testing.assert_array_equal(out, 0)

        assert manager.should_commit()
        # state applied + step jumped
        assert manager.current_step() == 6  # healed to 5, then committed
        np.testing.assert_array_equal(
            manager._test_state["w"], np.full(3, 42.0)
        )

    def test_send_checkpoint_to_recovering_peers(self) -> None:
        client = StubClient()
        client.quorum_results.append(_quorum_result(recover_dst=[1], max_step=3))
        manager = _make_manager(client)
        manager.start_quorum()
        manager.wait_quorum()
        transport = manager._checkpoint_transport
        assert transport.sent == [dict(dst_ranks=[1], step=3)]

    def test_sync_quorum_participation(self) -> None:
        """With use_async_quorum=False everyone participates (heal completes
        before the step)."""
        client = StubClient()
        MemoryTransport.exchange[2] = {
            "user": {"default": {"w": np.full(3, 7.0)}},
            "torchft": {"step": 2, "batches_committed": 4},
        }
        client.quorum_results.append(
            _quorum_result(
                replica_rank=1,
                replica_world_size=3,
                heal=True,
                max_step=2,
                max_replica_rank=None,
                max_world_size=2,
                recover_src=0,
            )
        )
        manager = _make_manager(client, use_async_quorum=False)
        manager.start_quorum()
        assert not manager._healing  # applied eagerly
        assert manager.is_participating()
        assert manager.num_participants() == 3
        np.testing.assert_array_equal(manager._test_state["w"], np.full(3, 7.0))
        assert manager.current_step() == 2

    def test_fixed_with_spares(self) -> None:
        client = StubClient()
        client.quorum_results.append(
            _quorum_result(
                replica_rank=2,
                replica_world_size=3,
                max_replica_rank=2,
                max_world_size=3,
            )
        )
        manager = _make_manager(
            client,
            min_replica_size=2,
            world_size_mode=WorldSizeMode.FIXED_WITH_SPARES,
        )
        manager.start_quorum()
        manager.wait_quorum()
        # rank 2 with min_replica_size=2 → parked as a spare
        assert manager.num_participants() == 2
        assert not manager.is_participating()


class TestAllreduce:
    def test_averages_by_participants(self) -> None:
        client = StubClient()
        client.quorum_results.append(_quorum_result(max_world_size=4))
        manager = _make_manager(client)
        manager.start_quorum()
        # DummyCommunicator returns input; AVG = input / 4
        out = manager.allreduce(np.full(3, 8.0)).wait(timeout=5.0)
        np.testing.assert_array_equal(out, np.full(3, 2.0))

    def test_errored_short_circuits(self) -> None:
        client = StubClient()
        client.quorum_results.append(_quorum_result())
        manager = _make_manager(client)
        manager.start_quorum()
        manager.report_error(RuntimeError("boom"))
        data = np.ones(3)
        out = manager.allreduce(data).wait(timeout=5.0)
        np.testing.assert_array_equal(out, data)  # unchanged passthrough

    def test_comm_error_swallowed_and_recorded(self) -> None:
        client = StubClient()
        client.quorum_results.append(_quorum_result())
        client.commit_responses.append(False)
        comm = FakeCommunicatorWrapper(DummyCommunicator())
        manager = _make_manager(client, comm=comm)
        manager.start_quorum()
        manager.wait_quorum()
        comm.report_future_error(RuntimeError("injected collective failure"))
        data = np.ones(3)
        out = manager.allreduce(data).wait(timeout=5.0)  # must not raise
        np.testing.assert_array_equal(out, data)
        assert not manager.should_commit()
        assert manager.current_step() == 0
        assert client.commit_calls[0]["should_commit"] is False


    def test_should_commit_fences_inflight_collectives(self) -> None:
        """A collective failure landing after the vote must not let this
        replica commit (analog of the reference's stream sync,
        ``manager.py:888-893``)."""
        import threading as _threading
        import time as _time
        from concurrent.futures import Future

        from torchft_tpu.work import Work

        class SlowFailingCommunicator(DummyCommunicator):
            def allreduce(self, buffers, op=None, in_place=False, divisor=None):  # type: ignore[override]
                fut: Future = Future()

                def _later() -> None:
                    _time.sleep(0.3)
                    fut.set_exception(RuntimeError("late collective failure"))

                _threading.Thread(target=_later, daemon=True).start()
                return Work(fut)

        client = StubClient()
        client.quorum_results.append(_quorum_result())
        client.commit_responses.append(False)
        manager = _make_manager(client, comm=SlowFailingCommunicator())
        manager.start_quorum()
        manager.allreduce(np.ones(3))  # deliberately not waited
        assert manager.errored() is None  # failure hasn't landed yet
        assert not manager.should_commit()
        assert manager.errored() is not None
        assert client.commit_calls[0]["should_commit"] is False

    def test_should_commit_waits_slow_successful_work(self) -> None:
        import threading as _threading
        import time as _time
        from concurrent.futures import Future

        from torchft_tpu.work import Work

        class SlowCommunicator(DummyCommunicator):
            def allreduce(self, buffers, op=None, in_place=False, divisor=None):  # type: ignore[override]
                fut: Future = Future()
                averaged = super().allreduce(buffers, divisor=divisor).wait()

                def _later() -> None:
                    _time.sleep(0.3)
                    fut.set_result(averaged)

                _threading.Thread(target=_later, daemon=True).start()
                return Work(fut)

        client = StubClient()
        client.quorum_results.append(_quorum_result())
        manager = _make_manager(client, comm=SlowCommunicator())
        manager.start_quorum()
        work = manager.allreduce(np.full(3, 8.0))
        assert manager.should_commit()
        # fencing implies the work is complete by the time the vote returns
        assert work.done()
        np.testing.assert_array_equal(work.wait(timeout=0), np.full(3, 4.0))


class TestShouldCommit:
    def test_not_enough_replicas_votes_false(self) -> None:
        client = StubClient()
        client.quorum_results.append(_quorum_result(max_world_size=1))
        client.commit_responses.append(False)
        manager = _make_manager(client, min_replica_size=2)
        manager.start_quorum()
        manager.wait_quorum()
        assert not manager.should_commit()
        assert client.commit_calls[0]["should_commit"] is False

    def test_max_retries_raises(self) -> None:
        client = StubClient()
        for _ in range(2):
            client.quorum_results.append(_quorum_result())
            client.commit_responses.append(False)
        manager = _make_manager(client, max_retries=1)
        manager.start_quorum()
        assert not manager.should_commit()  # failure 1 == max_retries, ok
        manager.start_quorum()
        with pytest.raises(RuntimeError, match="max_retries"):
            manager.should_commit()  # failure 2 > max_retries

    def test_commit_failure_counter_resets(self) -> None:
        client = StubClient()
        client.quorum_results.append(_quorum_result())
        client.commit_responses.append(False)
        client.quorum_results.append(_quorum_result(quorum_id=2))
        client.commit_responses.append(True)
        manager = _make_manager(client, max_retries=1)
        manager.start_quorum()
        manager.wait_quorum()
        assert not manager.should_commit()
        assert manager._commit_failures == 1
        manager.start_quorum()
        manager.wait_quorum()
        # commit_failures rides the next quorum request
        assert client.quorum_calls[1]["commit_failures"] == 1
        assert manager.should_commit()
        assert manager._commit_failures == 0

    def test_state_dict_roundtrip(self) -> None:
        client = StubClient()
        client.quorum_results.append(_quorum_result())
        manager = _make_manager(client)
        manager.start_quorum()
        manager.should_commit()
        sd = manager.state_dict()
        assert sd == {"step": 1, "batches_committed": 2}

        client.quorum_results.append(_quorum_result())
        manager2 = _make_manager(client)
        manager2.load_state_dict(sd)
        assert manager2.current_step() == 1
        assert manager2.batches_committed() == 2


def test_allreduce_default_does_not_mutate_input() -> None:
    """Without in_place, caller buffers (e.g. LocalSGD's live host params)
    must survive the collective unchanged."""
    client = StubClient()
    client.quorum_results.append(_quorum_result())
    manager = _make_manager(client)
    manager.start_quorum()
    data = np.full(8, 6.0)
    keep = data.copy()
    out = manager.allreduce(data).wait(timeout=5.0)
    np.testing.assert_array_equal(data, keep)  # input untouched
    np.testing.assert_array_equal(out, keep / 2)  # AVG over 2 participants


def _div_input(dtype_name: str) -> np.ndarray:
    """Every 16-bit pattern for the 16-bit floats (NaNs, infinities,
    subnormals, both zeros); seeded values with edge cases for the rest."""
    import ml_dtypes

    dtype = np.dtype(getattr(ml_dtypes, dtype_name, dtype_name))
    if dtype.itemsize == 2:
        return np.arange(1 << 16, dtype=np.uint16).view(dtype)
    rng = np.random.default_rng(27)
    if np.issubdtype(dtype, np.integer):
        info = np.iinfo(dtype)
        edge = np.array([0, 1, -1, 256, -257, info.max, info.min + 1], dtype=dtype)
        return np.concatenate([rng.integers(-(1 << 20), 1 << 20, 4096).astype(dtype), edge])
    edge = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, np.finfo(dtype).tiny / 2, np.finfo(dtype).max], dtype=dtype)
    return np.concatenate([(rng.standard_normal(4096) * 1e3).astype(dtype), edge])


@pytest.mark.parametrize("n", [1, 2, 3, 7, 257, 1000])
@pytest.mark.parametrize("dtype_name", ["bfloat16", "float16", "float32", "float64", "int32", "int64"])
def test_div_is_bit_equal_to_the_out_of_place_formula(dtype_name: str, n: int) -> None:
    """``_div`` averages a reduced buffer with no array of the payload's
    size beside it, and gives bit for bit what ``(a / n).astype(a.dtype)``
    (integers ``a // n``) gave: no configuration can train differently."""
    from torchft_tpu.communicator import _div

    a = _div_input(dtype_name)
    keep = a.copy()
    with np.errstate(all="ignore"):
        want = a // n if np.issubdtype(a.dtype, np.integer) else (a / n).astype(a.dtype)
        fresh = _div(a, n)
        assert a.tobytes() == keep.tobytes()  # out-of-place: the input is untouched
        mine = a.copy()
        written = _div(mine, n, out=mine)
    assert fresh.dtype == written.dtype == want.dtype == a.dtype
    assert np.shares_memory(written, mine)
    if n == 1:
        # no pass, not even a copy: every number is the formula's (a NaN
        # keeps the payload bits a trip through float32 would have quieted)
        assert fresh is a and written is mine
        with np.errstate(invalid="ignore"):
            assert np.array_equal(a, want, equal_nan=a.dtype.kind != "i")
    else:
        assert not np.shares_memory(fresh, a)
        assert fresh.tobytes() == want.tobytes()
        assert written.tobytes() == want.tobytes()


def test_allreduce_in_place_averages_into_the_bucket_itself() -> None:
    """``in_place=True`` (the ddp bucket path): the average lands in the
    reduced buffer, and nothing near the payload's size is allocated on the
    way (the out-of-place formula peaked at three times the payload)."""
    import tracemalloc

    import ml_dtypes

    client = StubClient()
    client.quorum_results.append(_quorum_result())
    manager = _make_manager(client)
    manager.start_quorum()
    manager.wait_quorum()
    rng = np.random.default_rng(27)
    bucket = rng.standard_normal(16 << 20, dtype=np.float32).astype(ml_dtypes.bfloat16)  # 32 MB
    want = (bucket / 2).astype(bucket.dtype)
    tracemalloc.start()  # the bucket and `want` were made before: not counted
    try:
        out = manager.allreduce(bucket, in_place=True).wait(timeout=30.0)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert peak < bucket.nbytes // 4, (peak, bucket.nbytes)
    assert np.shares_memory(out, bucket)
    assert out.tobytes() == want.tobytes()


def test_allreduce_in_place_leaves_a_read_only_buffer_alone() -> None:
    """A buffer the communicator let through read-only is not ours to write."""
    client = StubClient()
    client.quorum_results.append(_quorum_result())
    manager = _make_manager(client)
    manager.start_quorum()
    data = np.full(8, 6.0, dtype=np.float32)
    data.flags.writeable = False
    out = manager.allreduce(data, in_place=True).wait(timeout=5.0)
    np.testing.assert_array_equal(data, np.full(8, 6.0, dtype=np.float32))
    np.testing.assert_array_equal(out, np.full(8, 3.0, dtype=np.float32))
