"""``Manager.allreduce`` hands the communicator its participant count for a
divisor (ISSUE 40): the ring returns the average, the done-callback under the
span ``tpuft/manager/normalize`` only says so (``in_ring=1``), and a path
that still returns sums (the quantized ring) is divided there as before.

Managers on stub control planes, real communicators over loopback."""

from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, List, Optional

import ml_dtypes
import numpy as np
import pytest

from torchft_tpu import native
from torchft_tpu.communicator import ReduceOp, TCPCommunicator, _div
from torchft_tpu.manager import Manager, WorldSizeMode
from torchft_tpu.obs import spans as obs_spans

from tests.test_manager import StubClient, _make_manager, _quorum_result

TIERS = {"python": TCPCommunicator, "cpp": native.CppCommunicator}
NORMALIZE = "tpuft/manager/normalize"


@pytest.fixture()
def store():
    server = native.CppStoreServer("127.0.0.1:0")
    yield server
    server.shutdown()


@pytest.fixture()
def spans():
    """The process's span buffer, on and empty for one test."""
    obs_spans.configure(True)
    obs_spans.clear()
    yield lambda: [s for s in obs_spans.snapshot() if s["name"] == NORMALIZE]
    obs_spans.configure(None)
    obs_spans.clear()


def _ring(
    store,
    comms: List[Any],
    step: Callable[[Manager, int], Any],
    fields: Optional[dict] = None,
    **manager_kw: Any,
) -> List[Any]:
    """One quorum round of ``len(comms)`` Managers on one ring: replica r
    runs ``step(manager, r)`` on a thread of its own.  ``fields`` are set on
    every replica's quorum result."""
    built: List[Manager] = []

    def _one(r: int) -> Any:
        client = StubClient()
        result = _quorum_result(
            replica_rank=r,
            replica_world_size=len(comms),
            max_replica_rank=r,
            max_world_size=len(comms),
            store_address=f"127.0.0.1:{store.port}",
        )
        for key, value in (fields or {}).items():
            setattr(result, key, value)
        client.quorum_results.append(result)
        manager = _make_manager(client, comm=comms[r], **manager_kw)
        built.append(manager)
        manager.start_quorum()
        return step(manager, r)

    try:
        with ThreadPoolExecutor(max_workers=len(comms)) as pool:
            return list(pool.map(_one, range(len(comms))))
    finally:
        for manager in built:
            manager.shutdown()


def _grad(dtype: Any, r: int, count: int = 1001) -> np.ndarray:
    rng = np.random.default_rng([r, count])
    if np.dtype(dtype).kind == "i":
        return rng.integers(-1000, 1000, count).astype(dtype)
    return (rng.standard_normal(count) * 100).astype(dtype)


@pytest.mark.parametrize("in_place", [False, True], ids=["out_of_place", "in_place"])
@pytest.mark.parametrize("dtype", [ml_dtypes.bfloat16, np.float32, np.int32], ids=["bfloat16", "float32", "int32"])
@pytest.mark.parametrize("tier", sorted(TIERS))
def test_average_is_over_the_participants_not_the_ring(store, spans, tier: str, dtype: Any, in_place: bool) -> None:
    """A spare rides the ring of three with zeros and is not counted: the
    divisor is 2, bit for bit ``_div`` of the ring's sum, and every replica
    (the spare too) is handed the same average."""
    grads = [_grad(dtype, r) for r in range(3)]
    want = _div(grads[0] + grads[1], 2)  # two contributions: the sum rounds once

    def _step(manager: Manager, r: int) -> Any:
        mine = grads[r].copy()
        out = manager.allreduce(mine, in_place=in_place).wait(timeout=20.0)
        assert manager.num_participants() == 2
        assert manager.is_participating() == (r < 2)
        if not in_place:
            assert mine.tobytes() == grads[r].tobytes()
        elif r < 2:  # the spare's own buffer was swapped for zeros
            assert np.shares_memory(out, mine)
        assert manager.should_commit()
        return out

    outs = _ring(
        store,
        [TIERS[tier](timeout_s=10.0) for _ in range(3)],
        _step,
        world_size_mode=WorldSizeMode.FIXED_WITH_SPARES,
        min_replica_size=2,
    )
    for out in outs:
        assert out.dtype == want.dtype and out.tobytes() == want.tobytes()
    # one span a collective (three replicas, one collective each), all averaged in the ring
    assert [s["attrs"]["in_ring"] for s in spans()] == [1, 1, 1]
    assert all(s["attrs"]["bytes"] == want.nbytes for s in spans())


@pytest.mark.parametrize("tier", sorted(TIERS))
def test_capacity_weighted_contributions_meet_the_rings_division(store, tier: str) -> None:
    """The pre-scale acts on the INPUT (w_i x N), the ring divides by N."""
    caps = [0.5, 1.0]
    grads = [_grad(np.float32, r) for r in range(2)]
    scaled = [(g * np.float32(c / sum(caps) * 2)).astype(np.float32) for g, c in zip(grads, caps)]
    want = _div(scaled[0] + scaled[1], 2)

    def _step(manager: Manager, r: int) -> Any:
        out = manager.allreduce(grads[r].copy()).wait(timeout=20.0)
        assert manager._capacity_weight_scale() == pytest.approx(caps[r] / sum(caps) * 2)
        assert manager.should_commit()
        return out

    outs = _ring(
        store, [TIERS[tier](timeout_s=10.0) for _ in range(2)], _step, fields=dict(participant_capacities=caps)
    )
    for out in outs:
        np.testing.assert_allclose(out, want, rtol=1e-6)
    assert outs[0].tobytes() == outs[1].tobytes()


@pytest.mark.parametrize("tier", sorted(TIERS))
def test_quantized_path_still_divides_in_the_callback(store, spans, tier: str, monkeypatch) -> None:
    """``allreduce_quantized`` returns sums: the callback divides them and
    the span says ``in_ring=0``."""
    monkeypatch.setenv("TORCHFT_QUANT_DEVICE_REDUCE", "0")
    grads = [_grad(np.float32, r, 64 * 1024) for r in range(2)]
    sums: List[Optional[np.ndarray]] = [None, None]

    def _step(manager: Manager, r: int) -> Any:
        from torchft_tpu.collectives import allreduce_quantized

        out = manager.allreduce(grads[r].copy(), should_quantize=True).wait(timeout=30.0)
        # the same collective again, bare: what the callback was handed
        sums[r] = np.asarray(allreduce_quantized(manager._comm, grads[r].copy()).wait(timeout=30.0))
        assert manager.should_commit()
        return out

    outs = _ring(store, [TIERS[tier](timeout_s=20.0) for _ in range(2)], _step)
    for out, summed in zip(outs, sums):
        assert out.tobytes() == _div(summed, 2).tobytes()
        np.testing.assert_allclose(out, (grads[0] + grads[1]) / 2, atol=5.0)
    assert [s["attrs"]["in_ring"] for s in spans()] == [0, 0]


class _SumsAsBefore(TCPCommunicator):
    """A peer from before the divisor: it rings plain sums (the tag window at
    0) and divides what comes back."""

    def allreduce(self, buffers, op=ReduceOp.SUM, in_place=False, divisor=None):  # type: ignore[override]
        return super().allreduce(buffers, op, in_place=in_place).then(lambda summed: _div(summed, divisor))


@pytest.mark.parametrize("tier", sorted(TIERS))
def test_a_peer_without_the_averaging_ring_fails_the_step_and_corrupts_nothing(store, tier: str) -> None:
    """The op fails on both sides well inside the timeout, each replica is
    handed its own input back (never sums for some chunks and averages for
    others), and neither votes to commit."""
    import time

    grads = [_grad(np.float32, r) for r in range(2)]

    def _step(manager: Manager, r: int) -> Any:
        began = time.monotonic()
        out = manager.allreduce(grads[r].copy()).wait(timeout=20.0)
        took = time.monotonic() - began
        assert manager.errored() is not None
        assert not manager.should_commit()
        return out, took

    outs = _ring(store, [TIERS[tier](timeout_s=8.0), _SumsAsBefore(timeout_s=8.0)], _step)
    for (out, took), grad in zip(outs, grads):
        assert out.tobytes() == grad.tobytes()
        assert took < 8.0


def test_not_participating_alone_hands_back_zeros_averaged(spans) -> None:
    """The zero contribution acts on the INPUT, as before (passthrough
    communicator: the "sum" is the zeros themselves)."""
    client = StubClient()
    client.quorum_results.append(_quorum_result(max_replica_rank=None, max_world_size=1, heal=False))
    manager = _make_manager(client)
    manager.start_quorum()
    data = np.full(5, 3.0, np.float32)
    out = manager.allreduce(data).wait(timeout=5.0)
    assert not manager.is_participating()
    np.testing.assert_array_equal(out, np.zeros(5, np.float32))
    np.testing.assert_array_equal(data, np.full(5, 3.0, np.float32))
    assert [s["attrs"]["in_ring"] for s in spans()] == [1]
    manager.shutdown()
