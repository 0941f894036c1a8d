"""The ring hands back the average (ISSUE 40): ``allreduce(divisor=n)`` is
bit for bit ``_div(the ring's sum, n)``, whichever tier divides.

The rank that owns a chunk at the end of the reduce phase divides it before
the allgather phase sends it round: ``native/comm.h`` in the add of the
reduce phase's last step (``reduce_buffer`` with the divisor; a ring of one
member in ``average_buffer``'s pass), ``communicator._ring_allreduce`` with
``_div`` on the owned view between the phases.  One parametrised test holds
the two to the same bits on every dtype the Manager averages, for divisors
that are no power of two and one that is no bfloat16, in rings of one, two
and three whose size does not divide the element count and a native one of
four (three reduce steps: a division in any but the last is off by a
factor), on each tier and on a ring of one of each; the rest is the
contract's edges: a passthrough never writes what it was handed, and a peer
that expects sums fails the op and the vote."""

import ctypes
from typing import Any, Callable, List

import ml_dtypes
import numpy as np
import pytest

from torchft_tpu import native
from torchft_tpu.communicator import (
    CommunicatorError,
    DummyCommunicator,
    FakeCommunicatorWrapper,
    ManagedCommunicator,
    ReduceOp,
    _div,
)

from tests.test_native import _run_mixed_ranks

BF16 = np.dtype(ml_dtypes.bfloat16)
DTYPES = {
    "bfloat16": BF16,
    "float32": np.dtype(np.float32),
    "float64": np.dtype(np.float64),
    "int32": np.dtype(np.int32),
    "int64": np.dtype(np.int64),
}
# 1,001 elements: neither 2 nor 3 divides them, so a ring's chunks differ
COUNT = 1001


@pytest.fixture()
def store():
    server = native.CppStoreServer("127.0.0.1:0")
    yield server
    server.shutdown()


def _contribution(dtype: np.dtype, rank: int, count: int = COUNT) -> np.ndarray:
    """Rank ``rank``'s gradient: random values of both signs, and at fixed
    places what a division must not get wrong (the floor of a negative sum;
    infinities that meet, and that cancel to a NaN; a NaN; subnormals, whose
    quotient rounds to zero or stays subnormal; the largest finite value)."""
    rng = np.random.default_rng([rank, count, dtype.itemsize])
    if dtype.kind == "i":
        a = rng.integers(-(1 << 20), 1 << 20, count).astype(dtype)
        a[:6] = [-1, -7, 7, 0, -256, np.iinfo(dtype).max // 4]
        return a
    a = (rng.standard_normal(count) * 1e3).astype(dtype)
    finfo = ml_dtypes.finfo(dtype)
    tiny = float(finfo.smallest_subnormal)
    edge = [np.inf, -np.inf, np.nan, tiny, tiny * 3, -tiny * 5, 0.0, -0.0, float(finfo.max) / 4]
    # infinities that cancel: a NaN from the second rank on
    edge = np.array([np.inf if rank % 2 else -np.inf] + edge).astype(dtype)[:count]
    a[: len(edge)] = edge
    return a


def _payloads(rank: int) -> List[Any]:
    """One array of each dtype, then several of mixed dtypes in one call (a
    ring a dtype; on the native tier the arrays of one dtype ride as
    scattered iovec segments), then fewer elements than a ring of three has
    positions (an empty chunk)."""
    single = [_contribution(dt, rank) for dt in DTYPES.values()]
    mixed = [
        _contribution(BF16, rank, 333),
        _contribution(DTYPES["float32"], rank, 17),
        _contribution(BF16, rank, 5).reshape(5, 1),
        _contribution(DTYPES["int64"], rank, 101),
        _contribution(DTYPES["float64"], rank, 3),
        _contribution(DTYPES["float32"], rank, 64).reshape(8, 8),
    ]
    return single + [mixed, _contribution(BF16, rank, 2)]


def _as_list(x: Any) -> List[np.ndarray]:
    return [x] if isinstance(x, np.ndarray) else list(x)


def _copy(x: Any) -> Any:
    return x.copy() if isinstance(x, np.ndarray) else [a.copy() for a in x]


def _run(store, tiers: List[str], fn: Callable, prefix: str, timeout_s: float = 30.0) -> List[Any]:
    """One rendezvous, rank r on tier ``tiers[r]``."""
    cpp_ranks = {r for r, tier in enumerate(tiers) if tier == "cpp"}
    return _run_mixed_ranks(store, len(tiers), cpp_ranks, fn, prefix, timeout_s)


def _tiers(tier: str, world: int) -> List[str]:
    if tier == "mixed":  # the owner of the LAST chunk divides natively, the others in numpy
        return ["python"] * (world - 1) + ["cpp"]
    return [tier] * world


RINGS = [(tier, world) for tier in ("cpp", "python", "mixed") for world in (1, 2, 3) if (tier, world) != ("mixed", 1)]
# four native members: three reduce steps, of which the LAST alone divides
RINGS.append(("cpp", 4))


@pytest.mark.parametrize("n", [2, 3, 5, 257])
@pytest.mark.parametrize("tier,world", RINGS)
def test_divisor_is_div_of_the_rings_sum_bit_for_bit(store, tier: str, world: int, n: int) -> None:
    def _ops(comm, rank):
        for p, data in enumerate(_payloads(rank)):
            with np.errstate(all="ignore"):
                summed = comm.allreduce(_copy(data), ReduceOp.SUM).wait(timeout=30.0)
                want = [_div(a, n) for a in _as_list(summed)]
            # out of place: the caller's buffers are untouched, read-only ones too
            kept = _copy(data)
            for a in _as_list(kept):
                a.flags.writeable = False
            got = comm.allreduce(kept, ReduceOp.SUM, divisor=n).wait(timeout=30.0)
            assert isinstance(got, np.ndarray) == isinstance(data, np.ndarray)
            for a, b, w, g in zip(_as_list(kept), _as_list(data), want, _as_list(got)):
                assert a.tobytes() == b.tobytes(), (p, "the input was written")
                assert not np.shares_memory(a, g)
                assert g.dtype == w.dtype and g.shape == a.shape
                assert g.tobytes() == w.tobytes(), (p, g.dtype, np.flatnonzero(g.view(np.uint8) != w.view(np.uint8))[:8])
            # in place: the average lands in the buffers the caller gave up
            # (the Python tier concatenates several arrays of one dtype into
            # a ring buffer of its own, as it did before)
            mine = _copy(data)
            got = comm.allreduce(mine, ReduceOp.SUM, in_place=True, divisor=n).wait(timeout=30.0)
            lands = tiers[rank] == "cpp" or isinstance(data, np.ndarray) or world == 1
            for a, w, g in zip(_as_list(mine), want, _as_list(got)):
                assert g.tobytes() == w.tobytes(), (p, g.dtype)
                assert not lands or (np.shares_memory(a, g) and a.tobytes() == w.tobytes())
        return True

    tiers = _tiers(tier, world)
    assert all(_run(store, tiers, _ops, f"avg_{tier}_{world}_{n}"))


@pytest.mark.parametrize("lanes", [1, 4])
@pytest.mark.parametrize("tier,world", [r for r in RINGS if r[1] > 1])
def test_divisor_on_a_striped_ring_of_many_quanta(store, tier: str, world: int, lanes: int, monkeypatch) -> None:
    """10 MB of bfloat16 and a list of float32 arrays: the reduce phase runs a
    lane and a 4 MB quantum at a time, and the owned chunk that is divided
    after it lies over scattered segments."""
    monkeypatch.setenv("TORCHFT_RING_LANES", str(lanes))
    monkeypatch.setenv("TORCHFT_RING_FRAME_KB", "64")

    def _ops(comm, rank):
        big = _contribution(BF16, rank, 5_000_003)
        parts = [_contribution(DTYPES["float32"], rank, n) for n in (300_001, 7, 200_003)]
        for data in (big, parts):
            with np.errstate(all="ignore"):
                summed = comm.allreduce(_copy(data), ReduceOp.SUM).wait(timeout=60.0)
                want = [_div(a, 3) for a in _as_list(summed)]
            got = comm.allreduce(_copy(data), ReduceOp.SUM, in_place=True, divisor=3).wait(timeout=60.0)
            for w, g in zip(want, _as_list(got)):
                assert g.tobytes() == w.tobytes()
        return True

    assert all(_run(store, _tiers(tier, world), _ops, f"big_{tier}_{world}_{lanes}", timeout_s=60.0))


@pytest.mark.parametrize("lanes", [1, 4])
@pytest.mark.parametrize("tier,world", [("cpp", 2), ("cpp", 3), ("mixed", 2), ("mixed", 3)])
def test_divisor_where_a_quantum_straddles_a_segment_boundary(store, tier: str, world: int, lanes: int, monkeypatch) -> None:
    """Three float32 arrays of 6, 5.2 and 6.8 MB in one call, scattered
    segments of one ring on the native tier: every owned chunk holds several
    4 MiB quanta a lane at one lane, and at either lane count a boundary
    between two arrays lies INSIDE a quantum of the last reduce step, so the
    add that divides is cut in two there and each part must divide."""
    monkeypatch.setenv("TORCHFT_RING_LANES", str(lanes))
    monkeypatch.setenv("TORCHFT_RING_FRAME_KB", "64")

    def _ops(comm, rank):
        data = [_contribution(DTYPES["float32"], rank, n) for n in (1_500_001, 1_300_003, 1_700_001)]
        with np.errstate(all="ignore"):
            summed = comm.allreduce(_copy(data), ReduceOp.SUM).wait(timeout=60.0)
            want = [_div(a, 3) for a in summed]
        got = comm.allreduce(data, ReduceOp.SUM, in_place=True, divisor=3).wait(timeout=60.0)
        for w, g in zip(want, got):
            assert g.tobytes() == w.tobytes()
        return True

    assert all(_run(store, _tiers(tier, world), _ops, f"straddle_{tier}_{world}_{lanes}", timeout_s=60.0))


def _ring_seconds(comm) -> dict:
    """The op thread's seconds in the stand-alone division pass and all lanes'
    in the reduce's add: ``lane_stats()``'s, and for a world of one member,
    which it hands nothing, the native epoch's counters themselves."""
    stats = comm.lane_stats()
    if stats:
        return {"ring_average_s": stats["ring_average_s"], "lane_add_s": sum(stats["lane_add_s"])}
    cap = 64
    lane = [(ctypes.c_uint64 * cap)() for _ in range(6)]
    ring_ns, floor = (ctypes.c_uint64 * 4)(), ctypes.c_uint64()
    comm._lib.tpuft_comm_lane_stats(comm._h, *lane, cap, ctypes.byref(floor), ring_ns)
    return {"ring_average_s": ring_ns[1] / 1e9, "lane_add_s": sum(lane[4]) / 1e9}


@pytest.mark.parametrize("world", [1, 2, 3])
def test_the_native_ring_divides_in_its_add_and_a_ring_of_one_in_a_pass(store, world: int) -> None:
    """The account that says where the division ran: a native ring of two or
    three members that averages adds nothing to ``ring_average_s`` (the
    stand-alone pass) and grows ``lane_add_s`` (the last reduce step's add
    divides); a ring of ONE member has no add, and its pass is counted."""

    def _ops(comm, rank):
        data = _contribution(BF16, rank, 2_000_003)
        comm.allreduce(data.copy(), ReduceOp.SUM).wait(timeout=30.0)
        before = _ring_seconds(comm)
        comm.allreduce(data, ReduceOp.SUM, in_place=True, divisor=3).wait(timeout=30.0)
        after = _ring_seconds(comm)
        if world == 1:
            assert after["ring_average_s"] > before["ring_average_s"] == 0.0
            assert after["lane_add_s"] == 0.0
        else:
            assert after["ring_average_s"] == before["ring_average_s"] == 0.0
            assert after["lane_add_s"] > before["lane_add_s"] > 0.0
        return True

    assert all(_run(store, ["cpp"] * world, _ops, f"account_{world}"))


@pytest.mark.parametrize("tier,world", RINGS)
def test_avg_is_the_world_size_for_a_divisor_and_one_divides_nothing(store, tier: str, world: int) -> None:
    """``ReduceOp.AVG`` stays what it is, by the same mechanism; a divisor of
    1 is the sum itself (no pass, the sum ring's frames)."""

    def _ops(comm, rank):
        for dtype in DTYPES.values():
            data = _contribution(dtype, rank)
            with np.errstate(all="ignore"):
                summed = comm.allreduce(data.copy(), ReduceOp.SUM).wait(timeout=30.0)
                avg = comm.allreduce(data.copy(), ReduceOp.AVG).wait(timeout=30.0)
                assert avg.tobytes() == _div(summed, world).tobytes(), dtype
            one = comm.allreduce(data.copy(), ReduceOp.SUM, divisor=1).wait(timeout=30.0)
            assert one.tobytes() == summed.tobytes(), dtype
        return True

    assert all(_run(store, _tiers(tier, world), _ops, f"avgop_{tier}_{world}"))


@pytest.mark.parametrize("tier", ["python", "cpp", "dummy"])
@pytest.mark.parametrize(
    "op,divisor",
    [(ReduceOp.AVG, 2), (ReduceOp.MAX, 2), (ReduceOp.MIN, 3), (ReduceOp.SUM, 0), (ReduceOp.SUM, -2)],
    ids=["avg_and_divisor", "max", "min", "zero", "negative"],
)
def test_a_divisor_goes_with_sum_alone(store, tier, op, divisor) -> None:
    def _ops(comm, rank):
        with pytest.raises(ValueError):
            comm.allreduce(np.ones(4, np.float32), op, divisor=divisor).wait(timeout=5.0)
        return True

    if tier == "dummy":
        assert _ops(DummyCommunicator(), 0)
    else:
        assert all(_run(store, [tier], _ops, f"bad_{tier}"))


@pytest.mark.parametrize("in_place", [False, True], ids=["out_of_place", "in_place"])
@pytest.mark.parametrize("wrapped", [False, True], ids=["dummy", "fake_wrapper"])
@pytest.mark.parametrize("dtype_name", sorted(DTYPES))
def test_passthrough_divides_out_of_place_what_it_was_handed(dtype_name: str, wrapped: bool, in_place: bool) -> None:
    """A communicator with no ring of its own returns the caller's buffer as
    the "sum": the average is a new array unless the caller gave the buffer
    up AND it can be written; a read-only buffer is never written."""
    comm = DummyCommunicator(rank=0, world_size=2)
    if wrapped:
        comm = FakeCommunicatorWrapper(comm)
    data = _contribution(DTYPES[dtype_name], 0)
    keep = data.copy()
    with np.errstate(all="ignore"):
        want = _div(keep, 3)
        out = comm.allreduce(data, ReduceOp.SUM, in_place=in_place, divisor=3).wait(timeout=5.0)
        assert out.tobytes() == want.tobytes()
        assert np.shares_memory(out, data) == in_place
        assert in_place or data.tobytes() == keep.tobytes()
        # a list, and a read-only member of it, whatever in_place says
        frozen = keep.copy()
        frozen.flags.writeable = False
        outs = comm.allreduce([frozen, keep.copy()], ReduceOp.SUM, in_place=in_place, divisor=3).wait(timeout=5.0)
    assert [o.tobytes() for o in outs] == [want.tobytes()] * 2
    assert frozen.tobytes() == keep.tobytes() and not np.shares_memory(outs[0], frozen)
    # no divisor, a divisor of 1 and AVG (one contribution): the buffer itself, as before
    assert comm.allreduce(data, ReduceOp.SUM).wait(timeout=5.0) is data
    assert comm.allreduce(data, ReduceOp.SUM, divisor=1).wait(timeout=5.0) is data
    assert comm.allreduce(data, ReduceOp.AVG).wait(timeout=5.0) is data


def test_managed_communicator_takes_no_divisor() -> None:
    """Its ``allreduce`` IS the Manager's, which averages over participants."""

    class _Manager:
        def allreduce(self, buffers):
            return "the manager's"

    comm = ManagedCommunicator(_Manager())
    assert comm.allreduce(np.ones(2)) == "the manager's"
    with pytest.raises(ValueError):
        comm.allreduce(np.ones(2), divisor=2)


@pytest.mark.parametrize(
    "tiers",
    [["cpp", "cpp"], ["python", "python"], ["python", "cpp"], ["cpp", "python"]],
    ids=["cpp", "python", "averaging_python_summing_cpp", "averaging_cpp_summing_python"],
)
def test_a_peer_that_expects_sums_fails_the_op_and_mixes_nothing(store, tiers: List[str]) -> None:
    """Rank 0 divides its chunk, rank 1 rings as a program from before the
    divisor does (a plain SUM, the tag window at 0): both fail, well inside
    the timeout, and neither is handed a value."""
    import time

    def _ops(comm, rank):
        data = np.full(COUNT, float(rank + 1), np.float32)
        began = time.monotonic()
        work = comm.allreduce(data, ReduceOp.SUM, divisor=2 if rank == 0 else None)
        with pytest.raises(CommunicatorError, match="tag mismatch|aborted|closed|connection"):
            work.wait(timeout=20.0)
        assert comm.errored() is not None
        return time.monotonic() - began

    took = _run(store, tiers, _ops, "old_peer_" + "_".join(tiers), timeout_s=8.0)
    assert max(took) < 8.0
