"""Multi-lane ring striping + adaptive framing tests.

The tentpole contract of the lane work (``_TcpMesh`` lane sockets,
``_lane_parts`` striping): striping only moves BYTES differently — every
element still accumulates the same values in the same order — so a
multi-lane allreduce must be **bit-identical** to the single-lane one; and
a peer dying mid-collective with many lanes in flight must poison the epoch
exactly once (first error latches, no double-abort, no wedge), exactly like
the single-socket failure contract in ``test_communicator.py``.
"""

import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List

import numpy as np
import pytest

from torchft_tpu.communicator import (
    CommunicatorError,
    ReduceOp,
    TCPCommunicator,
    _MAX_AUTO_LANES,
    _UNSHAPED_AUTO_LANES,
    _lane_parts,
    _NetEmu,
    _ring_lanes,
    _stripe_floor,
)
from torchft_tpu.store import StoreServer

from tests.test_allreduce_divisor import _run as _run_tiers, _tiers


@pytest.fixture()
def store():
    server = StoreServer("127.0.0.1:0")
    yield server
    server.shutdown()


def _run_ranks(
    store: StoreServer,
    world_size: int,
    fn: Callable[[TCPCommunicator, int], object],
    prefix: str,
    timeout_s: float = 30.0,
) -> List[object]:
    def _one(rank: int) -> object:
        comm = TCPCommunicator(timeout_s=timeout_s)
        comm.configure(
            f"127.0.0.1:{store.port}/{prefix}",
            replica_id=f"rep_{rank}",
            rank=rank,
            world_size=world_size,
        )
        try:
            return fn(comm, rank)
        finally:
            comm.shutdown()

    with ThreadPoolExecutor(max_workers=world_size) as pool:
        return list(pool.map(_one, range(world_size)))


class TestLaneParts:
    def test_small_payload_rides_lane_zero_whole(self) -> None:
        assert _lane_parts(1000, 4, 64 << 10) == [(0, 0, 1000)]
        assert _lane_parts(0, 4, 64 << 10) == [(0, 0, 0)]
        assert _lane_parts(10 << 20, 1, 64 << 10) == [(0, 0, 10 << 20)]

    def test_parts_partition_and_align(self) -> None:
        for n in (1 << 20, (1 << 20) + 3, 7 * 12345, 2 * (64 << 10)):
            for lanes in (2, 3, 4, 8):
                parts = _lane_parts(n, lanes, 64 << 10)
                assert parts[0][1] == 0 and parts[-1][2] == n
                for (l1, _s1, e1), (l2, s2, _e2) in zip(parts, parts[1:]):
                    assert e1 == s2 and l2 == l1 + 1
                # interior boundaries 64-byte aligned so no element of any
                # supported dtype ever splits across lanes
                for _lane, s, _e in parts[1:]:
                    assert s % 64 == 0

    def test_floor_bounds_part_count(self) -> None:
        # 3 floors of payload across 4 lanes -> at most 3 parts
        parts = _lane_parts(3 * (64 << 10), 4, 64 << 10)
        assert 1 < len(parts) <= 3


class TestLaneResolution:
    def test_explicit_env_wins(self, monkeypatch) -> None:
        monkeypatch.setenv("TORCHFT_RING_LANES", "3")
        assert _ring_lanes(None) == 3

    def test_bad_env_is_loud(self, monkeypatch) -> None:
        monkeypatch.setenv("TORCHFT_RING_LANES", "many")
        with pytest.raises(CommunicatorError, match="TORCHFT_RING_LANES"):
            _ring_lanes(None)
        monkeypatch.setenv("TORCHFT_RING_LANES", "0")
        with pytest.raises(CommunicatorError, match=">= 1"):
            _ring_lanes(None)

    def test_auto_stripes_where_no_link_is_emulated(self, monkeypatch) -> None:
        # a stream on loopback (or any fast link) moves at one core's copy
        # rate, so auto without a profile is the constant, not 1
        monkeypatch.delenv("TORCHFT_RING_LANES", raising=False)
        assert _ring_lanes(None) == _UNSHAPED_AUTO_LANES > 1
        monkeypatch.setenv("TORCHFT_RING_LANES", "auto")
        assert _ring_lanes(None) == _UNSHAPED_AUTO_LANES
        # never more than auto could already pick on a shaped link
        assert _UNSHAPED_AUTO_LANES <= _MAX_AUTO_LANES
        # an explicit count wins, 1 included (the rolling-upgrade pin)
        monkeypatch.setenv("TORCHFT_RING_LANES", "1")
        assert _ring_lanes(None) == 1

    def test_auto_scales_with_stream_gap(self, monkeypatch) -> None:
        monkeypatch.delenv("TORCHFT_RING_LANES", raising=False)
        # wan_1g profile: 1 Gb/s link, 10 ms RTT, 256 KiB cwnd -> one stream
        # covers ~1/5 of the link -> auto picks the lane cap
        emu = _NetEmu(gbps=1.0, rtt_ms=10.0)
        assert _ring_lanes(emu) == 4
        # a stream that covers the link needs no second one
        assert _ring_lanes(_NetEmu(gbps=0.1, rtt_ms=10.0)) == 1
        # no RTT -> the emulator names no per-stream cap -> the link is
        # shaped but a stream is not: the unshaped constant (the lanes share
        # the one link bucket, so the emulated rate holds)
        assert _ring_lanes(_NetEmu(gbps=1.0, rtt_ms=0.0)) == _UNSHAPED_AUTO_LANES

    def test_adaptive_frame_floor(self, monkeypatch) -> None:
        monkeypatch.delenv("TORCHFT_RING_FRAME_KB", raising=False)
        # loopback: small frames
        assert _stripe_floor(None) == 64 << 10
        # DCN: jumbo frames sized to the RTTxBW product
        emu = _NetEmu(gbps=1.0, rtt_ms=10.0)
        assert _stripe_floor(emu) == emu.bdp_bytes() == 1_250_000
        monkeypatch.setenv("TORCHFT_RING_FRAME_KB", "512")
        assert _stripe_floor(emu) == 512 << 10

    def test_net_emu_named_profile(self, monkeypatch) -> None:
        monkeypatch.setenv("TORCHFT_NET_EMU", "wan_1g")
        from torchft_tpu.communicator import _net_emu_from_env

        emu = _net_emu_from_env()
        assert emu is not None
        assert emu.bytes_per_s == pytest.approx(1e9 / 8)
        assert emu.half_rtt_s == pytest.approx(0.005)
        monkeypatch.setenv("TORCHFT_NET_EMU", "wan_9000g")
        with pytest.raises(CommunicatorError, match="TORCHFT_NET_EMU"):
            _net_emu_from_env()


class TestStreamCap:
    def test_per_stream_bucket_caps_below_link(self) -> None:
        emu = _NetEmu(gbps=10.0, rtt_ms=10.0, cwnd_bytes=64 << 10)
        # the link alone would allow the full burst; the stream cap clamps
        # one connection to its cwnd
        first = emu.allow(10 << 20, stream=("p", 0))
        assert first <= 64 << 10
        emu.consume(first, stream=("p", 0))
        # a second stream has its own bucket: not starved by the first
        assert emu.allow(10 << 20, stream=("p", 1)) > 0


@pytest.mark.parametrize("world_size", [2, 3])
def test_multi_lane_bit_identical_to_single_lane(
    store, world_size, monkeypatch
) -> None:
    """Striping splits bytes, never math: per element the ring applies the
    same adds in the same order at any lane count."""
    n = 1_000_003  # ~4 MB of f32, odd length -> uneven chunks + odd parts
    rng = np.random.default_rng(5)
    inputs = [rng.normal(size=n).astype(np.float32) for _ in range(world_size)]

    def _fn(comm, rank):
        return comm.allreduce(inputs[rank].copy(), ReduceOp.SUM).wait(
            timeout=30.0
        )

    monkeypatch.setenv("TORCHFT_RING_LANES", "1")
    base = _run_ranks(store, world_size, _fn, prefix=f"lane1_{world_size}")
    for lanes in (2, 4):
        monkeypatch.setenv("TORCHFT_RING_LANES", str(lanes))
        got = _run_ranks(
            store, world_size, _fn, prefix=f"lane{lanes}_{world_size}"
        )
        for b, g in zip(base, got):
            np.testing.assert_array_equal(
                np.asarray(b), np.asarray(g),
                err_msg=f"{lanes}-lane result diverged from 1-lane",
            )


def test_multi_lane_quantized_bit_identical(store, monkeypatch) -> None:
    """The windowed quantized pipeline's alltoall/allgather frames stripe
    across lanes too; the dequantized result must not move."""
    from torchft_tpu.collectives import allreduce_quantized

    monkeypatch.setenv("TORCHFT_QUANT_WINDOW_MB", "0.25")
    rng = np.random.default_rng(23)
    n = 512 * 1024
    inputs = [rng.normal(size=n).astype(np.float32) for _ in range(2)]

    def _fn(comm, rank):
        return allreduce_quantized(comm, inputs[rank].copy()).wait(timeout=30.0)

    monkeypatch.setenv("TORCHFT_RING_LANES", "1")
    base = _run_ranks(store, 2, _fn, prefix="qlane1")
    monkeypatch.setenv("TORCHFT_RING_LANES", "4")
    got = _run_ranks(store, 2, _fn, prefix="qlane4")
    for b, g in zip(base, got):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(g))


def test_lane_stats_populated(store, monkeypatch) -> None:
    monkeypatch.setenv("TORCHFT_RING_LANES", "4")
    monkeypatch.setenv("TORCHFT_RING_FRAME_KB", "64")

    def _fn(comm, rank):
        comm.allreduce(np.ones(1 << 20, dtype=np.float32)).wait(timeout=30.0)
        return comm.lane_stats()

    stats = _run_ranks(store, 2, _fn, prefix="stats")
    for st in stats:
        assert st["lanes"] == 4
        assert len(st["lane_tx_bytes"]) == 4
        # a 4 MB ring at a 64 KiB floor stripes across every lane
        assert all(b > 0 for b in st["lane_tx_bytes"])
        assert all(b > 0 for b in st["lane_rx_bytes"])
        assert st["stripe_floor_bytes"] == 64 << 10


@pytest.mark.parametrize(
    "lanes_a,lanes_b", [(2, 3), (1, 4), (4, 1)],
    ids=["multi-vs-multi", "legacy-dials-multi", "multi-dials-legacy"],
)
def test_lane_count_mismatch_is_loud(store, lanes_a, lanes_b) -> None:
    """A non-uniform TORCHFT_RING_LANES must fail rendezvous LOUDLY — in
    BOTH directions, including against a legacy single-lane hello (the
    hello's flag bit carries the distinction) — never desynchronize frames
    mid-collective.  (Lanes are resolved per-mesh at configure, so the
    mismatch is injected via the private ctor arg.)"""
    from torchft_tpu.communicator import _TcpMesh

    errors: List[Exception] = []
    results: List[object] = []

    def _one(rank: int, lanes: int) -> None:
        try:
            results.append(
                _TcpMesh(
                    f"127.0.0.1:{store.port}/mm{lanes_a}_{lanes_b}",
                    rank,
                    2,
                    timeout_s=5.0,
                    lanes=lanes,
                )
            )
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [
        threading.Thread(target=_one, args=(0, lanes_a)),
        threading.Thread(target=_one, args=(1, lanes_b)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    for mesh in results:
        mesh.abort()
    assert errors, "lane mismatch must surface as a rendezvous error"
    assert any("lane-count mismatch" in str(e) for e in errors), errors


class TestAbortMidLane:
    @pytest.mark.parametrize("lanes", [2, 4])
    def test_killed_peer_poisons_epoch_exactly_once(
        self, store, lanes, monkeypatch
    ) -> None:
        """Kill a peer while a multi-lane collective has frames in flight on
        every lane: each survivor's op fails, the epoch latches exactly ONE
        abort (several lane sockets erroring concurrently must not
        double-abort), and a reconfigure fully recovers."""
        monkeypatch.setenv("TORCHFT_RING_LANES", str(lanes))
        monkeypatch.setenv("TORCHFT_RING_FRAME_KB", "64")
        world_size = 3
        barrier = threading.Barrier(world_size)
        abort_counts: List[int] = []
        second_round: List[np.ndarray] = []

        def _fn(rank: int) -> None:
            comm = TCPCommunicator(timeout_s=5.0)
            comm.configure(
                f"127.0.0.1:{store.port}/abortlane{lanes}",
                replica_id=f"rep_{rank}",
                rank=rank,
                world_size=world_size,
            )
            # count epoch poisonings on this survivor
            n_aborts = [0]
            orig = comm._abort_locked

            def _counting_abort(reason: str) -> None:
                n_aborts[0] += 1
                orig(reason)

            comm._abort_locked = _counting_abort
            barrier.wait()
            if rank == world_size - 1:
                comm.abort("injected failure")
                return
            # large enough that every lane carries stripes when it dies
            work = comm.allreduce(
                np.ones(1 << 20, dtype=np.float32), ReduceOp.SUM
            )
            err = work.exception(timeout=30.0)
            assert err is not None
            first = comm.errored()
            assert first is not None
            # a second op fails with the SAME latched poison, not a fresh one
            err2 = comm.allreduce(
                np.ones(8, dtype=np.float32)
            ).exception(timeout=5.0)
            assert err2 is first
            abort_counts.append(n_aborts[0])

            comm._abort_locked = orig
            comm.configure(
                f"127.0.0.1:{store.port}/abortlane{lanes}b",
                replica_id=f"rep_{rank}",
                rank=rank,
                world_size=world_size - 1,
            )
            assert comm.errored() is None
            res = comm.allreduce(
                np.full(4096, float(rank + 1), dtype=np.float32), ReduceOp.SUM
            ).wait(timeout=30.0)
            second_round.append(res)
            comm.shutdown()

        threads = [
            threading.Thread(target=_fn, args=(r,)) for r in range(world_size)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        assert len(abort_counts) == world_size - 1, "a survivor wedged"
        # exactly once: several lane sockets erroring concurrently latch ONE
        # epoch poison (the `err2 is first` identity above) and at most one
        # abort (0 when the op failed fast, 1 when the watchdog fired) —
        # never a second abort of an already-poisoned epoch
        assert all(c <= 1 for c in abort_counts), abort_counts
        assert len(second_round) == world_size - 1
        for res in second_round:
            np.testing.assert_allclose(res, np.full(4096, 3.0))


# --- auto stripes where no link is emulated (PR 47) -------------------------


@pytest.mark.parametrize("tier", ["cpp", "python", "mixed"])
def test_pieces_with_divisor_bit_identical_at_one_lane_and_auto(store, tier, monkeypatch) -> None:
    """What a step of the two-group cells rings: a bfloat16 leaf cut in
    pieces that are views into one kept buffer, each rung in place with the
    divisor, and one small leaf (under two stripe floors: lane 0 whole).
    ``auto`` without a profile stripes it over the constant's lanes on both
    tiers and on a mixed pair, and every piece comes back bit for bit what
    one lane gives."""
    import ml_dtypes

    monkeypatch.delenv("TORCHFT_NET_EMU", raising=False)
    monkeypatch.delenv("TORCHFT_RING_FRAME_KB", raising=False)
    bf16 = np.dtype(ml_dtypes.bfloat16)
    piece, pieces, small = 700_001, 3, 100  # a piece's half is 5 floors and some
    rng = np.random.default_rng(47)
    grads = [
        (rng.standard_normal(piece * pieces + small) * 1e3).astype(bf16) for _ in range(2)
    ]

    def _fn(comm, rank):
        flat = grads[rank].copy()
        views = [flat[k * piece : (k + 1) * piece] for k in range(pieces)] + [flat[piece * pieces :]]
        works = [comm.allreduce(v, ReduceOp.SUM, in_place=True, divisor=2) for v in views]
        for v, w in zip(views, works):
            got = w.wait(timeout=30.0)
            if not np.may_share_memory(np.asarray(got), v):
                v[:] = got
        return flat, comm.lane_stats()

    monkeypatch.setenv("TORCHFT_RING_LANES", "1")
    base = _run_tiers(store, _tiers(tier, 2), _fn, f"pieces1_{tier}")
    monkeypatch.delenv("TORCHFT_RING_LANES")
    auto = _run_tiers(store, _tiers(tier, 2), _fn, f"piecesauto_{tier}")
    for (want, one), (got, striped) in zip(base, auto):
        assert one["lanes"] == 1 and striped["lanes"] == _UNSHAPED_AUTO_LANES
        np.testing.assert_array_equal(got.view(np.uint16), want.view(np.uint16))
        # the same bytes cross, now on every lane; the small leaf's 200
        # bytes and nothing else of the payload stay whole on lane 0
        tx = striped["lane_tx_bytes"]
        # (the Python tier counts a sub-frame's 16-byte header with its payload)
        assert 0 <= sum(tx) - sum(one["lane_tx_bytes"]) < 1024 and all(b > 0 for b in tx)
        share = sum(tx[1:]) / sum(tx)
        assert abs(share - (1 - 1 / _UNSHAPED_AUTO_LANES)) < 0.01
    np.testing.assert_array_equal(auto[0][0].view(np.uint16), auto[1][0].view(np.uint16))


@pytest.mark.parametrize("new_tier", ["cpp", "python"])
def test_one_lane_peer_from_before_auto_striped_fails_loudly_and_the_pin_heals(
    store, new_tier, monkeypatch
) -> None:
    """A version boundary: a peer from before PR 47 resolves ``auto`` to one
    lane and speaks the legacy hello; against a new peer's ``auto`` the
    rendezvous fails LOUDLY on both sides, and ``TORCHFT_RING_LANES=1`` on
    the new side (here: in the process) rings with it."""
    from torchft_tpu import native
    from torchft_tpu.communicator import _TcpMesh

    monkeypatch.delenv("TORCHFT_NET_EMU", raising=False)
    monkeypatch.delenv("TORCHFT_RING_LANES", raising=False)
    errors: List[BaseException] = []
    made: List[object] = []

    def _old(prefix: str) -> None:
        try:  # lanes=1: what the parent's ``auto`` resolved to, the legacy hello
            made.append(_TcpMesh(f"127.0.0.1:{store.port}/{prefix}", 0, 2, timeout_s=5.0, lanes=1))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def _new(prefix: str):
        comm = native.CppCommunicator(timeout_s=5.0) if new_tier == "cpp" else TCPCommunicator(timeout_s=5.0)
        try:
            comm.configure(f"127.0.0.1:{store.port}/{prefix}", replica_id="new", rank=1, world_size=2)
        except Exception as e:  # noqa: BLE001
            errors.append(e)
        return comm

    old = threading.Thread(target=_old, args=(f"boundary_{new_tier}",))
    old.start()
    comm = _new(f"boundary_{new_tier}")
    old.join(timeout=30.0)
    for mesh in made:
        mesh.abort()
    comm.shutdown()
    assert errors, "a one-lane peer against auto's lanes must fail the rendezvous"
    assert any("mismatch" in str(e) and "lane" in str(e) for e in errors), errors

    monkeypatch.setenv("TORCHFT_RING_LANES", "1")

    def _ring(comm, rank):
        assert comm.lane_stats()["lanes"] == 1
        return np.asarray(comm.allreduce(np.full(1 << 16, rank + 1.0, np.float32), ReduceOp.SUM).wait(timeout=30.0))

    for got in _run_tiers(store, ["python", new_tier], _ring, f"pinned_{new_tier}"):
        np.testing.assert_array_equal(got, np.full(1 << 16, 3.0, np.float32))
