"""Topology-aware hierarchical collectives + shared-memory transport tests.

The tentpole contract of the topology work (``_HostTopology`` discovery,
``_ShmSeg`` intra-host transport, leader-ring dispatch):

- host grouping is a pure function of the (rank -> host id) map — hosts
  ordered by smallest rank, that rank leading — identical on every rank;
- the hierarchical schedule is DETERMINISTIC (fixed intra-host reduction
  order): allclose to the flat ring, and bit-identical to itself across
  lane counts at a fixed topology;
- the quantized pipeline quantizes once per HOST: non-leaders move zero
  socket bytes;
- the shm segment is unlinked-after-map (no /dev/shm orphans, ever — even
  after aborts and leader kills), and an abort latches into the segment so
  spinning members unblock with the standard poison;
- losing a host leader mid-collective poisons the epoch; the next epoch's
  topology elects the lowest surviving rank and the group re-forms.
"""

import glob
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Optional

import numpy as np
import pytest

from torchft_tpu.communicator import (
    CommunicatorAborted,
    CommunicatorError,
    ReduceOp,
    TCPCommunicator,
    _hier_mode,
    _HostTopology,
    _ring_bounds,
)
from torchft_tpu.store import StoreServer


@pytest.fixture()
def store():
    server = StoreServer("127.0.0.1:0")
    yield server
    server.shutdown()


def _shm_orphans() -> List[str]:
    return glob.glob("/dev/shm/tpuft_shm_*")


def _run_ranks(
    store: StoreServer,
    hosts: List[str],
    fn: Callable[[TCPCommunicator, int], object],
    prefix: str,
    hier: Optional[str] = "1",
    timeout_s: float = 30.0,
) -> List[object]:
    world_size = len(hosts)

    def _one(rank: int) -> object:
        comm = TCPCommunicator(
            timeout_s=timeout_s, host_id=hosts[rank], hierarchical=hier
        )
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


class TestHostTopology:
    def test_grouping_orders_hosts_by_min_rank(self) -> None:
        host_of = {0: "b", 1: "a", 2: "b", 3: "a", 4: "c"}
        t = _HostTopology(host_of, rank=3)
        # host "b" holds rank 0 -> first; "a" holds rank 1 -> second
        assert t.hosts == [[0, 2], [1, 3], [4]]
        assert t.leader_ring == [0, 1, 4]
        assert t.local == [1, 3]
        assert t.leader == 1
        assert not t.is_leader
        assert t.local_index == 1
        assert t.num_hosts == 3 and t.local_world == 2

    def test_leader_is_lowest_rank(self) -> None:
        t = _HostTopology({0: "x", 1: "x", 2: "x"}, rank=0)
        assert t.is_leader and t.leader == 0 and t.leader_ring == [0]

    def test_worth_it_needs_two_hosts_and_a_group(self) -> None:
        assert _HostTopology({0: "a", 1: "a", 2: "b"}, 0).worth_it()
        # single host: no cross-host ring to shorten
        assert not _HostTopology({0: "a", 1: "a"}, 0).worth_it()
        # one replica per host: flat ring is already once-per-host
        assert not _HostTopology({0: "a", 1: "b", 2: "c"}, 0).worth_it()

    def test_mode_parse_is_loud(self, monkeypatch) -> None:
        assert _hier_mode(None) == "auto"
        assert _hier_mode("1") == "1"
        assert _hier_mode("off") == "0"
        monkeypatch.setenv("TORCHFT_HIERARCHICAL", "maybe")
        with pytest.raises(CommunicatorError, match="TORCHFT_HIERARCHICAL"):
            _hier_mode(None)

    def test_host_id_env_groups_ranks(self, store, monkeypatch) -> None:
        # both thread-ranks read the same TORCHFT_HOST_ID -> one host group
        monkeypatch.setenv("TORCHFT_HOST_ID", "envhost")

        def _fn(comm, rank):
            return comm.hier_topology()

        topos = _run_ranks(
            store, [None, None], _fn, prefix="envhost", hier="1"  # type: ignore[list-item]
        )
        for t in topos:
            assert t is not None and t["hosts"] == 1 and t["local_world"] == 2

    def test_auto_stays_flat_on_one_host(self, store) -> None:
        topos = _run_ranks(
            store, ["h0", "h0"], lambda c, r: c.hier_topology(),
            prefix="auto1", hier="auto",
        )
        assert topos == [None, None]

    def test_mode_mismatch_is_loud(self, store) -> None:
        """auto-vs-forced would let each rank gate engagement on its own —
        a silent schedule desync — so it must fail rendezvous loudly, like
        a lane-count mismatch."""
        errors: List[BaseException] = []

        def _one(rank: int, mode: str) -> None:
            comm = TCPCommunicator(
                timeout_s=8.0, host_id="h0", hierarchical=mode
            )
            try:
                comm.configure(
                    f"127.0.0.1:{store.port}/modemm",
                    replica_id=f"rep_{rank}",
                    rank=rank,
                    world_size=2,
                )
                err = comm.allreduce(np.ones(8, np.float32)).exception(
                    timeout=10.0
                )
                if err is not None:
                    errors.append(err)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            finally:
                comm.shutdown()

        threads = [
            threading.Thread(target=_one, args=(0, "1")),
            threading.Thread(target=_one, args=(1, "auto")),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
        assert any(
            "TORCHFT_HIERARCHICAL mismatch" in str(e) for e in errors
        ), errors

    def test_auto_engages_on_multi_host_groups(self, store) -> None:
        topos = _run_ranks(
            store, ["h0", "h0", "h1"], lambda c, r: c.hier_topology(),
            prefix="auto2", hier="auto",
        )
        for t in topos:
            assert t is not None and t["hosts"] == 2
            assert t["leader_ring"] == [0, 2]


HOSTS_2x2 = ["h0", "h0", "h1", "h1"]


class TestHierarchicalCollectives:
    def test_allreduce_matches_flat_allclose(self, store) -> None:
        n = 300_007
        rng = np.random.default_rng(11)
        inputs = [rng.normal(size=n).astype(np.float32) for _ in range(4)]

        def _fn(comm, rank):
            return comm.allreduce(inputs[rank].copy(), ReduceOp.SUM).wait(
                timeout=30.0
            )

        flat = _run_ranks(store, HOSTS_2x2, _fn, prefix="arflat", hier="0")
        hier = _run_ranks(store, HOSTS_2x2, _fn, prefix="arhier", hier="1")
        for f, h in zip(flat, hier):
            # different (fixed) reduction ORDER: allclose, not bit-equal
            np.testing.assert_allclose(
                np.asarray(f), np.asarray(h), rtol=1e-4, atol=1e-3
            )

    def test_bit_identical_across_lane_counts(self, store, monkeypatch) -> None:
        """At a FIXED topology, lane striping still only moves bytes: the
        leader ring's frames split differently but every element sees the
        same adds in the same order."""
        monkeypatch.setenv("TORCHFT_RING_FRAME_KB", "64")
        n = 500_009
        rng = np.random.default_rng(12)
        inputs = [rng.normal(size=n).astype(np.float32) for _ in range(4)]

        def _fn(comm, rank):
            return comm.allreduce(inputs[rank].copy(), ReduceOp.SUM).wait(
                timeout=30.0
            )

        monkeypatch.setenv("TORCHFT_RING_LANES", "1")
        base = _run_ranks(store, HOSTS_2x2, _fn, prefix="hl1", hier="1")
        monkeypatch.setenv("TORCHFT_RING_LANES", "2")
        got = _run_ranks(store, HOSTS_2x2, _fn, prefix="hl2", hier="1")
        for b, g in zip(base, got):
            np.testing.assert_array_equal(np.asarray(b), np.asarray(g))

    @pytest.mark.parametrize(
        "hosts",
        [["hostA", "hostA", "hostB", "hostB"], ["hostA", "hostA", "hostA"], ["hostA", "hostB", "hostB"]],
        ids=["2x2", "one_host_forced", "1+2"],
    )
    @pytest.mark.parametrize("dtype", ["bfloat16", "float32", "int32"])
    def test_allreduce_divisor_divides_in_the_leader_ring(self, store, hosts, dtype) -> None:
        """With a divisor the LEADERS' ring hands back the average (a lone
        leader divides what the shared memory reduced), the fan-out carries
        it to the members: bit for bit ``_div`` of the same topology's sum,
        the same on every rank (PR 40)."""
        import ml_dtypes

        from torchft_tpu.communicator import _div

        dt = np.dtype(ml_dtypes.bfloat16 if dtype == "bfloat16" else dtype)
        rng = np.random.default_rng(40)
        inputs = [(rng.normal(size=100_003) * 50).astype(dt) for _ in hosts]
        # a member that shuts down latches the abort into its host's shared
        # segment, where the leader may still wait for another member's ack
        all_done = threading.Barrier(len(hosts))

        def _fn(comm, rank):
            summed = comm.allreduce(inputs[rank].copy(), ReduceOp.SUM).wait(timeout=30.0)
            mine = inputs[rank].copy()
            avg = comm.allreduce(mine, ReduceOp.SUM, in_place=True, divisor=3).wait(timeout=30.0)
            kept = inputs[rank].copy()
            out = comm.allreduce(kept, ReduceOp.SUM, divisor=3).wait(timeout=30.0)
            all_done.wait(timeout=30.0)
            assert kept.tobytes() == inputs[rank].tobytes() and np.shares_memory(avg, mine)
            return np.asarray(summed), np.asarray(avg), np.asarray(out)

        results = _run_ranks(store, hosts, _fn, prefix=f"avg_{dtype}_{len(hosts)}_{hosts[1]}", hier="1")
        want = _div(results[0][0], 3).tobytes()
        for summed, avg, out in results:
            assert summed.tobytes() == results[0][0].tobytes()
            assert avg.tobytes() == want and out.tobytes() == want

    def test_allgather_and_reduce_scatter(self, store) -> None:
        n = 70_001
        rng = np.random.default_rng(13)
        inputs = [rng.normal(size=n).astype(np.float32) for _ in range(4)]
        expect = np.sum(inputs, axis=0)

        def _ag(comm, rank):
            return comm.allgather(inputs[rank]).wait(timeout=30.0)

        for got in _run_ranks(store, HOSTS_2x2, _ag, prefix="hag"):
            for p in range(4):
                np.testing.assert_array_equal(np.asarray(got[p]), inputs[p])

        def _rs(comm, rank):
            return comm.reduce_scatter(inputs[rank].copy(), ReduceOp.SUM).wait(
                timeout=30.0
            )

        bounds = _ring_bounds(n, 4)
        for rank, got in enumerate(
            _run_ranks(store, HOSTS_2x2, _rs, prefix="hrs")
        ):
            np.testing.assert_allclose(
                np.asarray(got),
                expect[bounds[rank] : bounds[rank + 1]],
                rtol=1e-4,
                atol=1e-3,
            )

    def test_broadcast_from_non_leader_root(self, store) -> None:
        n = 50_000
        payload = np.arange(n, dtype=np.float32)

        def _fn(comm, rank):
            buf = payload.copy() if rank == 1 else np.zeros(n, np.float32)
            return comm.broadcast(buf, root=1).wait(timeout=30.0)

        for got in _run_ranks(store, HOSTS_2x2, _fn, prefix="hbc"):
            np.testing.assert_array_equal(np.asarray(got), payload)

    def test_members_move_zero_socket_bytes(self, store) -> None:
        def _fn(comm, rank):
            comm.allreduce(
                np.ones(1 << 18, dtype=np.float32), ReduceOp.SUM
            ).wait(timeout=30.0)
            return comm.lane_stats()

        stats = _run_ranks(store, HOSTS_2x2, _fn, prefix="hbytes")
        for st in stats:
            assert st["topo_hosts"] == 2 and st["topo_local_world"] == 2
            if st["topo_is_leader"]:
                assert sum(st["lane_tx_bytes"]) > 0
            else:
                # the whole point: members never touch the DCN
                assert sum(st["lane_tx_bytes"]) == 0
                assert st["shm_tx_bytes"] > 0
            # where the time went: the shared-memory legs are no lane's, they
            # lie in the phase they belong to (``lane_stats``' docstring), so
            # a member, which rings nothing, still says how long each took
            assert st["ring_reduce_s"] > 0.0 and st["ring_gather_s"] > 0.0
            moved = sum(st["lane_rx_s"]) + sum(st["lane_tx_s"]) + st["ring_tail_s"]
            assert moved > 0.0 if st["topo_is_leader"] else moved == 0.0


class TestQuantizedOncePerHost:
    def test_quantized_allreduce_close_and_host_quantized(self, store) -> None:
        from torchft_tpu.collectives import allreduce_quantized

        n = 128 * 1024
        rng = np.random.default_rng(21)
        inputs = [rng.normal(size=n).astype(np.float32) for _ in range(4)]
        expect = np.sum(inputs, axis=0)
        atol = 1.5 * np.abs(expect).max() / 127.0

        def _fn(comm, rank):
            out = allreduce_quantized(comm, inputs[rank].copy()).wait(
                timeout=30.0
            )
            return np.asarray(out), comm.lane_stats()

        res = _run_ranks(store, HOSTS_2x2, _fn, prefix="hquant")
        leader_tx = 0
        for got, st in res:
            np.testing.assert_allclose(got, expect, rtol=0.02, atol=atol)
            if st["topo_is_leader"]:
                leader_tx += sum(st["lane_tx_bytes"])
            else:
                # quantize-once-per-host: members contribute over shm only
                assert sum(st["lane_tx_bytes"]) == 0

        flat = _run_ranks(store, HOSTS_2x2, _fn, prefix="fquant", hier="0")
        flat_tx = sum(sum(st["lane_tx_bytes"]) for _, st in flat)
        for got, _ in flat:
            np.testing.assert_allclose(got, expect, rtol=0.02, atol=atol)
        # int8 wire bytes drop by ~the local-group factor (2 leaders of 4
        # ranks, and the leader pair exchanges a single host-sum stream)
        assert leader_tx < flat_tx / 2, (leader_tx, flat_tx)

    def test_prequantized_takes_hier_path(self, store) -> None:
        from torchft_tpu.collectives import allreduce_prequantized
        from torchft_tpu.quantization import quantize_rowwise

        n = 64 * 1024
        rng = np.random.default_rng(22)
        inputs = [rng.normal(size=n).astype(np.float32) for _ in range(4)]
        expect = np.sum(inputs, axis=0)
        atol = 2.0 * np.abs(expect).max() / 127.0

        def _fn(comm, rank):
            q, s = quantize_rowwise(inputs[rank], 512, "int8")
            return allreduce_prequantized(comm, q, s, n)

        for got in _run_ranks(store, HOSTS_2x2, _fn, prefix="hpreq"):
            np.testing.assert_allclose(
                np.asarray(got), expect, rtol=0.03, atol=atol
            )


class TestShmLifecycle:
    def test_unlinked_after_map(self, store) -> None:
        """The segment must not exist as a file once the epoch is live — a
        later SIGKILL of any member can then never orphan it.  (The assert
        runs after the first collective: a MEMBER's configure may return a
        beat before the leader's unlink lands, but no collective can
        complete before the leader finished rendezvous.)"""

        def _fn(comm, rank):
            comm.allreduce(np.ones(1024, np.float32)).wait(timeout=30.0)
            assert not _shm_orphans()
            return True

        assert all(_run_ranks(store, ["h0", "h0"], _fn, prefix="unlink"))
        assert not _shm_orphans()

    def test_abort_unblocks_shm_spin_and_leaks_nothing(self, store) -> None:
        """A leader spinning on a member that never posts (the member died)
        must unblock via the abort latch, fail the op with the standard
        poison, and leave /dev/shm clean."""
        comms: List[Optional[TCPCommunicator]] = [None, None]
        barrier = threading.Barrier(2)
        errs: List[BaseException] = []

        def _one(rank: int) -> None:
            comm = TCPCommunicator(
                timeout_s=20.0, host_id="h0", hierarchical="1"
            )
            comm.configure(
                f"127.0.0.1:{store.port}/shmabort",
                replica_id=f"rep_{rank}",
                rank=rank,
                world_size=2,
            )
            comms[rank] = comm
            barrier.wait()
            if rank == 0:
                # the member (rank 1) never joins this collective: spin on
                # its slot until the abort latch fires
                work = comm.allreduce(np.ones(4096, np.float32))
                err = work.exception(timeout=15.0)
                if err is not None:
                    errs.append(err)
            else:
                time.sleep(0.3)
                comm.abort("chaos: member died")

        threads = [threading.Thread(target=_one, args=(r,)) for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
        # rank 1's abort latched into the SHARED segment and unblocked rank
        # 0's spin (CommunicatorAborted), or rank 0's own watchdog fired
        # (TimeoutError->abort); either way the op failed fast and cleanly
        assert errs and isinstance(
            errs[0], (CommunicatorAborted, TimeoutError)
        ), errs
        for comm in comms:
            if comm is not None:
                comm.shutdown()
        assert not _shm_orphans()


class TestHostLeaderChaos:
    def test_leader_death_reelects_next_epoch(self, store) -> None:
        """The HOST_LEADER drill: kill a host leader mid-allreduce — the
        survivors' epoch poisons (no wedge), the next epoch's topology
        elects the lowest surviving rank as leader, the group re-forms, and
        /dev/shm holds no orphaned segments afterwards."""
        world = 3
        hosts = ["h0", "h0", "h1"]
        barrier = threading.Barrier(world)
        second_round: List[np.ndarray] = []
        new_topos: List[dict] = []

        def _one(rank: int) -> None:
            comm = TCPCommunicator(
                timeout_s=8.0, host_id=hosts[rank], hierarchical="1"
            )
            comm.configure(
                f"127.0.0.1:{store.port}/leaderkill",
                replica_id=f"rep_{rank}",
                rank=rank,
                world_size=world,
            )
            topo = comm.hier_topology()
            assert topo is not None
            barrier.wait()
            if rank == 0:
                # rank 0 leads h0 AND the leader ring: its death severs both
                # the shm hub (rank 1) and the cross-host ring (rank 2)
                assert topo["is_leader"]
                comm.abort("chaos: host leader killed")
                return
            err = comm.allreduce(
                np.ones(1 << 19, dtype=np.float32)
            ).exception(timeout=30.0)
            assert err is not None, f"rank {rank} should have been poisoned"
            # next epoch: survivors re-rendezvous; old rank 1 (now rank 0)
            # is h0's lowest surviving rank -> the re-elected leader
            comm.configure(
                f"127.0.0.1:{store.port}/leaderkill2",
                replica_id=f"rep_{rank}",
                rank=rank - 1,
                world_size=world - 1,
            )
            new_topo = comm.hier_topology()
            # 2 hosts x 1 replica: auto would go flat; forced "1" keeps the
            # topology surfaced so the re-election is observable
            assert new_topo is not None
            new_topos.append(new_topo)
            res = comm.allreduce(
                np.full(4096, float(rank), dtype=np.float32), ReduceOp.SUM
            ).wait(timeout=30.0)
            second_round.append(np.asarray(res))
            comm.shutdown()

        threads = [threading.Thread(target=_one, args=(r,)) for r in range(world)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        assert len(second_round) == 2, "a survivor wedged"
        for res in second_round:
            np.testing.assert_allclose(res, np.full(4096, 3.0))
        for topo in new_topos:
            assert topo["leader_ring"] == [0, 1]
        assert not _shm_orphans()

    def test_chaos_api_targets_leaders_only(self) -> None:
        from torchft_tpu.chaos import Failure, ThreadReplica

        class _FakeComm:
            def __init__(self, leader: bool) -> None:
                self._leader = leader

            def hier_topology(self):
                return {"is_leader": self._leader, "hosts": 2}

        class _Obj:
            def __init__(self, leader: bool) -> None:
                self.comm = _FakeComm(leader)
                self.kill_flag = threading.Event()
                self.commits = 0

        leader = ThreadReplica("lead", _Obj(True))
        member = ThreadReplica("member", _Obj(False))
        assert leader.supports(Failure.HOST_LEADER)
        assert not member.supports(Failure.HOST_LEADER)
        leader.inject(Failure.HOST_LEADER)
        assert leader._obj.kill_flag.is_set()
        with pytest.raises(RuntimeError, match="not a host leader"):
            member.inject(Failure.HOST_LEADER)
