"""The host buckets of ``ddp.allreduce_pytree`` last from step to step
(ISSUE 30) and cross to the host in an order, a window of bytes at a time
(ISSUE 32, ISSUE 46), a leaf over the cap in pieces (ISSUE 46: the section
after the order's).  Five rules, each with a test that fails when it is
broken:

1. a set of buckets is handed out again only after a round trip that ended
   without error and whose restored leaves are ready;
2. nothing returned to the caller aliases a kept bucket;
3. the store is the Manager's: a new life starts cold;
4. it is bounded;
5. the values are bit for bit the parent's: ``_div(sum over replicas, n)``.

Two harnesses: two thread replicas over the loopback ``TCPCommunicator``
behind a real lighthouse, and one Manager on a stub client with a
communicator the test holds, fails or lets through.
"""

import threading
import time
import tracemalloc
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu import ddp
from torchft_tpu.communicator import DummyCommunicator, TCPCommunicator, _div
from torchft_tpu.ddp import BUCKET_CAP_MB_ENV, allreduce_pytree
from torchft_tpu.lighthouse import LighthouseServer
from torchft_tpu.manager import Manager
from torchft_tpu.work import Work

from tests.test_manager import MemoryTransport, StubClient, _quorum_result


def _gathers_done() -> None:
    """The gather threads give a set back AFTER the composite's future is
    set: a test that looks at the store waits for them."""
    for t in threading.enumerate():
        if t.name == "tpuft_ddp_gather":
            t.join(timeout=10.0)
            assert not t.is_alive()


def _record_handed(manager: Manager) -> List[np.ndarray]:
    """Every buffer this Manager's ``allreduce`` is handed, kept alive (so
    that shared memory means the SAME buffer and never a reused address)."""
    handed: List[np.ndarray] = []
    inner = manager.allreduce

    def _allreduce(data: Any, *args: Any, **kwargs: Any) -> Work:
        handed.append(data)
        return inner(data, *args, **kwargs)

    manager.allreduce = _allreduce  # type: ignore[method-assign]
    return handed


def _syncs(manager: Manager) -> List[Dict[str, Any]]:
    return [e for e in manager._flight.snapshot() if e["name"] == "DDP_SYNC"]


def _bits(x: Any) -> bytes:
    return np.asarray(x).tobytes()


# ----------------------------------------------------------------------
# two thread replicas over the loopback communicator
# ----------------------------------------------------------------------


class _Pair:
    def __init__(self, lighthouse_addr: str, comm: Callable[[], Any] = lambda: TCPCommunicator(timeout_s=10.0)) -> None:
        self.managers: List[Manager] = []
        for r in range(2):
            state = {"w": np.zeros(3, np.float32)}
            self.managers.append(
                Manager(
                    comm=comm(),
                    load_state_dict=state.update,
                    state_dict=lambda state=state: dict(state),
                    min_replica_size=2,
                    replica_id=f"bucket_replica_{r}",
                    lighthouse_addr=lighthouse_addr,
                    timeout=10.0,
                    quorum_timeout=10.0,
                    connect_timeout=10.0,
                )
            )
        self.handed = [_record_handed(m) for m in self.managers]
        # who took part in the last step (the replica that heals in a life's
        # first step sends zeros)
        self.participating = [True, True]
        self._pool = ThreadPoolExecutor(max_workers=2)

    def step(self, trees: List[Any]) -> List[Any]:
        """One committed step on both replicas: the averaged trees."""

        def _one(r: int) -> Any:
            manager = self.managers[r]
            manager.start_quorum()
            out = allreduce_pytree(manager, trees[r]).wait(timeout=30.0)
            self.participating[r] = manager.is_participating()
            assert manager.should_commit()
            return out

        futures = [self._pool.submit(_one, r) for r in range(2)]
        outs = [f.result(timeout=60.0) for f in futures]
        _gathers_done()
        return outs

    def shutdown(self) -> None:
        self._pool.shutdown(wait=False)
        for m in self.managers:
            m.shutdown()


@pytest.fixture()
def lighthouse_addr():
    server = LighthouseServer(
        bind="127.0.0.1:0",
        min_replicas=2,
        join_timeout_ms=100,
        quorum_tick_ms=20,
        heartbeat_timeout_ms=5000,
    )
    yield server.address()
    server.shutdown()


@pytest.fixture()
def pair(lighthouse_addr, monkeypatch):
    # 2 KB a bucket: the float32 leaves below split into several
    monkeypatch.setenv(BUCKET_CAP_MB_ENV, str(2048 / (1 << 20)))
    p = _Pair(lighthouse_addr)
    yield p
    p.shutdown()


def _tree(rng: np.random.Generator, wide: int = 300) -> Dict[str, Any]:
    """jax and numpy leaves of two dtypes, values that differ every call."""
    f32 = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    return {
        "embed": jnp.asarray(f32(wide, 4)),
        "layers": [jnp.asarray(f32(16, 16)), jnp.asarray(f32(7))],
        "half": jnp.asarray(f32(33, 5)).astype(jnp.bfloat16),
        "host": f32(41, 3),
    }


def _expected(trees: List[Any], participating: Optional[List[bool]] = None) -> Any:
    """The parent's formula: the ring's sum in the leaf's dtype (a replica
    that does not participate sends zeros), averaged by ``_div``."""
    participating = participating or [True] * len(trees)
    flat = [jax.tree_util.tree_leaves(t) for t in trees]
    out = []
    for leaves in zip(*flat):
        arrays = [
            np.asarray(l) if p else np.zeros_like(np.asarray(l))
            for l, p in zip(leaves, participating)
        ]
        total = arrays[0]
        for a in arrays[1:]:
            total = total + a
        out.append(_div(total, len(trees)))
    return out


STEPS = 6
CHANGES_AT = 3


@pytest.mark.parametrize(
    "case",
    [
        "same_tree", "cap_flipped", "leaf_reshaped", "non_participating_step", "buckets_differ_30_times",
        "cap_cuts_every_leaf", "cap_of_one_row", "cap_at_the_largest_leaf",
    ],
)
def test_six_steps_are_bit_equal_to_the_parents_formula(pair, monkeypatch, case) -> None:
    rngs = [np.random.default_rng(10 + r) for r in range(2)]
    # the fixture's 2,048 bytes cut ``embed`` (4,800; 48,000 where it is wide)
    # alone; these cut inside the other jax leaves too, or inside none
    cap = {"cap_cuts_every_leaf": 24, "cap_of_one_row": 16, "cap_at_the_largest_leaf": 4800}.get(case, 2048)
    monkeypatch.setenv(BUCKET_CAP_MB_ENV, str(cap / (1 << 20)))
    for step in range(STEPS):
        # 48,000 bytes of ``embed`` in 24 pieces beside buckets of 1,544 and 330
        wide = 3000 if case == "buckets_differ_30_times" else 300
        if step >= CHANGES_AT and case == "cap_flipped":
            monkeypatch.setenv(BUCKET_CAP_MB_ENV, str(1024 / (1 << 20)))
        if step >= CHANGES_AT and case == "leaf_reshaped":
            wide = 200
        if step == CHANGES_AT and case == "non_participating_step":
            monkeypatch.setattr(pair.managers[1], "is_participating", lambda: False)
        elif case == "non_participating_step":
            monkeypatch.undo()
            monkeypatch.setenv(BUCKET_CAP_MB_ENV, str(2048 / (1 << 20)))
        if step >= CHANGES_AT and case == "cap_flipped":
            cap = 1024
        trees = [_tree(rng, wide) for rng in rngs]
        outs = pair.step(trees)
        want = _expected(trees, pair.participating)
        assert pair.participating == [True, step != 0 and (step, case) != (CHANGES_AT, "non_participating_step")]
        for r, out in enumerate(outs):
            got = jax.tree_util.tree_leaves(out)
            for leaf, g, w in zip(jax.tree_util.tree_leaves(trees[r]), got, want):
                assert type(g) is type(leaf) or isinstance(g, type(leaf))
                assert np.asarray(g).dtype == w.dtype and np.asarray(g).shape == w.shape
                assert _bits(g) == _bits(w), (case, step, r)
    # the steps after the change were filled in kept memory again
    for m in pair.managers:
        syncs = _syncs(m)
        assert len(syncs) == STEPS
        assert syncs[-1]["warm_buckets"] == syncs[-1]["buckets"] > 1
        handed = pair.handed[pair.managers.index(m)]
        sizes = [a.nbytes for a in handed[-syncs[-1]["buckets"]:]]
        # no ring carries more than the cap but where one element is more
        # (``cap_cuts_every_leaf``: none is), a numpy leaf goes whole
        assert max(sizes) <= max(cap, 41 * 3 * 4)
        # the counter: the bytes of the jax leaves that are over the cap
        over = [l.nbytes for l in jax.tree_util.tree_leaves(trees[0]) if isinstance(l, jax.Array) and l.nbytes > cap]
        assert syncs[-1]["split_bytes"] == sum(over) <= syncs[-1]["bytes"] == sum(sizes)
        assert (case == "cap_at_the_largest_leaf") == (not over)
        if case == "buckets_differ_30_times":
            # the rings ran in the plan's order, not the tree's: the smallest
            # bucket, then by falling size (the wide leaf's pieces side by side)
            assert sizes == [330] + [2000] * 24 + [1544]


def test_from_the_second_step_the_buckets_are_the_first_steps_memory(pair) -> None:
    rngs = [np.random.default_rng(20 + r) for r in range(2)]
    for _ in range(3):
        pair.step([_tree(rng) for rng in rngs])
    for m, handed in zip(pair.managers, pair.handed):
        syncs = _syncs(m)
        n = syncs[0]["buckets"]
        assert n > 2 and len(handed) == 3 * n
        # DDP_SYNC carries the counter beside ``buckets``: 0, then all
        assert [e["warm_buckets"] for e in syncs] == [0, n, n]
        for b in range(n):
            assert np.shares_memory(handed[b], handed[n + b])
            assert np.shares_memory(handed[b], handed[2 * n + b])
        # one set a signature is all this traffic ever holds
        assert m._host_buckets.kept_bytes() == sum(a.nbytes for a in handed[:n])


def test_a_round_trip_reads_the_rings_counters_twice_and_says_where_its_time_went(pair) -> None:
    """``Manager.ring_counters()`` once before a round trip's first submit and
    once after its last ring, a ``lane_stats()`` call each and no other on the
    round trip's threads; DDP_SYNC carries the differences."""
    logs: List[List[str]] = []
    for m in pair.managers:
        log: List[str] = []
        logs.append(log)
        reading = threading.local()
        counters, allreduce, lane_stats = m.ring_counters, m.allreduce, m._comm.lane_stats

        def _lane_stats(log=log, reading=reading, inner=lane_stats):
            if getattr(reading, "on", False):  # (the heartbeat reads it too, on its own thread)
                log.append("lane_stats")
            return inner()

        def _counters(log=log, reading=reading, inner=counters):
            log.append("counters")
            reading.on = True
            try:
                return inner()
            finally:
                reading.on = False

        def _allreduce(*args, log=log, inner=allreduce, **kwargs):
            log.append("submit")
            return inner(*args, **kwargs)

        m._comm.lane_stats, m.ring_counters, m.allreduce = _lane_stats, _counters, _allreduce
    rngs = [np.random.default_rng(40 + r) for r in range(2)]
    for _ in range(3):
        pair.step([_tree(rng) for rng in rngs])
    _gathers_done()
    for m, log in zip(pair.managers, logs):
        syncs = _syncs(m)
        n = syncs[0]["buckets"]
        # (the second reading is the gather thread's, after its last ``work.wait()``)
        assert log == 3 * (["counters", "lane_stats"] + ["submit"] * n + ["counters", "lane_stats"])
        # a life's first round trip may begin before its quorum is adopted:
        # the epoch changes under it and it records none; the next two do
        for e in syncs[1:]:
            assert e["ring_bytes"] > 0
            phases = e["ring_reduce_s"] + e["ring_average_s"] + e["ring_gather_s"]
            assert e["ring_average_s"] > 0.0 and 0.0 < phases <= e["duration_s"] + 1e-3
            assert 0.0 <= e["ring_tail_s"] <= phases + 1e-5
            assert all(e[k] >= 0.0 for k in ("ring_rx_s", "ring_add_s", "ring_tx_s"))


@pytest.mark.parametrize(
    "before,after,expects",
    [
        # two lanes of four sent bytes: a lane's seconds are the mean over those two
        (
            dict(epoch=3, lane_tx_bytes=[10, 10, 5, 5], lane_rx_s=[1.0, 1.0, 1.0, 1.0], lane_add_s=[0.5] * 4,
                 lane_tx_s=[0.0] * 4, ring_reduce_s=2.0, ring_average_s=0.1, ring_gather_s=1.0, ring_tail_s=0.2),
            dict(epoch=3, lane_tx_bytes=[110, 60, 5, 5], lane_rx_s=[1.4, 1.2, 9.0, 1.0], lane_add_s=[0.7, 0.6, 0.5, 0.5],
                 lane_tx_s=[0.2, 0.4, 0.0, 0.0], ring_reduce_s=2.5, ring_average_s=0.15, ring_gather_s=1.25, ring_tail_s=0.3),
            dict(ring_bytes=150, striped_bytes=50, ring_rx_s=0.3, ring_add_s=0.15, ring_tx_s=0.3,
                 ring_reduce_s=0.5, ring_average_s=0.05, ring_gather_s=0.25, ring_tail_s=0.1),
        ),
        # the epoch changed under the round trip: the counts began anew, none is recorded
        (
            dict(epoch=3, lane_tx_bytes=[10], lane_rx_s=[1.0], lane_add_s=[1.0], lane_tx_s=[1.0],
                 ring_reduce_s=2.0, ring_average_s=0.1, ring_gather_s=1.0, ring_tail_s=0.2),
            dict(epoch=4, lane_tx_bytes=[90], lane_rx_s=[3.0], lane_add_s=[3.0], lane_tx_s=[3.0],
                 ring_reduce_s=4.0, ring_average_s=0.3, ring_gather_s=3.0, ring_tail_s=0.4),
            dict(ring_bytes=0, striped_bytes=0),
        ),
        # a communicator that counts bytes and no time (a tier of its own): bytes alone
        (dict(epoch=1, lane_tx_bytes=[1, 2]), dict(epoch=1, lane_tx_bytes=[4, 8]), dict(ring_bytes=9, striped_bytes=6)),
        # no lanes at all (one member, a stand-in)
        (dict(epoch=1), dict(epoch=1), dict(ring_bytes=0, striped_bytes=0)),
        # time counted and no byte sent: zeros of a lane, the op thread's as they are
        (
            dict(epoch=1, lane_tx_bytes=[7], lane_rx_s=[1.0], lane_add_s=[1.0], lane_tx_s=[1.0],
                 ring_reduce_s=0.0, ring_average_s=0.0, ring_gather_s=0.0, ring_tail_s=0.0),
            dict(epoch=1, lane_tx_bytes=[7], lane_rx_s=[1.0], lane_add_s=[1.0], lane_tx_s=[1.0],
                 ring_reduce_s=0.0, ring_average_s=0.25, ring_gather_s=0.0, ring_tail_s=0.0),
            dict(ring_bytes=0, striped_bytes=0, ring_rx_s=0.0, ring_add_s=0.0, ring_tx_s=0.0,
                 ring_reduce_s=0.0, ring_average_s=0.25, ring_gather_s=0.0, ring_tail_s=0.0),
        ),
    ],
    ids=["two_lanes_of_four", "epoch_changed", "bytes_alone", "no_lanes", "no_byte_sent"],
)
def test_ring_account_differences_two_readings(before, after, expects) -> None:
    from torchft_tpu.ddp import _ring_account

    got = _ring_account(before, after)
    assert set(got) == set(expects)
    assert all(got[k] == pytest.approx(expects[k], abs=1e-9) for k in expects)


def test_a_second_manager_starts_cold(lighthouse_addr, monkeypatch) -> None:
    """Rule 3: the store lives and dies with its Manager; one at module level
    would hand a new life the dead life's warm pages."""
    monkeypatch.setenv(BUCKET_CAP_MB_ENV, str(2048 / (1 << 20)))
    first_handed: List[np.ndarray] = []
    for life in range(2):
        p = _Pair(lighthouse_addr)
        try:
            rngs = [np.random.default_rng(30 + r) for r in range(2)]
            for _ in range(2):
                p.step([_tree(rng) for rng in rngs])
            for m, handed in zip(p.managers, p.handed):
                assert [e["warm_buckets"] for e in _syncs(m)] == [0, _syncs(m)[0]["buckets"]]
                assert not any(np.shares_memory(a, b) for a in handed for b in first_handed)
                assert m._host_buckets is not None
            if life == 0:
                first_handed = [a for handed in p.handed for a in handed]
        finally:
            p.shutdown()
        # ``shutdown()`` lets the store go
        assert all(m._host_buckets is None for m in p.managers)
    assert not [n for n in vars(ddp) if isinstance(getattr(ddp, n), ddp._BucketStore)]


def test_results_of_step_k_are_unchanged_after_step_k_plus_one(pair) -> None:
    """Rule 2, both leaf kinds: a numpy leaf that was a view of its bucket, or
    a ``jax.Array`` that the CPU backend made over the bucket's memory without
    a copy, would read step k+1's values after step k+1."""
    rngs = [np.random.default_rng(40 + r) for r in range(2)]
    kept_outs, kept_bits = [], []
    for _ in range(3):
        outs = pair.step([_tree(rng) for rng in rngs])
        kept_outs.append(outs)
        kept_bits.append([[_bits(l) for l in jax.tree_util.tree_leaves(o)] for o in outs])
    for outs, bits in zip(kept_outs, kept_bits):
        for out, want in zip(outs, bits):
            leaves = jax.tree_util.tree_leaves(out)
            assert [_bits(l) for l in leaves] == want
            for handed in pair.handed:
                for leaf in leaves:
                    if isinstance(leaf, np.ndarray):
                        assert not any(np.shares_memory(leaf, a) for a in handed)


# ----------------------------------------------------------------------
# one Manager, a communicator the test holds
# ----------------------------------------------------------------------


class _HeldComm(DummyCommunicator):
    """Passthrough whose works end when, and how, the test says."""

    def __init__(self) -> None:
        super().__init__()
        self.hold = False
        self.fail_with: Optional[Callable[[], BaseException]] = None
        self.held: List["Future[Any]"] = []
        self.buffers: List[np.ndarray] = []

    def allreduce(self, buffers, op=None, in_place=False, divisor=None) -> Work:  # type: ignore[override]
        fut: "Future[Any]" = Future()
        if self.fail_with is not None:
            fut.set_exception(self.fail_with())
            return Work(fut)
        # the passthrough's own average (PR 40: the communicator divides)
        buffers = super().allreduce(buffers, in_place=in_place, divisor=divisor).wait()
        if self.hold:
            self.held.append(fut)
            self.buffers.append(buffers)
        else:
            fut.set_result(buffers)
        return Work(fut)

    def release(self) -> None:
        for fut, buffers in zip(self.held, self.buffers):
            fut.set_result(buffers)
        self.held, self.buffers = [], []


class _Solo:
    def __init__(self, steps: int = 16) -> None:
        self.comm = _HeldComm()
        self.client = StubClient()
        self.client.quorum_results.extend(_quorum_result() for _ in range(steps))
        self.manager = Manager(
            comm=self.comm,
            load_state_dict=None,
            state_dict=None,
            min_replica_size=1,
            checkpoint_transport=MemoryTransport(),
            _manager_client=self.client,
            rank=0,
            world_size=1,
        )
        self.handed = _record_handed(self.manager)

    def step(self, tree: Any, **kwargs: Any) -> Any:
        self.manager.start_quorum()
        out = allreduce_pytree(self.manager, tree, **kwargs).wait(timeout=10.0)
        self.manager.should_commit()
        _gathers_done()
        return out


@pytest.fixture()
def solo():
    s = _Solo()
    yield s
    s.manager.shutdown()


def _failure(kind: str) -> Callable[[], BaseException]:
    return {
        "raised": lambda: RuntimeError("peer closed the connection mid-ring"),
        "timed_out": lambda: TimeoutError("allreduce timed out after 60 s"),
    }[kind]


@pytest.mark.parametrize("cut", [False, True], ids=["whole_leaves", "a_leaf_in_pieces"])
@pytest.mark.parametrize("kind", ["raised", "timed_out", "errored_before_submit"])
def test_a_failed_round_trip_never_gives_its_buckets_back(solo, monkeypatch, kind, cut) -> None:
    """Rule 1: an op thread that is still receiving writes into memory nobody
    reuses."""
    if cut:
        # 32 bytes: ``b`` crosses in four pieces, views of ONE kept buffer (a
        # failed ring of any of them keeps the whole set out)
        monkeypatch.setenv(BUCKET_CAP_MB_ENV, str(32 / (1 << 20)))
    tree = {"a": np.arange(64, dtype=np.float32), "b": jnp.ones((8, 8), jnp.bfloat16)}
    solo.step(tree)
    n = len(solo.handed)
    assert n == (5 if cut else 2) and _syncs(solo.manager)[-1]["split_bytes"] == (128 if cut else 0)
    solo.step(tree)  # warm: the first step's buckets
    assert all(np.shares_memory(solo.handed[b], solo.handed[n + b]) for b in range(n))

    if kind == "errored_before_submit":
        # the first bucket's ring fails, the second is never submitted to one
        inner, calls = solo.comm.allreduce, []

        def _first_fails(buffers, *args, **kwargs):
            calls.append(buffers)
            if len(calls) == 1:
                fut: "Future[Any]" = Future()
                fut.set_exception(RuntimeError("ring broke"))
                return Work(fut)
            return inner(buffers, *args, **kwargs)

        solo.comm.allreduce = _first_fails  # type: ignore[method-assign]
    else:
        solo.comm.fail_with = _failure(kind)
    out = solo.step(tree)  # the round trip that fails, in the kept buckets
    assert solo.manager.errored() is not None
    np.testing.assert_array_equal(out["a"], tree["a"])  # the input rides through
    poisoned = solo.handed[2 * n : 3 * n]
    assert _syncs(solo.manager)[-1]["warm_buckets"] == n
    if kind == "errored_before_submit":
        del solo.comm.allreduce
    solo.comm.fail_with = None

    solo.step(tree)  # the step after: fresh memory, cold
    after = solo.handed[3 * n : 4 * n]
    assert len(after) == n
    assert not any(np.shares_memory(a, p) for a in after for p in poisoned)
    assert _syncs(solo.manager)[-1]["warm_buckets"] == 0
    solo.step(tree)  # and kept again from there
    assert all(np.shares_memory(a, b) for a, b in zip(after, solo.handed[4 * n : 5 * n]))
    assert _syncs(solo.manager)[-1]["warm_buckets"] == n


def test_a_set_that_is_still_out_is_not_handed_out_again(solo) -> None:
    """Rule 1 again, and the streamed case: two fragments of one signature in
    flight hold different memory; both sets are kept afterwards."""
    tree = {"a": np.arange(64, dtype=np.float32)}
    manager = solo.manager
    manager.start_quorum()
    solo.comm.hold = True
    works = [allreduce_pytree(manager, tree, stream=frag) for frag in range(2)]
    first, second = solo.handed
    assert not np.shares_memory(first, second)
    solo.comm.release()
    for frag, w in enumerate(works):
        np.testing.assert_array_equal(w.wait(timeout=10.0)["a"], tree["a"] / 2)
        manager.stream_resolved(frag, True)
    _gathers_done()
    assert manager._host_buckets.kept_bytes() == 2 * first.nbytes
    # the next two in flight are both warm, each in a set of its own
    works = [allreduce_pytree(manager, tree, stream=frag) for frag in range(2)]
    third, fourth = solo.handed[2:]
    assert not np.shares_memory(third, fourth)
    assert all(any(np.shares_memory(x, y) for y in (first, second)) for x in (third, fourth))
    solo.comm.release()
    for w in works:
        w.wait(timeout=10.0)
    assert [e["warm_buckets"] for e in _syncs(manager)] == [0, 0, 1, 1]


def test_a_warm_call_allocates_nothing_of_the_payloads_size(solo) -> None:
    """What the train thread pays: with kept buckets the call makes no array
    of the payload's size (the parent made one a bucket, every page of it
    touched for the first time)."""
    tree = {
        "w": np.ones(1 << 20, dtype=np.float32),
        "v": np.ones(1 << 20, dtype=np.float32),
    }
    payload = 2 * (4 << 20)
    solo.step(tree)
    solo.manager.start_quorum()
    solo.comm.hold = True  # the restore (which copies numpy leaves out) waits
    tracemalloc.start()
    try:
        work = allreduce_pytree(solo.manager, tree)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert len(solo.handed) == 2 and solo.handed[-1].nbytes == payload
    assert peak < payload / 4, peak
    solo.comm.release()
    out = work.wait(timeout=10.0)
    np.testing.assert_array_equal(out["w"], np.full(1 << 20, 0.5, np.float32))


def test_the_store_is_bounded(solo) -> None:
    """Rule 4: signatures that stop coming are dropped after a fixed number
    of others, and a signature keeps a fixed number of sets."""
    store_bytes = []
    for n in range(3 * ddp._KEPT_SIGNATURES):
        solo.step({"a": np.ones(1000 + n, dtype=np.float32)})
        store = solo.manager._host_buckets
        assert len(store._plans) <= ddp._KEPT_SIGNATURES
        store_bytes.append(store.kept_bytes())
    assert max(store_bytes) <= ddp._KEPT_SIGNATURES * 4 * (1000 + 3 * ddp._KEPT_SIGNATURES)
    # one in, one out: the newest, and the one that came _KEPT_SIGNATURES ago
    assert store_bytes[-1] == store_bytes[-2] + 4 * ddp._KEPT_SIGNATURES
    # more fragments of one signature in flight than a signature keeps sets
    solo.manager.start_quorum()
    solo.comm.hold = True
    tree = {"a": np.ones(500, dtype=np.float32)}
    works = [allreduce_pytree(solo.manager, tree, stream=f) for f in range(ddp._KEPT_SETS + 2)]
    solo.comm.release()
    for w in works:
        w.wait(timeout=10.0)
    _gathers_done()
    (plan,) = [p for p in store._plans.values() if p.nbytes == 2000]
    assert len(plan.free) == ddp._KEPT_SETS


def test_no_knob() -> None:
    import inspect

    assert list(inspect.signature(allreduce_pytree).parameters) == [
        "manager", "tree", "should_quantize", "stream",
    ]
    source = inspect.getsource(ddp)
    assert source.count("os.environ") == 1  # the bucket cap, as before
    # the window and the order (ISSUE 32): a constant and a function of the
    # buckets' sizes, not an argument, an environment variable or a knob; the
    # window counts bytes (ISSUE 46) and the count of buckets is gone, and a
    # piece's size is the cap: no second one beside it
    assert type(ddp._D2H_AHEAD_BYTES) is int and ddp._D2H_AHEAD_BYTES >= 1
    assert not hasattr(ddp, "_D2H_AHEAD")
    assert list(inspect.signature(ddp._pieces).parameters) == ["shape", "itemsize", "cap"]
    assert list(inspect.signature(ddp._pipeline_order).parameters) == ["nbytes"]
    assert list(inspect.signature(ddp._make_plan).parameters) == ["leaves", "bucket_cap"]
    assert "knobs" not in source and "getenv" not in source
    # the loop that started every leaf's copy at once is gone, not switched off
    assert source.count("copy_to_host_async()") == 1


# ----------------------------------------------------------------------
# the order the buckets cross in, and how far ahead their copies start
# ----------------------------------------------------------------------


class _Shape:
    """What ``_make_plan`` reads of a leaf that is no ``jax.Array``."""

    def __init__(self, shape, dtype) -> None:
        self.shape, self.dtype = tuple(shape), np.dtype(dtype)
        self.size = int(np.prod(self.shape, dtype=np.int64))
        self.nbytes = self.size * self.dtype.itemsize


def _cell_trees(name: str) -> List[Any]:
    """The trees a trainer of the benchmark's cell sends through
    ``allreduce_pytree`` in a step, as shapes (``jax.eval_shape``): the
    gradients (a state leaf's slot carries its float32 signal) and, for a
    model with state the optimizer does not own under ``quantize_outer``,
    the signal by itself."""
    from ftbench import spec

    cell = spec.load_cell(name)
    model = cell.architecture.model(cell.config)
    shapes = jax.tree_util.tree_leaves(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    mask = jax.tree_util.tree_leaves(model.state_mask()) if hasattr(model, "state_mask") else [False] * len(shapes)
    grads = [_Shape(s.shape, np.float32 if is_state else s.dtype) for s, is_state in zip(shapes, mask)]
    return [grads] + ([[g for g, is_state in zip(grads, mask) if is_state]] if any(mask) else [])


@pytest.mark.parametrize(
    "cell,which",
    [("mistral7b-ddp2-steady", 0), ("mistral7b-hsdp2x2-steady", 0), ("ling3flash-ws1-seq8k", 0), ("ling3flash-ws1-seq8k", 1)],
    ids=["mistral_gradients", "mistral_2x2_gradients", "ling_gradients", "ling_signal"],
)
def test_two_managers_derive_one_order_from_the_bucket_sizes_alone(cell, which) -> None:
    leaves = _cell_trees(cell)[which]
    cap = ddp._bucket_cap_bytes()
    plans = [ddp._BucketStore().plan(("signature",), leaves, cap) for _ in range(2)]
    assert plans[0] is not plans[1]
    layouts = [[[slot.index for slot in b.slots] for b in p.buckets] for p in plans]
    assert layouts[0] == layouts[1]
    # every leaf in exactly one bucket, a bucket's leaves in the tree's order
    assert sorted(i for group in layouts[0] for i in group) == list(range(len(leaves)))
    assert all(group == sorted(group) for group in layouts[0])
    sizes = [b.size * b.dtype.itemsize for b in plans[0].buckets]
    assert sum(sizes) == plans[0].nbytes == sum(l.nbytes for l in leaves)
    # the smallest first, then by falling size; ties by place in the tree
    if len(sizes) > 1:
        assert sizes[0] == min(sizes) and sizes[1:] == sorted(sizes[1:], reverse=True)
    # ... ties by place in the tree's listing: a dtype's buckets together (a
    # ring a dtype), the dtypes as they first come
    dtypes = [l.dtype.name for l in leaves]
    place = [(dtypes.index(b.dtype.name), group[0]) for b, group in zip(plans[0].buckets, layouts[0])]
    for a, b in zip(range(1, len(sizes)), range(2, len(sizes))):
        assert sizes[a] > sizes[b] or place[a] < place[b]
    # a function of the sizes alone: the same sizes from anywhere, the same order
    in_tree = sorted(range(len(sizes)), key=lambda b: place[b])
    order = ddp._pipeline_order([sizes[b] for b in in_tree])
    assert [in_tree[b] for b in order] == list(range(len(sizes)))
    if cell.startswith("mistral"):
        # these leaves are shapes and no ``jax.Array``s, so none is cut: the
        # buffers of the cell's plan.  On the chip every one over the cap
        # crosses in ``_pieces``' pieces: the buckets a step and DDP_SYNC's
        # ``split_bytes`` of ``bytes`` (PERF.md section 6, PR 46)
        want_mb, buckets, split, total = {
            "mistral7b-ddp2-steady": (
                [0.0, 268.4, 268.4, 117.4, 117.4, 117.4, 33.6, 33.6, 8.4, 8.4], 62, 956301312, 973127680,
            ),
            "mistral7b-hsdp2x2-steady": (
                [0.1, 268.4, 268.4, 234.9, 234.9, 234.9, 67.1, 67.1, 16.8, 16.8], 89, 1375731712, 1409368064,
            ),
        }[cell]
        assert [round(n / 1e6, 1) for n in sizes] == want_mb and cap == 16 << 20
        over = [l for l in leaves if l.nbytes > cap]
        pieces = [ddp._pieces(l.shape, l.dtype.itemsize, cap) for l in over]
        assert len(sizes) - len(over) + sum(map(len, pieces)) == buckets
        assert (sum(l.nbytes for l in over), plans[0].nbytes) == (split, total)
        assert max(size for of_leaf in pieces for _, _, size in of_leaf) * 2 == cap


def test_the_order_of_sizes() -> None:
    assert ddp._pipeline_order([]) == []
    assert ddp._pipeline_order([7]) == [0]
    assert ddp._pipeline_order([5, 5, 5]) == [2, 0, 1]
    assert ddp._pipeline_order([268, 117, 117, 117, 8, 34, 34, 8, 268, 1]) == [9, 0, 8, 1, 2, 3, 5, 6, 4, 7]


# ----------------------------------------------------------------------
# a leaf over the cap crosses in pieces (ISSUE 46): the plan as a pure function
# ----------------------------------------------------------------------


@pytest.mark.parametrize(
    "shape,itemsize,cap,count,largest",
    [
        ((32768, 4096), 2, 16 << 20, 16, 16 << 20),  # Mistral's embedding at the default cap: rows a:b
        ((4096, 32768), 2, 16 << 20, 16, 16 << 20),  # its head
        ((1, 4096, 14336), 2, 16 << 20, 8, 512 * 28672),  # ddp2-steady's stacked MLP matrices: [0, a:b, :]
        ((2, 4096, 14336), 2, 16 << 20, 16, 512 * 28672),  # hsdp2x2-steady's: [i, a:b, :]
        ((2, 14336, 4096), 2, 16 << 20, 14, 2048 * 8192),  # seven pieces a layer
        ((2, 4096, 4096), 2, 32 << 20, 2, 32 << 20),  # its attention matrices at a cap of 32 MiB: [i, :, :]
        ((1000, 7), 4, 100, 334, 84),  # a long leading axis, three rows a piece at most
        ((3, 5, 11), 4, 64, 15, 44),  # a row of the middle axis fits, a row of the first does not
        ((4, 300), 4, 256, 20, 240),  # a row of the LAST axis is over the cap: [i, a:b]
        ((1, 1, 9), 8, 8, 9, 8),  # one element a piece
        ((5,), 8, 4, 5, 8),  # an element over the cap goes alone, over it
        ((129,), 1, 64, 3, 43),  # as equal as they can be: 43 each, not 64, 64, 1
    ],
)
def test_pieces_tile_the_leaf_in_row_major_order(shape, itemsize, cap, count, largest) -> None:
    pieces = ddp._pieces(shape, itemsize, cap)
    assert len(pieces) == count and max(size for _, _, size in pieces) * itemsize == largest
    total = int(np.prod(shape, dtype=np.int64))
    numbered = np.arange(total).reshape(shape) if total < 100_000 else None  # the cells' leaves by arithmetic
    at = 0
    for index, start, size in pieces:
        # a contiguous range, where the last one ended: fixed indices of the
        # leading axes, then a slice of one axis
        assert all(isinstance(i, int) for i in index[:-1]) and isinstance(index[-1], slice)
        first = index[:-1] + (index[-1].start,) + (0,) * (len(shape) - len(index))
        row = int(np.prod(shape[len(index):], dtype=np.int64))
        assert start == at == np.ravel_multi_index(first, shape) and size == (index[-1].stop - index[-1].start) * row
        if numbered is not None:
            assert numbered[index].reshape(-1).tolist() == list(range(start, start + size))
        assert size * itemsize <= max(cap, itemsize)
        at += size
    assert at == total
    sizes = [size for _, _, size in pieces]
    assert max(sizes) - min(sizes) <= int(np.prod(shape[len(pieces[0][0]):]))  # a row apart at most


@pytest.mark.parametrize("axis", [0, 1, None], ids=["rows_on_two_chips", "columns_on_two_chips", "replicated"])
@pytest.mark.parametrize("cap", [96, 500, 4096])
def test_a_group_of_two_chips_and_a_group_of_one_derive_the_same_pieces(axis, cap) -> None:
    """The pieces follow from the shape, the dtype and the cap; where a piece
    lies on the chips is each process's own matter."""
    rng = np.random.default_rng(80)
    host = {
        "big": rng.standard_normal((40, 12)).astype(np.float32),
        "stack": rng.standard_normal((2, 8, 16)).astype(np.float32),
        "small": rng.standard_normal(5).astype(np.float32),
        "host": rng.standard_normal((30, 12)).astype(np.float32),
    }
    devices = jax.devices()
    trees = [
        {name: v if name == "host" else _put(v, None if name == "small" else axis, devs) for name, v in host.items()}
        for devs in (devices[:2], devices[2:3])
    ]
    plans = [ddp._make_plan(jax.tree_util.tree_leaves(t), cap) for t in trees]
    described = [
        [(b.buffer, b.offset, b.size, b.last, b.piece and b.piece.shape, [s.index for s in b.slots]) for b in p.buckets]
        for p in plans
    ]
    assert described[0] == described[1]
    assert [(p.buffers, p.nbytes, p.split_nbytes) for p in plans][0] == (plans[1].buffers, plans[1].nbytes, plans[1].split_nbytes)
    over = [v.nbytes for name, v in host.items() if name != "host" and v.nbytes > cap]
    assert plans[0].split_nbytes == sum(over) and (cap == 4096) == (not over)
    sizes = [b.nbytes for b in plans[0].buckets]
    assert sizes[0] == min(sizes) and sizes[1:] == sorted(sizes[1:], reverse=True)
    # no bucket over the cap but the numpy leaf's, which goes whole
    assert sorted(sizes)[-2] <= cap < sizes[1] == host["host"].nbytes or cap == 4096
    # a buffer's buckets tile it, and the last of them in the plan's order restores the leaf
    for k, (_dtype, size) in enumerate(plans[0].buffers):
        mine = [b for b in plans[0].buckets if b.buffer == k]
        assert sorted((b.offset, b.offset + b.size) for b in mine)[0][0] == 0 and sum(b.size for b in mine) == size
        assert [b.last for b in mine] == [False] * (len(mine) - 1) + [True]
    # each process finds every element of a piece exactly once on its own chips
    for plan, tree in zip(plans, trees):
        leaves = jax.tree_util.tree_leaves(tree)
        for b in plan.buckets:
            if b.piece is None:
                continue
            shards = list(ddp._unique_local_shards(leaves[b.slots[0].index]).values())
            got = np.full(b.piece.shape, np.nan, np.float32)
            for src in b.piece.sources:
                block = np.asarray(shards[src.place].data)[tuple(slice(s, s + n) for s, n in zip(src.starts, src.sizes))]
                assert np.isnan(got[src.where]).all()
                got[src.where] = block
            want = np.asarray(leaves[b.slots[0].index]).reshape(-1)[b.offset : b.offset + b.size]
            assert _bits(got) == _bits(want)


def _parents_buckets(leaves: List[Any], cap: int) -> List[List[int]]:
    """The plan of the commit before ISSUE 46: by dtype, cut between leaves
    at the cap, in ``_pipeline_order``."""
    by_dtype: Dict[str, List[int]] = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(np.asarray(leaf).dtype.name if not hasattr(leaf, "dtype") else leaf.dtype.name, []).append(i)
    groups: List[List[int]] = []
    for idxs in by_dtype.values():
        group: List[int] = []
        for i in idxs:
            if group and sum(leaves[j].nbytes for j in group) + leaves[i].nbytes > cap:
                groups.append(group)
                group = []
            group.append(i)
        groups.append(group)
    order = ddp._pipeline_order([sum(leaves[j].nbytes for j in g) for g in groups])
    return [groups[b] for b in order]


@pytest.mark.parametrize("cap", [4800, 6000, 1 << 20], ids=["cap_at_the_largest_leaf", "cap_over_it", "one_bucket_a_dtype"])
def test_a_tree_under_the_cap_derives_the_parents_plan(cap) -> None:
    leaves = jax.tree_util.tree_leaves(_tree(np.random.default_rng(81)))
    assert max(l.nbytes for l in leaves) == 4800
    plan = ddp._make_plan(leaves, cap)
    assert [[s.index for s in b.slots] for b in plan.buckets] == _parents_buckets(leaves, cap)
    assert plan.split_nbytes == 0 and all(b.piece is None and b.last and b.offset == 0 for b in plan.buckets)
    assert plan.buffers == [(b.dtype, b.size) for b in sorted(plan.buckets, key=lambda b: b.buffer)]
    assert sorted(b.buffer for b in plan.buckets) == list(range(len(plan.buckets)))
    for b in plan.buckets:
        assert [s.offset for s in b.slots] == [sum(t.size for t in b.slots[:k]) for k in range(len(b.slots))]
    # one byte less and the largest leaf, and it alone, crosses in pieces
    cut = ddp._make_plan(leaves, 4799)
    assert cut.split_nbytes == 4800 and sum(b.piece is not None for b in cut.buckets) == 2


@pytest.fixture()
def asked(monkeypatch):
    """Every ``copy_to_host_async`` of a jax array, in order: the array's id
    and its bytes."""
    calls: List[Any] = []
    array_type = type(jnp.zeros(1))
    inner = array_type.copy_to_host_async

    def _recorded(self: Any) -> None:
        calls.append((id(self), self.nbytes))
        inner(self)

    monkeypatch.setattr(array_type, "copy_to_host_async", _recorded)
    return calls


@pytest.mark.parametrize("window", [1, 3000, 4096, 1 << 30])
def test_copies_start_a_window_of_bytes_ahead_of_the_ring(solo, asked, monkeypatch, window) -> None:
    """At the submit of the bucket in place b of the plan the copies of a
    prefix of the plan have been started: bucket b's own, and the next ones'
    until the bytes from b on pass the window, none of a later one.  By the
    end every jax leaf under the cap was asked for exactly once, every piece
    of a leaf over it exactly once as a transfer of its own, and no numpy
    leaf at all."""
    monkeypatch.setenv(BUCKET_CAP_MB_ENV, str(2048 / (1 << 20)))
    monkeypatch.setattr(ddp, "_D2H_AHEAD_BYTES", window)
    rng = np.random.default_rng(50)
    tree = {f"w{k}": jnp.asarray(rng.standard_normal(100 * (k + 1)).astype(np.float32)) for k in range(9)}
    tree["host"] = rng.standard_normal(700).astype(np.float32)
    leaves = jax.tree_util.tree_leaves(tree)
    seen: List[int] = []
    inner = solo.manager.allreduce

    def _allreduce(data: Any, *args: Any, **kwargs: Any) -> Work:
        seen.append(len(asked))
        return inner(data, *args, **kwargs)

    solo.manager.allreduce = _allreduce  # type: ignore[method-assign]
    for _ in range(2):  # a cold round trip and a warm one
        del asked[:], seen[:]
        out = solo.step(tree)
        (plan,) = solo.manager._host_buckets._plans.values()
        n = len(plan.buckets)
        # w5 .. w8 (2,400 to 3,600 bytes) cross in two pieces each, the numpy
        # leaf of 2,800 bytes whole
        assert n == len(seen) == 13 and sum(b.piece is not None for b in plan.buckets) == 8
        assert max(b.nbytes for b in plan.buckets) == 2800
        # what a bucket asks for: its jax leaves as they are, or its piece
        asks = [
            [(None, b.nbytes)] if b.piece is not None
            else [(id(leaves[s.index]), leaves[s.index].nbytes) for s in b.slots if s.sharding is not None]
            for b in plan.buckets
        ]
        crossing = [sum(nbytes for _, nbytes in ask) for ask in asks]
        assert crossing == [b.crossing for b in plan.buckets] and crossing.count(0) == 1
        started = 0
        for b in range(n):
            assert seen[b] >= seen[max(b - 1, 0)]
            started = next(k for k in range(n + 1) if sum(len(a) for a in asks[:k]) == seen[b])
            assert started > b
            assert sum(crossing[b:started]) >= window or started == n, (b, started)
            assert sum(crossing[b : started - 1]) < window or started == b + 1, (b, started)
        flat_asks = [a for ask in asks for a in ask]
        assert len(asked) == len(flat_asks)
        for (got_id, got_bytes), (want_id, want_bytes) in zip(asked, flat_asks):
            assert got_bytes == want_bytes and want_id in (None, got_id)
        whole = [i for i, _ in flat_asks if i is not None]
        assert len(set(whole)) == len(whole) and id(tree["host"]) not in [i for i, _ in asked]
        assert _syncs(solo.manager)[-1]["split_bytes"] == sum(tree[f"w{k}"].nbytes for k in range(5, 9))
        for name, leaf in tree.items():
            np.testing.assert_array_equal(np.asarray(out[name]), np.asarray(leaf) / 2)


@pytest.mark.parametrize("kind", ["one_jax_bucket", "numpy_leaves", "fewer_bytes_than_the_window"])
def test_trees_the_window_does_not_reach_go_through_unchanged(solo, asked, kind) -> None:
    """One bucket, no jax leaf, or fewer bytes than the window: what the
    parent did (every copy started before the first wait)."""
    rng = np.random.default_rng(60)
    f32 = lambda n: rng.standard_normal(n).astype(np.float32)  # noqa: E731
    tree = {
        "one_jax_bucket": {"a": jnp.asarray(f32(64)), "b": jnp.asarray(f32(8))},
        "numpy_leaves": {"a": f32(64), "b": f32(8).astype(np.float64), "c": 3.0},
        "fewer_bytes_than_the_window": {"a": jnp.asarray(f32(64)), "b": jnp.asarray(f32(8)).astype(jnp.bfloat16)},
    }[kind]
    seen: List[int] = []
    inner = solo.manager.allreduce

    def _allreduce(data: Any, *args: Any, **kwargs: Any) -> Work:
        seen.append(len(asked))
        return inner(data, *args, **kwargs)

    solo.manager.allreduce = _allreduce  # type: ignore[method-assign]
    out = solo.step(tree)
    n_jax = sum(isinstance(l, jax.Array) for l in jax.tree_util.tree_leaves(tree))
    buckets = {"one_jax_bucket": 1, "numpy_leaves": 2, "fewer_bytes_than_the_window": 2}[kind]
    assert seen == [n_jax] * buckets and len(asked) == n_jax
    sync = _syncs(solo.manager)[-1]
    assert sync["bytes"] < ddp._D2H_AHEAD_BYTES and sync["split_bytes"] == 0
    for name, leaf in tree.items():
        got = out[name]
        assert isinstance(got, jax.Array) == isinstance(leaf, jax.Array)
        want = _div(np.asarray(leaf) + np.zeros_like(np.asarray(leaf)), 2)
        assert _bits(got) == _bits(want)
    assert sync["first_submit_s"] > 0.0


# ----------------------------------------------------------------------
# a group's sharded leaves go from each chip's shard into the bucket (ISSUE 44)
# ----------------------------------------------------------------------

LAYOUTS = {
    # name: (shape, the axis laid over the group's two chips; None: see _put)
    "axis_0": ((64, 6), 0),
    "axis_1": ((6, 64), 1),
    "axis_2_of_a_stacked_leaf": ((3, 4, 32), 2),
    "replicated_over_the_group": ((40, 3), None),
    "on_one_device": ((40, 3), None),
    "numpy_leaf_and_python_scalar_beside": ((6, 64), 1),
}


def _put(host: np.ndarray, axis: Optional[int], devices: List[Any], one_device: bool = False) -> jax.Array:
    """``host`` on ``devices``: on the first alone, or over all of them,
    ``axis`` in equal shards (None: every one holds the whole)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if one_device or len(devices) == 1:
        return jax.device_put(host, devices[0])
    spec = P() if axis is None else P(*([None] * axis + ["fsdp"]))
    return jax.device_put(host, NamedSharding(Mesh(np.array(devices), ("fsdp",)), spec))


def _layout_trees(kind: str, dtype: Any, rng: np.random.Generator):
    """(host values, the fsdp-2 group's tree on chips 0 and 1, the one-chip
    group's tree on chip 2, the names of the leaves that go direct)."""
    shape, axis = LAYOUTS[kind]
    draw = lambda *s: rng.standard_normal(s).astype(np.float32).astype(dtype)  # noqa: E731
    host: Dict[str, Any] = {"a_kind": draw(*shape), "b_axis_1": draw(10, 8), "c_one_device": draw(7)}
    if kind.startswith("numpy_leaf"):
        host["host"] = rng.standard_normal((5, 3)).astype(np.float32)
        host["scalar"] = float(rng.standard_normal())
    devices = jax.devices()
    group, chip = devices[:2], devices[2:3]
    axes = {"a_kind": axis, "b_axis_1": 1}
    alone = {"c_one_device"} | ({"a_kind"} if kind == "on_one_device" else set())
    trees = [
        {
            name: value if name in ("host", "scalar") else _put(value, axes.get(name), devs, one_device=name in alone)
            for name, value in host.items()
        }
        for devs in (group, chip)
    ]
    direct = ["b_axis_1"] + (["a_kind"] if axis is not None else [])
    return host, trees[0], trees[1], direct


def _record_copies(manager: Manager) -> List[np.ndarray]:
    """A COPY of every buffer this Manager's ``allreduce`` is handed, as it
    was handed (the ring reduces in place)."""
    copies: List[np.ndarray] = []
    inner = manager.allreduce

    def _allreduce(data: Any, *args: Any, **kwargs: Any) -> Work:
        copies.append(np.array(data, copy=True))
        return inner(data, *args, **kwargs)

    manager.allreduce = _allreduce  # type: ignore[method-assign]
    return copies


def _newest_plan(manager: Manager) -> Any:
    return next(reversed(manager._host_buckets._plans.values()))


def _buffers_of(plan: Any, copies: List[np.ndarray]) -> List[bytes]:
    """The plan's host buffers as the rings were handed them: a bucket's own
    bytes, or a leaf's pieces put together at their places."""
    out = [np.zeros(size, dtype) for dtype, size in plan.buffers]
    for bucket, copy in zip(plan.buckets, copies):
        out[bucket.buffer][bucket.offset : bucket.offset + bucket.size] = copy
    return [b.tobytes() for b in out]


@pytest.mark.parametrize("kept_set", [False, True], ids=["no_kept_set", "kept_set"])
@pytest.mark.parametrize(
    "cap", [64, 256, 1 << 20], ids=["cap_of_a_row_or_less", "cap_of_some_rows", "cap_above_the_tree"]
)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, np.float32], ids=["bfloat16", "float32"])
@pytest.mark.parametrize("kind", sorted(LAYOUTS))
def test_sharded_leaves_go_from_their_shards_into_the_bucket(
    lighthouse_addr, monkeypatch, kind, dtype, cap, kept_set
) -> None:
    """The two small caps cut INSIDE the leaves (ISSUE 46): 64 bytes is a row
    of ``axis_0`` or a part of a row of the others, so a piece lies in one
    chip's shard or, cut along the sharded axis's rows, in both; 256 bytes is
    some rows, and a piece of ``axis_0`` straddles the two shards."""
    monkeypatch.setenv(BUCKET_CAP_MB_ENV, str(cap / (1 << 20)))
    p = _Pair(lighthouse_addr)
    try:
        copies = [_record_copies(m) for m in p.managers]
        # a life's first step heals replica 1, which then sends zeros: spend it
        # on a tree of another signature
        p.step([{"z": np.ones(3, np.float32)}] * 2)
        rng = np.random.default_rng(70)
        for step in range(2 if kept_set else 1):
            host, sharded, whole, direct = _layout_trees(kind, dtype, rng)
            for c in copies:
                del c[:]
            outs = p.step([sharded, whole])
            assert p.participating == [True, True]
            syncs = [_syncs(m)[-1] for m in p.managers]
            n = syncs[0]["buckets"]
            assert [e["warm_buckets"] for e in syncs] == [n if step else 0] * 2
            # a jax leaf over the cap is as many buckets as it has pieces; at
            # 64 bytes every other leaf is a bucket by itself
            over = {
                name: ddp._pieces(value.shape, value.dtype.itemsize, cap)
                for name, value in host.items()
                if name not in ("host", "scalar") and value.nbytes > cap
            }
            if cap == 64:
                assert set(over) == {"a_kind", "b_axis_1"}
                assert n == len(host) - len(over) + sum(len(pieces) for pieces in over.values())
            elif cap == 1 << 20:
                assert not over and n == len({np.asarray(v).dtype for v in host.values()})
            else:
                assert "a_kind" in over or host["a_kind"].nbytes == 240
            assert [e["split_bytes"] for e in syncs] == [sum(host[name].nbytes for name in over)] * 2
            assert max(c.nbytes for c in copies[0]) <= cap
        # (a) the averages: the parent's formula on the HOST values, in both
        # groups (c: the fsdp-2 group and the one-chip group averaged the same
        # elements), bit for bit, each leaf back in its own type and layout
        want = _expected([host, host])
        for tree, out in zip((sharded, whole), outs):
            for leaf, g, w in zip(*map(jax.tree_util.tree_leaves, (tree, out)), want):
                assert isinstance(g, jax.Array) == isinstance(leaf, jax.Array)
                assert not isinstance(leaf, jax.Array) or g.sharding == leaf.sharding
                assert np.asarray(g).dtype == w.dtype and _bits(g) == _bits(w), kind
        # (b) the wire: both groups handed the same bytes, and every leaf lies
        # in them whole and row-major
        assert [c.tobytes() for c in copies[0]] == [c.tobytes() for c in copies[1]]
        plans = [_newest_plan(m) for m in p.managers]
        wire = b"".join(_buffers_of(plans[0], copies[0]))
        assert wire == b"".join(_buffers_of(plans[1], copies[1]))
        for name, value in host.items():
            assert np.asarray(value).tobytes() in wire, name
        # both groups cut the wire at the same places, whatever lies where
        places = [[(b.buffer, b.offset, b.size, b.piece and b.piece.shape) for b in plan.buckets] for plan in plans]
        assert places[0] == places[1]
        if direct and over:
            # ... and found the pieces on their own chips: the group's in its
            # two shards, the one-chip group's in one
            sources = [[len(b.piece.sources) for b in plan.buckets if b.piece is not None] for plan in plans]
            assert set(sources[1]) == {1} and set(sources[0]) <= {1, 2}
            if (kind, cap, dtype) == ("axis_0", 256, np.float32):
                assert {1, 2} == set(sources[0])  # rows 28:37 lie on both chips
        assert sum(c.nbytes for c in copies[0]) == syncs[0]["bytes"] == syncs[1]["bytes"]
        # the counter: the bytes of the leaves that lie in shards, and of no other
        assert syncs[0]["direct_bytes"] == sum(host[name].nbytes for name in direct) > 0
        assert syncs[1]["direct_bytes"] == 0
        # (d) no whole-leaf host value was made of a leaf that lies in shards
        for name in direct:
            assert sharded[name]._npy_value is None, name

        # (a) again, against the parent's PATH: the same values through the
        # same Managers with no leaf direct (a store made anew plans anew)
        monkeypatch.setattr(ddp, "_direct_indices", lambda leaf: None)
        for m in p.managers:
            m._host_buckets = None
        direct_copies = [c.tobytes() for c in copies[0]]
        for c in copies:
            del c[:]
        again = [
            {name: value if not isinstance(value, jax.Array) else jax.device_put(host[name], value.sharding)
             for name, value in tree.items()}
            for tree in (sharded, whole)
        ]
        parents = p.step(again)
        assert _syncs(p.managers[0])[-1]["direct_bytes"] == 0
        assert [c.tobytes() for c in copies[0]] == direct_copies
        for out, parent in zip(outs, parents):
            for g, w in zip(*map(jax.tree_util.tree_leaves, (out, parent))):
                assert _bits(g) == _bits(w)
        # and there the whole leaf WAS made on the host: (d) can fail (a leaf
        # over the cap is whole on the host on neither path: its pieces are)
        for name in direct:
            assert (again[0][name]._npy_value is None) == (name in over), name
    finally:
        p.shutdown()


# ----------------------------------------------------------------------
# what is never cut, the round trip under stress, and the Manager's end (ISSUE 46)
# ----------------------------------------------------------------------


@pytest.mark.parametrize("cap", [64, 256, 1000])
def test_numpy_and_multi_host_leaves_stay_whole(solo, monkeypatch, cap) -> None:
    """Only a fully addressable jax leaf over the cap is cut: a numpy leaf and
    a leaf of a group that spans hosts (this host's shards in ``segments``)
    are one bucket each, over the cap as they are, beside the pieces of the
    leaf that is cut."""
    from jax._src.array import ArrayImpl

    rng = np.random.default_rng(86)
    host = {name: rng.standard_normal((32, 8)).astype(np.float32) for name in ("cut", "multi", "numpy")}
    tree = {
        "cut": _put(host["cut"], 0, jax.devices()[:2]),
        "multi": _put(host["multi"], 0, jax.devices()[:2]),
        "numpy": host["numpy"],
    }
    spans_hosts = {id(tree["multi"])}
    whole = ArrayImpl.is_fully_addressable
    monkeypatch.setattr(
        ArrayImpl, "is_fully_addressable", property(lambda self: id(self) not in spans_hosts and whole.fget(self))
    )
    assert not tree["multi"].is_fully_addressable and tree["cut"].is_fully_addressable
    monkeypatch.setenv(BUCKET_CAP_MB_ENV, str(cap / (1 << 20)))
    out = solo.step(tree)
    plan = _newest_plan(solo.manager)
    by_leaf = {name: [b for b in plan.buckets if b.slots[0].index == i] for i, name in enumerate(sorted(tree))}
    assert [len(by_leaf[name]) for name in ("multi", "numpy")] == [1, 1]
    assert by_leaf["multi"][0].slots[0].segments is not None and by_leaf["multi"][0].piece is None
    assert by_leaf["multi"][0].nbytes == by_leaf["numpy"][0].nbytes == 1024 > cap
    pieces = ddp._pieces((32, 8), 4, cap)
    assert len(by_leaf["cut"]) == len(pieces) > 1 and all(b.piece is not None for b in by_leaf["cut"])
    assert _syncs(solo.manager)[-1]["split_bytes"] == 1024 and plan.nbytes == 3 * 1024
    # the stub quorum counts two participants: every leaf comes back halved, in its own type
    for name, value in host.items():
        assert _bits(out[name]) == _bits(_div(value, 2)), name
    assert isinstance(out["numpy"], np.ndarray) and out["multi"].sharding == tree["multi"].sharding


def test_two_hundred_round_trips_through_the_real_ring_with_cut_leaves(lighthouse_addr, monkeypatch) -> None:
    """The path the cells run, at a size of seconds: two thread replicas, a
    group of two chips with its leaves in shards and a group of one, leaves
    over the cap rung piece by piece in place in views of one kept buffer
    while the train thread packs the next ones.  Every step both replicas
    hold the same bits, from the third on in kept memory, and at the end
    every set that was made is back in its store."""
    monkeypatch.setenv(BUCKET_CAP_MB_ENV, str(1024 / (1 << 20)))
    monkeypatch.setattr(ddp, "_D2H_AHEAD_BYTES", 4096)
    rng = np.random.default_rng(87)
    devices = jax.devices()
    p = _Pair(lighthouse_addr)
    try:
        cold = [0, 0]
        for step in range(200):
            host = {
                "big": rng.standard_normal((64, 48)).astype(np.float32),  # twelve pieces
                "stack": rng.standard_normal((2, 16, 64)).astype(np.float32).astype(jnp.bfloat16),  # four
                "small": rng.standard_normal(9).astype(np.float32),
                "host": rng.standard_normal((40, 8)).astype(np.float32),  # numpy: whole, over the cap
            }
            trees = [
                {
                    "big": _put(host["big"] + r, 0, devs),
                    "stack": _put(host["stack"], 2, devs),
                    "small": _put(host["small"] * (r + 1), None, devs),
                    "host": host["host"] - r,
                }
                for r, devs in enumerate((devices[:2], devices[2:3]))
            ]
            outs = p.step(trees)
            got = [[_bits(l) for l in jax.tree_util.tree_leaves(o)] for o in outs]
            assert got[0] == got[1], step
            if step % 25 == 0:
                assert got[0] == [_bits(e) for e in _expected(trees, p.participating)], step
            for r, m in enumerate(p.managers):
                sync = _syncs(m)[-1]
                assert sync["buckets"] == 19 and sync["split_bytes"] == 64 * 48 * 4 + 2 * 16 * 64 * 2
                cold[r] += sync["warm_buckets"] == 0
                assert sync["warm_buckets"] in (0, 19) and (step < 2 or sync["warm_buckets"] == 19), (step, r)
        for r, m in enumerate(p.managers):
            assert m.errored() is None
            plan = _newest_plan(m)
            assert 1 <= cold[r] == len(plan.free) <= ddp._KEPT_SETS
            assert m._host_buckets.kept_bytes() == cold[r] * plan.nbytes
    finally:
        p.shutdown()


@pytest.mark.parametrize("ends", [True, False], ids=["the_gather_ends", "the_gather_never_ends"])
def test_a_manager_that_is_shut_down_waits_for_its_gather_threads(solo, ends) -> None:
    """A round trip's gather thread is a daemon that lives on after its Work
    is done: ``Manager.shutdown`` waits for it, so that the caller may drop
    the runtime afterwards, and for no longer than the Manager's timeout."""
    import time

    def _alive() -> List[threading.Thread]:
        return [t for t in threading.enumerate() if t.name == "tpuft_ddp_gather"]

    solo.manager._timeout = 5.0 if ends else 0.3
    solo.manager.start_quorum()
    solo.comm.hold = True
    work = allreduce_pytree(solo.manager, {"a": jnp.arange(64, dtype=jnp.float32)})
    assert len(_alive()) == 1  # it waits for the ring
    if ends:
        threading.Timer(0.3, solo.comm.release).start()
    t0 = time.monotonic()
    solo.manager.shutdown()
    waited = time.monotonic() - t0
    if ends:
        assert not _alive() and 0.25 <= waited < 4.0
        np.testing.assert_array_equal(work.wait(timeout=1.0)["a"], np.arange(64, dtype=np.float32) / 2)
    else:
        assert len(_alive()) == 1 and 0.25 <= waited < 4.0
        solo.comm.release()
        _gathers_done()


# ----------------------------------------------------------------------
# the native tier: a round trip's rings are ONE call of the op thread (PR 60)
# ----------------------------------------------------------------------


@pytest.fixture()
def native_pair(lighthouse_addr, monkeypatch):
    from torchft_tpu import native

    if not native.available():
        pytest.skip("native runtime unavailable")
    monkeypatch.setenv(BUCKET_CAP_MB_ENV, str(2048 / (1 << 20)))
    p = _Pair(lighthouse_addr, comm=lambda: native.CppCommunicator(timeout_s=10.0))
    yield p
    p.shutdown()


@pytest.mark.parametrize("case", ["same_tree", "non_participating_step", "cap_cuts_every_leaf", "buckets_differ_30_times"])
def test_the_session_s_steps_are_bit_equal_to_the_parents_formula(native_pair, monkeypatch, case) -> None:
    """What ``test_six_steps_are_bit_equal_to_the_parents_formula`` holds the
    per-call path to, through the session: the same bits, ONE ring call a
    round trip, no ``Manager.allreduce`` at all, the buckets kept and given
    back as before."""
    pair = native_pair
    rngs = [np.random.default_rng(10 + r) for r in range(2)]
    cap = 24 if case == "cap_cuts_every_leaf" else 2048
    monkeypatch.setenv(BUCKET_CAP_MB_ENV, str(cap / (1 << 20)))
    for step in range(STEPS):
        if step == CHANGES_AT and case == "non_participating_step":
            monkeypatch.setattr(pair.managers[1], "is_participating", lambda: False)
        elif case == "non_participating_step":
            monkeypatch.undo()  # (the fixture's too)
            monkeypatch.setenv(BUCKET_CAP_MB_ENV, str(2048 / (1 << 20)))
        trees = [_tree(rng, 3000 if case == "buckets_differ_30_times" else 300) for rng in rngs]
        outs = pair.step(trees)
        want = _expected(trees, pair.participating)
        assert pair.participating == [True, step != 0 and (step, case) != (CHANGES_AT, "non_participating_step")]
        for r, out in enumerate(outs):
            for g, w in zip(jax.tree_util.tree_leaves(out), want):
                assert np.asarray(g).dtype == w.dtype and _bits(g) == _bits(w), (case, step, r)
    for r, m in enumerate(pair.managers):
        assert m.errored() is None and pair.handed[r] == []  # no ring was an op of its own
        syncs = _syncs(m)
        assert len(syncs) == STEPS
        # (a life's first round trip may begin before its quorum is adopted
        # and records no counters; the later ones do)
        for e in syncs[2:]:
            assert e["ring_calls"] == 1 and e["buckets"] > 1 and e["ring_bytes"] > 0
            assert e["ring_wait_push_s"] >= 0.0
            phases = e["ring_reduce_s"] + e["ring_average_s"] + e["ring_gather_s"]
            assert phases + e["ring_wait_push_s"] <= e["duration_s"] + 1e-3
        assert syncs[-1]["warm_buckets"] == syncs[-1]["buckets"]
        assert len(_newest_plan(m).free) >= 1  # the sets came back


def test_the_session_s_spans_are_the_per_call_path_s(native_pair) -> None:
    """One ``tpuft/comm/op`` a bucket with ``k`` rising from 0 in every
    step, as the op thread opened them when each ring was an op, inside ONE
    live ``tpuft/comm/session``; no ``tpuft/manager/normalize`` (the ring
    has divided and no callback runs a bucket: nothing to time)."""
    from torchft_tpu.obs import spans as obs_spans

    obs_spans.configure(True, cap=1 << 15)
    try:
        rngs = [np.random.default_rng(60 + r) for r in range(2)]
        for _ in range(3):
            native_pair.step([_tree(rng) for rng in rngs])
        spans = obs_spans.snapshot()
    finally:
        obs_spans.configure(None)
        obs_spans.clear()
    for m in native_pair.managers:
        sync = _syncs(m)[-1]
        mine = [s for s in spans if s["attrs"].get("r") == m._flight.replica_id and s["attrs"].get("step") == sync["step"]]
        ops = sorted((s for s in mine if s["name"] == "tpuft/comm/op"), key=lambda s: s["t"])
        assert [s["attrs"]["k"] for s in ops] == list(range(sync["buckets"]))
        (live,) = [s for s in mine if s["name"] == "tpuft/comm/session"]
        assert live["attrs"]["pieces"] == sync["buckets"]
        assert all(live["t"] <= s["t"] and s["t"] + s["dur"] <= live["t"] + live["dur"] + 1e-6 for s in ops)
        assert not [s for s in mine if s["name"] == "tpuft/manager/normalize"]
        # a bucket's ring begins after its pack has ended, and its wait ends after its ring has
        packs = {s["attrs"]["bucket"]: s for s in mine if s["name"] == "tpuft/ddp/pack"}
        waits = {s["attrs"]["bucket"]: s for s in mine if s["name"] == "tpuft/ddp/ring_wait"}
        for b, op in enumerate(ops):
            assert packs[b]["t"] + packs[b]["dur"] <= op["t"] + 1e-4
            assert op["t"] + op["dur"] <= waits[b]["t"] + waits[b]["dur"] + 1e-4


def test_a_streamed_round_trip_registers_the_composite_alone(native_pair) -> None:
    """``stream=``: the one work in the stream-fence registry is the
    composite; the session's pieces register nowhere."""
    rngs = [np.random.default_rng(70 + r) for r in range(2)]
    trees = [_tree(rng) for rng in rngs]

    def _one(r: int) -> Any:
        m = native_pair.managers[r]
        m.start_quorum()
        work = allreduce_pytree(m, trees[r], stream=3)
        with m._pending_works_lock:
            assert m._pending_works == [] and list(m._stream_pending) == [3]
            assert m._stream_pending[3][0] is work
        out = work.wait(timeout=30.0)
        assert m.stream_unresolved() == []
        assert m.should_commit()
        m.stream_resolved(3, True)
        return out

    outs = [f.result(timeout=60.0) for f in [native_pair._pool.submit(_one, r) for r in range(2)]]
    _gathers_done()
    want = _expected(trees, [True, False])  # replica 1 heals in its life's first step
    for out in outs:
        assert [_bits(g) for g in jax.tree_util.tree_leaves(out)] == [_bits(w) for w in want]
    assert all(pair_handed == [] for pair_handed in native_pair.handed)


def test_a_session_that_fails_keeps_its_buckets_and_is_heard_once(native_pair, monkeypatch) -> None:
    """A piece fails mid round trip (replica 0's communicator is aborted
    under its third push): that piece and every later one fail on both
    replicas, ``report_error`` hears ONE error a replica, the vote discards
    the step, the set is never handed out again, and the next step serves in
    fresh memory through a session again."""
    pair = native_pair
    rngs = [np.random.default_rng(80 + r) for r in range(2)]
    trees = [_tree(rng, 3000) for rng in rngs]  # 26 buckets
    for _ in range(2):
        pair.step(trees)
    plans = [_newest_plan(m) for m in pair.managers]
    kept = [[id(b) for bufs in plan.free for b in bufs] for plan in plans]
    assert all(kept)
    heard: List[List[BaseException]] = [[], []]
    for r, m in enumerate(pair.managers):
        inner = m.report_error
        monkeypatch.setattr(m, "report_error", lambda e, inner=inner, r=r: (heard[r].append(e), inner(e))[1])
    open_session, pushes = pair.managers[0].ring_session, []
    opened: List[Any] = [None, None]
    other = pair.managers[1].ring_session
    monkeypatch.setattr(
        pair.managers[1], "ring_session", lambda pieces: opened.__setitem__(1, other(pieces)) or opened[1]
    )

    def _aborting(pieces: int):
        session = opened[0] = open_session(pieces)
        push = session.push

        def _push(flat):
            pushes.append(flat)
            if len(pushes) == 3:
                pair.managers[0]._comm.abort("injected under the third push")
            push(flat)

        session.push = _push
        return session

    monkeypatch.setattr(pair.managers[0], "ring_session", _aborting)

    def _one(r: int):
        m = pair.managers[r]
        m.start_quorum()
        out = allreduce_pytree(m, trees[r]).wait(timeout=30.0)
        return out, m.should_commit()

    results = [f.result(timeout=60.0) for f in [pair._pool.submit(_one, r) for r in range(2)]]
    _gathers_done()
    monkeypatch.setattr(pair.managers[0], "ring_session", open_session)
    monkeypatch.setattr(pair.managers[1], "ring_session", other)
    for r, (out, committed) in enumerate(results):
        # the session's error, whichever way it came (the run's, a wait's, or
        # the abort's where the run had not begun), was reported ONCE by the
        # session; ``should_commit`` reports what the communicator latched
        # besides, as it always did (the same object where the run latched it)
        mine = opened[r].swallowed
        assert not committed and mine is not None and 1 <= len(heard[r]) <= 2, heard[r]
        assert sum(e is mine for e in heard[r]) in (1, len(heard[r])) and any(e is mine for e in heard[r])
        assert _syncs(pair.managers[r])[-1]["warm_buckets"] > 0
        # the failed round trip's set did not come back
        assert [id(b) for bufs in plans[r].free for b in bufs] == kept[r][: len(kept[r]) - len(plans[r].buffers)]
    # the next steps: a new quorum, fresh memory, the session again
    outs = pair.step(trees)
    want = _expected(trees, pair.participating)
    for out in outs:
        assert [_bits(g) for g in jax.tree_util.tree_leaves(out)] == [_bits(w) for w in want]
    for m in pair.managers:
        assert _syncs(m)[-1]["warm_buckets"] == 0 and m.errored() is None


def test_a_train_thread_that_raises_mid_round_trip_closes_the_session(native_pair, monkeypatch) -> None:
    """The pack of the fourth bucket raises on both replicas: the exception
    is the caller's, the session is closed in the same breath (the buckets
    not pushed never start, as rings never submitted), the op thread leaves
    it, and the next step serves."""
    pair = native_pair
    rngs = [np.random.default_rng(90 + r) for r in range(2)]
    trees = [_tree(rng) for rng in rngs]
    pair.step(trees)
    inner = ddp._pack

    def _raising(bucket, flat, hosts):
        if bucket is _newest_plan_of[threading.get_ident()].buckets[3]:
            raise RuntimeError("the pack broke")
        return inner(bucket, flat, hosts)

    _newest_plan_of: Dict[int, Any] = {}
    monkeypatch.setattr(ddp, "_pack", _raising)

    def _one(r: int) -> None:
        m = pair.managers[r]
        _newest_plan_of[threading.get_ident()] = _newest_plan(m)
        m.start_quorum()
        with pytest.raises(RuntimeError, match="the pack broke"):
            allreduce_pytree(m, trees[r])
        deadline = time.monotonic() + 10.0
        while m._comm.busy() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not m._comm.busy()  # the op thread is out of the session
        m.report_error(RuntimeError("the caller funnels what it caught"))
        assert not m.should_commit()

    for f in [pair._pool.submit(_one, r) for r in range(2)]:
        f.result(timeout=60.0)
    monkeypatch.setattr(ddp, "_pack", inner)
    outs = pair.step(trees)
    want = _expected(trees, pair.participating)
    for out in outs:
        assert [_bits(g) for g in jax.tree_util.tree_leaves(out)] == [_bits(w) for w in want]


def test_the_per_call_path_serves_what_the_session_does_not(native_pair, monkeypatch) -> None:
    """``should_quantize`` and an error already recorded keep the per-call
    path on the native tier too; the choice is the call's and the
    communicator's, and ``Manager.ring_session`` takes no argument but the
    count."""
    import inspect

    assert list(inspect.signature(Manager.ring_session).parameters) == ["self", "pieces"]
    pair = native_pair
    rngs = [np.random.default_rng(95 + r) for r in range(2)]
    trees = [{"w": jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32))} for rng in rngs]
    opened = []
    for m in pair.managers:
        inner = m.ring_session
        monkeypatch.setattr(m, "ring_session", lambda pieces, inner=inner: opened.append(pieces) or inner(pieces))

    def _quantized(r: int) -> Any:
        m = pair.managers[r]
        m.start_quorum()
        # (numpy leaves: the bucketed path with the int8 wire, not the device quantizer)
        out = allreduce_pytree(m, jax.tree_util.tree_map(np.asarray, trees[r]), should_quantize=True).wait(timeout=30.0)
        assert m.should_commit()
        return out

    for f in [pair._pool.submit(_quantized, r) for r in range(2)]:
        f.result(timeout=60.0)
    _gathers_done()
    assert opened == [] and all(len(h) >= 1 for h in pair.handed)
    # an error recorded before the round trip: no session is opened over it
    m = pair.managers[0]
    m.report_error(RuntimeError("earlier in the step"))
    assert Manager.ring_session(m, 4) is None
