"""The host buckets of ``ddp.allreduce_pytree`` last from step to step
(ISSUE 30) and cross to the host in an order, a few at a time (ISSUE 32: the
last section).  Five rules, each with a test that fails when it is broken:

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
    def __init__(self, lighthouse_addr: str) -> None:
        self.managers: List[Manager] = []
        for r in range(2):
            state = {"w": np.zeros(3, np.float32)}
            self.managers.append(
                Manager(
                    comm=TCPCommunicator(timeout_s=10.0),
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
    "case", ["same_tree", "cap_flipped", "leaf_reshaped", "non_participating_step", "buckets_differ_30_times"]
)
def test_six_steps_are_bit_equal_to_the_parents_formula(pair, monkeypatch, case) -> None:
    rngs = [np.random.default_rng(10 + r) for r in range(2)]
    for step in range(STEPS):
        # 48,000 bytes of ``embed`` beside buckets of 1,544 and 330
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
        if case == "buckets_differ_30_times":
            # the rings ran in the plan's order, not the tree's: the smallest
            # bucket, then by falling size
            handed = pair.handed[pair.managers.index(m)]
            sizes = [a.nbytes for a in handed[-syncs[-1]["buckets"]:]]
            assert sizes == [330, 48000, 1544]


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


@pytest.mark.parametrize("kind", ["raised", "timed_out", "errored_before_submit"])
def test_a_failed_round_trip_never_gives_its_buckets_back(solo, kind) -> None:
    """Rule 1: an op thread that is still receiving writes into memory nobody
    reuses."""
    tree = {"a": np.arange(64, dtype=np.float32), "b": jnp.ones((8, 8), jnp.bfloat16)}
    solo.step(tree)
    n = len(solo.handed)
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
    # buckets' sizes, not an argument, an environment variable or a knob
    assert type(ddp._D2H_AHEAD) is int and ddp._D2H_AHEAD >= 1
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
    "cell,which", [("mistral7b-ddp2-steady", 0), ("ling3flash-ws1-seq8k", 0), ("ling3flash-ws1-seq8k", 1)],
    ids=["mistral_gradients", "ling_gradients", "ling_signal"],
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
    first = [group[0] for group in layouts[0]]
    for a, b in zip(range(1, len(sizes)), range(2, len(sizes))):
        assert sizes[a] > sizes[b] or first[a] < first[b]
    # a function of the sizes alone: the same sizes from anywhere, the same order
    in_tree = sorted(range(len(sizes)), key=lambda b: first[b])
    order = ddp._pipeline_order([sizes[b] for b in in_tree])
    assert [in_tree[b] for b in order] == list(range(len(sizes)))
    if cell.startswith("mistral") :
        assert [round(n / 1e6, 1) for n in sizes] == [0.0, 268.4, 268.4, 117.4, 117.4, 117.4, 33.6, 33.6, 8.4, 8.4]


def test_the_order_of_sizes() -> None:
    assert ddp._pipeline_order([]) == []
    assert ddp._pipeline_order([7]) == [0]
    assert ddp._pipeline_order([5, 5, 5]) == [2, 0, 1]
    assert ddp._pipeline_order([268, 117, 117, 117, 8, 34, 34, 8, 268, 1]) == [9, 0, 8, 1, 2, 3, 5, 6, 4, 7]


@pytest.fixture()
def asked(monkeypatch):
    """Every ``copy_to_host_async`` of a jax array, in order: the array's id."""
    calls: List[int] = []
    array_type = type(jnp.zeros(1))
    inner = array_type.copy_to_host_async

    def _recorded(self: Any) -> None:
        calls.append(id(self))
        inner(self)

    monkeypatch.setattr(array_type, "copy_to_host_async", _recorded)
    return calls


def test_copies_start_a_window_ahead_of_the_ring(solo, asked, monkeypatch) -> None:
    """At the submit of the bucket in place b of the plan, the leaves of the
    buckets in places up to b + W - 1 have been asked for, none of a later
    one; by the end every jax leaf exactly once and no numpy leaf at all."""
    monkeypatch.setenv(BUCKET_CAP_MB_ENV, str(2048 / (1 << 20)))
    rng = np.random.default_rng(50)
    tree = {f"w{k}": jnp.asarray(rng.standard_normal(100 * (k + 1)).astype(np.float32)) for k in range(9)}
    tree["host"] = rng.standard_normal(700).astype(np.float32)
    leaves = jax.tree_util.tree_leaves(tree)
    seen: List[List[int]] = []
    inner = solo.manager.allreduce

    def _allreduce(data: Any, *args: Any, **kwargs: Any) -> Work:
        seen.append(list(asked))
        return inner(data, *args, **kwargs)

    solo.manager.allreduce = _allreduce  # type: ignore[method-assign]
    for _ in range(2):  # a cold round trip and a warm one
        del asked[:], seen[:]
        out = solo.step(tree)
        (plan,) = solo.manager._host_buckets._plans.values()
        ids = [[id(leaves[s.index]) for s in b.slots if s.sharding is not None] for b in plan.buckets]
        n = len(plan.buckets)
        assert n >= 6 and len(seen) == n
        for b in range(n):
            upto = min(b + ddp._D2H_AHEAD, n)
            assert seen[b] == [i for group in ids[:upto] for i in group], b
        jax_leaves = [id(l) for l in leaves if isinstance(l, jax.Array)]
        assert sorted(asked) == sorted(jax_leaves) and len(set(asked)) == len(asked)
        for name, leaf in tree.items():
            np.testing.assert_array_equal(np.asarray(out[name]), np.asarray(leaf) / 2)


@pytest.mark.parametrize("kind", ["one_jax_bucket", "numpy_leaves", "fewer_buckets_than_the_window"])
def test_trees_the_window_does_not_reach_go_through_unchanged(solo, asked, kind) -> None:
    """One bucket, no jax leaf, or no more buckets than the window: what the
    parent did (every copy started before the first wait)."""
    rng = np.random.default_rng(60)
    f32 = lambda n: rng.standard_normal(n).astype(np.float32)  # noqa: E731
    tree = {
        "one_jax_bucket": {"a": jnp.asarray(f32(64)), "b": jnp.asarray(f32(8))},
        "numpy_leaves": {"a": f32(64), "b": f32(8).astype(np.float64), "c": 3.0},
        "fewer_buckets_than_the_window": {"a": jnp.asarray(f32(64)), "b": jnp.asarray(f32(8)).astype(jnp.bfloat16)},
    }[kind]
    seen: List[int] = []
    inner = solo.manager.allreduce

    def _allreduce(data: Any, *args: Any, **kwargs: Any) -> Work:
        seen.append(len(asked))
        return inner(data, *args, **kwargs)

    solo.manager.allreduce = _allreduce  # type: ignore[method-assign]
    out = solo.step(tree)
    n_jax = sum(isinstance(l, jax.Array) for l in jax.tree_util.tree_leaves(tree))
    buckets = {"one_jax_bucket": 1, "numpy_leaves": 2, "fewer_buckets_than_the_window": 2}[kind]
    assert buckets <= ddp._D2H_AHEAD and seen == [n_jax] * buckets and len(asked) == n_jax
    for name, leaf in tree.items():
        got = out[name]
        assert isinstance(got, jax.Array) == isinstance(leaf, jax.Array)
        want = _div(np.asarray(leaf) + np.zeros_like(np.asarray(leaf)), 2)
        assert _bits(got) == _bits(want)
    assert _syncs(solo.manager)[-1]["first_submit_s"] > 0.0


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


@pytest.mark.parametrize("kept_set", [False, True], ids=["no_kept_set", "kept_set"])
@pytest.mark.parametrize("cap", [64, 1 << 20], ids=["cap_below_a_leaf", "cap_above_the_tree"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, np.float32], ids=["bfloat16", "float32"])
@pytest.mark.parametrize("kind", sorted(LAYOUTS))
def test_sharded_leaves_go_from_their_shards_into_the_bucket(
    lighthouse_addr, monkeypatch, kind, dtype, cap, kept_set
) -> None:
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
            assert n == (len(host) if cap == 64 else len({np.asarray(v).dtype for v in host.values()}))
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
        wire = b"".join(c.tobytes() for c in copies[0])
        for name, value in host.items():
            assert np.asarray(value).tobytes() in wire, name
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
        # and there the whole leaf WAS made on the host: (d) can fail
        for name in direct:
            assert again[0][name]._npy_value is not None, name
    finally:
        p.shutdown()
