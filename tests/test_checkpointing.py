"""Serialization + HTTP transport conformance tests
(reference: ``torchft/checkpointing/transport_test.py`` ABC suite)."""

import io
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.checkpointing.http_transport import HTTPTransport
from torchft_tpu.checkpointing.serialization import (
    dumps_pytree,
    load_pytree,
    loads_pytree,
    save_pytree,
)


def _state():
    return {
        "user": {
            "model": {
                "w": np.arange(12, dtype=np.float32).reshape(3, 4),
                "b": jnp.ones(4, dtype=jnp.bfloat16),
                "layers": [jnp.zeros((2, 2)), np.full(3, 7.0)],
            },
            "opt": {"mu": jnp.arange(5, dtype=jnp.float32), "count": 3},
            "meta": ("tag", 1.5, None),
        },
        "torchft": {"step": 7, "batches_committed": 21},
    }


def _assert_state_equal(a, b) -> None:
    assert a["torchft"] == b["torchft"]
    au, bu = a["user"], b["user"]
    np.testing.assert_array_equal(np.asarray(au["model"]["w"]), bu["model"]["w"])
    np.testing.assert_array_equal(np.asarray(au["model"]["b"]), bu["model"]["b"])
    np.testing.assert_array_equal(
        np.asarray(au["model"]["layers"][0]), bu["model"]["layers"][0]
    )
    np.testing.assert_array_equal(
        np.asarray(au["model"]["layers"][1]), bu["model"]["layers"][1]
    )
    np.testing.assert_array_equal(np.asarray(au["opt"]["mu"]), bu["opt"]["mu"])
    assert au["opt"]["count"] == bu["opt"]["count"]
    assert au["meta"] == bu["meta"]


class TestSerialization:
    def test_roundtrip(self) -> None:
        state = _state()
        blob = dumps_pytree(state)
        restored = loads_pytree(blob)
        _assert_state_equal(state, restored)

    def test_bf16_dtype_preserved(self) -> None:
        state = {"x": jnp.ones(3, dtype=jnp.bfloat16)}
        restored = loads_pytree(dumps_pytree(state))
        assert restored["x"].dtype.name == "bfloat16"

    def test_bf16_numpy_leaf(self) -> None:
        """HOST bf16 arrays (np.asarray of a bf16 jax array — exactly what
        DiLoCo fragment backups register in the healing state dict) must
        serialize: probing ``.data`` on an extension-dtype ndarray raises
        ValueError, which once leaked out of the shard probe."""
        host = np.asarray(jnp.arange(6, dtype=jnp.bfloat16))
        assert isinstance(host, np.ndarray)
        restored = loads_pytree(dumps_pytree({"backup": [host]}))
        assert restored["backup"][0].dtype.name == "bfloat16"
        np.testing.assert_array_equal(
            restored["backup"][0].astype(np.float32),
            host.astype(np.float32),
        )

    def test_streaming(self) -> None:
        state = {"big": np.random.default_rng(0).normal(size=100_000)}
        buf = io.BytesIO()
        save_pytree(state, buf)
        buf.seek(0)
        restored = load_pytree(buf)
        np.testing.assert_array_equal(restored["big"], state["big"])

    def test_bad_magic(self) -> None:
        with pytest.raises(ValueError, match="magic"):
            loads_pytree(b"NOPE" + b"\x00" * 100)


class TestCommTransport:
    """Checkpoint over the communicator fabric (PGTransport analog)."""

    def _pair(self, fn0, fn1):
        from concurrent.futures import ThreadPoolExecutor

        from torchft_tpu.communicator import TCPCommunicator
        from torchft_tpu.store import StoreServer

        store = StoreServer("127.0.0.1:0")
        try:
            comms = [TCPCommunicator(timeout_s=15.0) for _ in range(2)]

            def _run(rank: int):
                comms[rank].configure(
                    f"127.0.0.1:{store.port}/ckpt",
                    replica_id=f"r{rank}",
                    rank=rank,
                    world_size=2,
                )
                try:
                    return (fn0 if rank == 0 else fn1)(comms[rank])
                finally:
                    comms[rank].shutdown()

            with ThreadPoolExecutor(max_workers=2) as pool:
                return list(pool.map(_run, range(2)))
        finally:
            store.shutdown()

    def test_roundtrip(self) -> None:
        from torchft_tpu.checkpointing.comm_transport import CommTransport

        state = _state()

        def _send(comm):
            CommTransport(comm).send_checkpoint(
                [1], step=7, state_dict=state, timeout=15.0
            )

        def _recv(comm):
            return CommTransport(comm).recv_checkpoint(
                src_rank=0, metadata="<comm>", step=7, timeout=15.0
            )

        _, received = self._pair(_send, _recv)
        _assert_state_equal(state, received)

    def test_in_place_recv(self) -> None:
        from torchft_tpu.checkpointing.comm_transport import CommTransport

        state = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}

        def _send(comm):
            CommTransport(comm).send_checkpoint(
                [1], step=3, state_dict=state, timeout=15.0
            )

        landing = {"w": np.zeros((2, 3), dtype=np.float32)}
        landing_buf = landing["w"]

        def _recv(comm):
            return CommTransport(comm).recv_checkpoint(
                src_rank=0, metadata="<comm>", step=3, timeout=15.0, into=landing
            )

        _, received = self._pair(_send, _recv)
        np.testing.assert_array_equal(received["w"], state["w"])
        assert received["w"] is landing_buf  # no allocation: recv'd in place


@pytest.mark.parametrize("num_chunks", [0, 4])
class TestHTTPTransport:
    def test_roundtrip(self, num_chunks) -> None:
        sender = HTTPTransport(timeout=10.0, num_chunks=num_chunks)
        receiver = HTTPTransport(timeout=10.0, num_chunks=num_chunks)
        try:
            state = _state()
            sender.send_checkpoint([1], step=7, state_dict=state, timeout=10.0)
            fetched = receiver.recv_checkpoint(
                src_rank=0, metadata=sender.metadata(), step=7, timeout=10.0
            )
            _assert_state_equal(state, fetched)
        finally:
            sender.shutdown()
            receiver.shutdown()

    def test_wrong_step_404(self, num_chunks) -> None:
        sender = HTTPTransport(timeout=2.0, num_chunks=num_chunks)
        receiver = HTTPTransport(timeout=2.0, num_chunks=num_chunks)
        try:
            sender.send_checkpoint([1], step=3, state_dict={"a": 1}, timeout=5.0)
            with pytest.raises(Exception):
                receiver.recv_checkpoint(
                    src_rank=0, metadata=sender.metadata(), step=9, timeout=2.0
                )
        finally:
            sender.shutdown()
            receiver.shutdown()

    def test_disallow_then_resend(self, num_chunks) -> None:
        sender = HTTPTransport(timeout=2.0, num_chunks=num_chunks)
        receiver = HTTPTransport(timeout=2.0, num_chunks=num_chunks)
        try:
            sender.send_checkpoint([1], step=1, state_dict={"a": 1}, timeout=5.0)
            sender.disallow_checkpoint()
            with pytest.raises(Exception):
                receiver.recv_checkpoint(
                    src_rank=0, metadata=sender.metadata(), step=1, timeout=1.0
                )
            sender.send_checkpoint([1], step=2, state_dict={"a": 2}, timeout=5.0)
            out = receiver.recv_checkpoint(
                src_rank=0, metadata=sender.metadata(), step=2, timeout=5.0
            )
            assert out == {"a": 2}
        finally:
            sender.shutdown()
            receiver.shutdown()

    def test_receiver_can_wait_for_staging(self, num_chunks) -> None:
        """A healing peer that races ahead of send_checkpoint blocks until
        the sender stages rather than failing."""
        sender = HTTPTransport(timeout=10.0, num_chunks=num_chunks)
        receiver = HTTPTransport(timeout=10.0, num_chunks=num_chunks)
        try:
            def _stage() -> None:
                import time

                time.sleep(0.3)
                sender.send_checkpoint([1], step=5, state_dict={"k": 9}, timeout=5.0)

            t = threading.Thread(target=_stage)
            t.start()
            out = receiver.recv_checkpoint(
                src_rank=0, metadata=sender.metadata(), step=5, timeout=10.0
            )
            assert out == {"k": 9}
            t.join()
        finally:
            sender.shutdown()
            receiver.shutdown()


def test_chunked_fetch_error_not_masked_as_timeout(monkeypatch) -> None:
    """A real fetch failure (connection refused) in a chunk thread must
    surface as that error, under one shared deadline."""
    import time as _time

    import torchft_tpu.checkpointing.http_transport as ht

    sender = HTTPTransport(timeout=10.0, num_chunks=3)
    receiver = HTTPTransport(timeout=10.0, num_chunks=3)
    try:
        sender.send_checkpoint(
            [1], step=1, state_dict={"a": np.arange(64)}, timeout=5.0
        )
        real_urlopen = ht.urlopen
        calls = {"n": 0}

        def flaky(url, timeout=None):
            calls["n"] += 1
            if calls["n"] > 1:  # first (synchronous) fetch succeeds
                raise ConnectionRefusedError("injected chunk failure")
            return real_urlopen(url, timeout=timeout)

        monkeypatch.setattr(ht, "urlopen", flaky)
        t0 = _time.monotonic()
        with pytest.raises(ConnectionRefusedError):
            receiver.recv_checkpoint(
                src_rank=0, metadata=sender.metadata(), step=1, timeout=5.0
            )
        assert _time.monotonic() - t0 < 4.0  # one deadline, not N*timeout
    finally:
        sender.shutdown()
        receiver.shutdown()


def test_sharded_host_array_restore_like() -> None:
    """restore_like rebuilds a sharded device array from a ShardedHostArray
    (the multi-host heal payload) without materializing it unsharded."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchft_tpu.checkpointing.serialization import (
        ShardedHostArray,
        shard_key,
    )
    from torchft_tpu.ddp import restore_like

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("fsdp",))
    sh = NamedSharding(mesh, P("fsdp"))
    old = jax.device_put(np.zeros((8, 3), np.float32), sh)

    want = np.arange(24, dtype=np.float32).reshape(8, 3)
    shards = {}
    for s in old.addressable_shards:
        k = shard_key(s.index, old.shape)
        shards[k] = want[s.index]
    new = ShardedHostArray(shape=(8, 3), dtype="float32", shards=shards)

    restored = restore_like(new, old)
    assert restored.sharding == sh
    np.testing.assert_array_equal(np.asarray(restored), want)


class TestStreamingPlan:
    def _tree(self):
        rng = np.random.default_rng(5)
        return {
            "w": rng.normal(size=(37, 11)).astype(np.float32),
            "b": rng.normal(size=129).astype(np.float64),
            "step": 7,
            "nested": [rng.integers(0, 100, size=13).astype(np.int32)],
        }

    def test_write_range_reassembles(self) -> None:
        from torchft_tpu.checkpointing.serialization import (
            dumps_pytree,
            plan_pytree,
        )

        tree = self._tree()
        blob = dumps_pytree(tree)
        plan = plan_pytree(tree)
        assert plan.total_len == len(blob)
        # any chunking of the byte range must reassemble to the full blob
        for n in (1, 2, 3, 7):
            size = -(-plan.total_len // n)
            buf = io.BytesIO()
            for i in range(n):
                plan.write_range(
                    i * size, min(plan.total_len, (i + 1) * size), buf
                )
            assert buf.getvalue() == blob

    def test_copy_mutable_snapshots_numpy(self) -> None:
        from torchft_tpu.checkpointing.serialization import (
            loads_pytree,
            plan_pytree,
        )

        tree = self._tree()
        plan = plan_pytree(tree, snapshot=True)
        expected = tree["w"].copy()
        tree["w"][:] = -1.0  # train loop mutates after staging
        buf = io.BytesIO()
        plan.write_range(0, plan.total_len, buf)
        out = loads_pytree(buf.getvalue())
        np.testing.assert_array_equal(out["w"], expected)

    def test_leaf_hook_maps_on_arrival(self) -> None:
        from torchft_tpu.checkpointing.serialization import (
            dumps_pytree,
            load_pytree,
        )

        tree = self._tree()
        seen = []

        def hook(arr):
            seen.append(arr.shape)
            return arr * 0 + 1 if arr.dtype.kind == "f" else arr

        out = load_pytree(io.BytesIO(dumps_pytree(tree)), leaf_hook=hook)
        assert len(seen) == 3
        np.testing.assert_array_equal(out["w"], np.ones_like(tree["w"]))
        np.testing.assert_array_equal(out["nested"][0], tree["nested"][0])

    def test_jax_leaves_stage_on_device(self) -> None:
        """jax leaves must not be materialized to HOST at plan time (the
        staging copy the streaming rework removes); the snapshot is a
        device-side copy, immune to later donation of the original."""
        import jax

        from torchft_tpu.checkpointing.serialization import plan_pytree

        cpu = jax.local_devices(backend="cpu")[0]
        leaf = jax.device_put(np.arange(1000, dtype=np.float32), cpu)
        plan = plan_pytree({"p": leaf}, snapshot=True)
        staged = plan.leaves[0]
        assert isinstance(staged, jax.Array) and staged is not leaf
        # survives deletion of the original (what donation does)
        leaf.delete()
        import io as iomod

        buf = iomod.BytesIO()
        plan.write_range(0, plan.total_len, buf)
        from torchft_tpu.checkpointing.serialization import loads_pytree

        np.testing.assert_array_equal(
            loads_pytree(buf.getvalue())["p"], np.arange(1000, dtype=np.float32)
        )


# -- the send path brings the next leaves to the host while the last one is
# -- being written (PytreePlan.host_leaves)


def _reference_stream(plan) -> bytes:
    """The serialized form as the plain loop wrote it before the leaves came
    ahead: the header, then for every leaf its length and its bytes."""
    import struct

    from torchft_tpu.checkpointing.serialization import materialize_leaf

    out = [plan.header]
    for leaf, nbytes in zip(plan.leaves, plan.leaf_nbytes):
        out += [struct.pack("<Q", nbytes), materialize_leaf(leaf).tobytes()]
    return b"".join(out)


def _plan_of(kind: str):
    """A plan of five leaves of 1,000 to 3,000 bytes: numpy leaves, jax
    leaves, or jax ``Shard``s (what a multi-host array's plan holds)."""
    import jax

    from torchft_tpu.checkpointing.serialization import plan_pytree

    rng = np.random.default_rng(11)
    arrays = [rng.normal(size=250 * (1 + i % 3)).astype(np.float32) for i in range(5)]
    if kind == "numpy":
        return plan_pytree({f"l{i}": a for i, a in enumerate(arrays)})
    plan = plan_pytree({f"l{i}": jax.numpy.asarray(a) for i, a in enumerate(arrays)})
    if kind == "shard":
        plan.leaves = [leaf.addressable_shards[0] for leaf in plan.leaves]
    return plan


def _ranges_of(plan, which: str):
    first = len(plan.header)  # where leaf 0's frame begins
    second = first + 8 + plan.leaf_nbytes[0]
    return {
        "whole": [(0, plan.total_len)],
        "cuts_leaves": [(0, second + 100), (second + 100, plan.total_len - 7), (plan.total_len - 7, plan.total_len)],
        "inside_one_leaf": [(second + 8 + 40, second + 8 + 440)],
        "header_only": [(0, first), (3, first - 2)],
        # ends on a leaf's length and begins on the next one's: no payload
        "lengths_only": [(first - 1, first + 8), (second + 2, second + 8)],
    }[which]


@pytest.mark.parametrize("which", ["whole", "cuts_leaves", "inside_one_leaf", "header_only", "lengths_only"])
@pytest.mark.parametrize("kind", ["jax", "numpy", "shard"])
def test_write_range_is_the_plain_loops_stream(kind, which) -> None:
    plan = _plan_of(kind)
    want = _reference_stream(plan)
    assert len(want) == plan.total_len
    if which == "whole":  # and what a durable save of the same arrays writes
        from torchft_tpu.checkpointing.serialization import materialize_leaf

        assert dumps_pytree({f"l{i}": materialize_leaf(l) for i, l in enumerate(plan.leaves)}) == want
    for start, stop in _ranges_of(plan, which):
        buf = io.BytesIO()
        sent = plan.write_range(start, stop, buf)
        assert buf.getvalue() == want[start:stop]
        assert 0 <= sent.ahead_bytes <= stop - start and sent.d2h_s >= 0.0
        if kind == "numpy" or which in ("inside_one_leaf", "header_only", "lengths_only"):
            assert sent.ahead_bytes == 0  # nothing was, or could be, asked ahead


class _RecordingLeaf:
    """Stands for a device array: ``copy_to_host_async`` and the read of its
    host value (``np.asarray``) are written into ``log``."""

    def __init__(self, log, index: int, nbytes: int, hold_s: float = 0.0) -> None:
        self._log, self._index, self._hold_s = log, index, hold_s
        self._value = np.full(nbytes, index + 1, np.uint8)
        self.dtype, self.shape = self._value.dtype, self._value.shape

    def copy_to_host_async(self) -> None:
        self._log.append(("ask", self._index))

    def __array__(self, dtype=None, copy=None):
        self._log.append(("host", self._index))
        if self._hold_s:
            import time

            time.sleep(self._hold_s)
        return self._value


class _RecordingShard:
    """A jax ``Shard``'s shape: the array is its ``.data``."""

    def __init__(self, data) -> None:
        self.data = data


class _RecordingStream:
    def __init__(self, log, fail_after=None) -> None:
        self._log, self._fail_after, self.wrote = log, fail_after, 0
        self.parts = []

    def write(self, b) -> int:
        if self._fail_after is not None and self.wrote + len(b) > self._fail_after:
            raise ConnectionResetError("the peer hung up")
        self.wrote += len(b)
        self.parts.append(bytes(b))
        self._log.append(("write", len(b)))
        return len(b)


def _recording_plan(sizes, log, numpy_at=(), shard_at=(), hold_s=0.0):
    from torchft_tpu.checkpointing.serialization import PytreePlan

    leaves = []
    for i, n in enumerate(sizes):
        if i in numpy_at:
            leaves.append(np.full(n, i + 1, np.uint8))
        elif i in shard_at:
            leaves.append(_RecordingShard(_RecordingLeaf(log, i, n, hold_s)))
        else:
            leaves.append(_RecordingLeaf(log, i, n, hold_s))
    header = b"HEADER--"
    plan = PytreePlan(header=header, leaves=leaves, leaf_nbytes=list(sizes),
                      total_len=len(header) + sum(8 + n for n in sizes))
    want = _reference_stream(plan)
    log.clear()  # the reference's own reads
    return plan, want


def _frame_start(plan, index: int) -> int:
    return len(plan.header) + sum(8 + n for n in plan.leaf_nbytes[:index])


SIZES = [300, 100, 100, 500, 100, 100, 100, 400]


@pytest.mark.parametrize(
    "ahead_leaves,ahead_bytes",
    [(1, 1 << 20), (2, 1 << 20), (4, 1 << 20), (len(SIZES), 1 << 20), (4, 250), (2, 50)],
    ids=["one", "two", "four", "all", "four_within_250_bytes", "bytes_under_a_leaf"],
)
@pytest.mark.parametrize("first,last", [(0, 7), (2, 6)], ids=["whole", "leaves_2_to_6"])
def test_next_leaves_are_asked_before_the_last_is_written(ahead_leaves, ahead_bytes, first, last, monkeypatch) -> None:
    from torchft_tpu.checkpointing import serialization

    monkeypatch.setattr(serialization, "_D2H_AHEAD_LEAVES", ahead_leaves)
    monkeypatch.setattr(serialization, "_D2H_AHEAD_BYTES", ahead_bytes)
    log = []
    plan, want = _recording_plan(SIZES, log, shard_at=(3, 4))
    # from inside leaf ``first`` to inside leaf ``last``
    start, stop = _frame_start(plan, first) + 8 + 10, _frame_start(plan, last) + 8 + 10
    stream = _RecordingStream(log)
    sent = plan.write_range(start, stop, stream)
    assert b"".join(stream.parts) == want[start:stop]

    wanted = list(range(first, last + 1))
    asks = [i for what, i in log if what == "ask"]
    assert asks == wanted  # each once, in the stream's order, none outside the range
    assert [i for what, i in log if what == "host"] == wanted
    at = {entry: n for n, entry in enumerate(log) if entry[0] != "write"}
    payload_writes = [n for n, entry in enumerate(log) if entry[0] == "write" and entry[1] > 8]
    for k, i in enumerate(wanted[:-1]):
        # leaf i+1 is under way BEFORE leaf i is waited for, let alone written
        assert at[("ask", i + 1)] < at[("host", i)] < payload_writes[k]
    # never further ahead than the window, in leaves and in bytes (the next
    # one always): at every ask, count from the leaf the send waits for next
    for n, (what, j) in enumerate(log):
        if what != "ask":
            continue
        waits_for = first + sum(1 for w, _ in log[:n] if w == "host")
        assert j - waits_for <= ahead_leaves
        assert j <= waits_for + 1 or sum(SIZES[waits_for + 1 : j + 1]) <= ahead_bytes
    # the first leaf's transfer was not under way when the send came to it
    written = [min(stop, _frame_start(plan, i) + 8 + SIZES[i]) - max(start, _frame_start(plan, i) + 8) for i in wanted]
    assert sent.ahead_bytes == sum(written[1:])


@pytest.mark.parametrize(
    "sizes,numpy_at,start_in,stop_in,asks",
    [
        # a range inside one leaf (a striped healer's request): nothing ahead
        ([400, 400, 400], (), (1, 50), (1, 350), []),
        # a plan of one leaf does what np.asarray does
        ([400], (), (0, 0), (0, 400), []),
        # numpy leaves have no transfer; the device leaves among them do
        ([100, 100, 100, 100], (1, 3), (0, 0), (3, 100), [0, 2]),
        ([100, 100, 100], (0, 1, 2), (0, 0), (2, 100), []),
        # the range ends on leaf 1's last byte: leaf 2 is not touched
        ([100, 100, 100], (), (0, 0), (1, 100), [0, 1]),
        # it ends inside leaf 2's LENGTH: its payload is not wanted either
        ([100, 100, 100], (), (0, 0), (2, -4), [0, 1]),
    ],
    ids=["inside_one_leaf", "one_leaf_plan", "numpy_between", "all_numpy", "ends_on_a_leaf", "ends_in_a_length"],
)
def test_what_is_asked_follows_what_the_range_needs(sizes, numpy_at, start_in, stop_in, asks) -> None:
    log = []
    plan, want = _recording_plan(sizes, log, numpy_at=numpy_at)
    start = _frame_start(plan, start_in[0]) + 8 + start_in[1]
    stop = _frame_start(plan, stop_in[0]) + 8 + stop_in[1]
    stream = _RecordingStream(log)
    plan.write_range(start, stop, stream)
    assert b"".join(stream.parts) == want[start:stop]
    assert [i for what, i in log if what == "ask"] == asks
    touched = [i for i in range(len(sizes)) if i not in numpy_at
               and max(start, _frame_start(plan, i) + 8) < min(stop, _frame_start(plan, i) + 8 + sizes[i])]
    assert [i for what, i in log if what == "host"] == touched


@pytest.mark.parametrize("fail_after", [20, 450, 700], ids=["in_leaf_0", "in_leaf_1", "in_leaf_2"])
def test_a_writer_that_raises_leaves_the_plan_servable(fail_after) -> None:
    log = []
    plan, want = _recording_plan([300, 200, 300, 100], log)
    with pytest.raises(ConnectionResetError):
        plan.write_range(0, plan.total_len, _RecordingStream(log, fail_after=fail_after))
    # the next request gets every byte, and no leaf's transfer is asked twice
    buf = io.BytesIO()
    sent = plan.write_range(0, plan.total_len, buf)
    assert buf.getvalue() == want
    asks = [i for what, i in log if what == "ask"]
    assert sorted(asks) == [0, 1, 2, 3]
    # whatever the first request had asked for counts as brought ahead
    assert sent.ahead_bytes >= 200 + 300 + 100


def _join_all(threads) -> None:
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    assert not any(t.is_alive() for t in threads)


def test_two_handlers_over_one_leaf_bring_it_to_the_host_once() -> None:
    log = []
    plan, want = _recording_plan([100, 4000, 100], log, hold_s=0.2)
    inside = _frame_start(plan, 1) + 8
    ranges = [(inside + 10, inside + 2000), (inside + 2000, inside + 3990)]
    outs = [io.BytesIO() for _ in ranges]
    barrier = threading.Barrier(len(ranges))

    def handler(n: int) -> None:
        barrier.wait(timeout=30.0)
        plan.write_range(*ranges[n], outs[n])

    _join_all([threading.Thread(target=handler, args=(n,)) for n in range(len(ranges))])
    assert [out.getvalue() for out in outs] == [want[a:b] for a, b in ranges]
    assert log.count(("host", 1)) == 1 and not [e for e in log if e[0] == "ask"]


def test_many_handlers_never_ask_for_a_leaf_twice() -> None:
    """More handlers than cores over one staged plan, whole and partial
    ranges mixed: every response is the stream's bytes and no leaf's
    transfer is started a second time."""
    import sys

    log = []
    sizes = [64, 900, 32, 900, 900, 16, 700, 64]
    plan, want = _recording_plan(sizes, log, numpy_at=(2,), shard_at=(4,))
    rng = np.random.default_rng(3)
    jobs = [(0, plan.total_len)] * 6 + [
        tuple(sorted(int(x) for x in rng.integers(0, plan.total_len + 1, 2))) for _ in range(26)
    ]
    outs = [io.BytesIO() for _ in jobs]
    barrier = threading.Barrier(len(jobs))

    def handler(n: int) -> None:
        barrier.wait(timeout=30.0)
        plan.write_range(*jobs[n], outs[n])

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        _join_all([threading.Thread(target=handler, args=(n,)) for n in range(len(jobs))])
    finally:
        sys.setswitchinterval(interval)
    assert [out.getvalue() for out in outs] == [want[a:b] for a, b in jobs]
    asks = [i for what, i in log if what == "ask"]
    assert len(asks) == len(set(asks)) and 2 not in asks


def test_durable_save_rides_the_same_send_path(tmp_path) -> None:
    """``save_pytree`` (what ``utils/checkpoint.py`` writes to disk) has no
    loop of its own: a file of it loads back, and its leaves come ahead."""
    log = []
    plan, _ = _recording_plan([300, 200, 100], log)
    from torchft_tpu.checkpointing import serialization

    path = tmp_path / "state.tftc"
    state = {"a": jnp.arange(300, dtype=jnp.float32), "b": np.arange(7), "c": jnp.ones((3, 5), jnp.bfloat16)}
    with open(path, "wb") as f:
        save_pytree(state, f)
    with open(path, "rb") as f:
        back = load_pytree(f)
    assert path.stat().st_size == serialization.plan_pytree(state).total_len
    for k in state:
        np.testing.assert_array_equal(np.asarray(state[k]), back[k])
    with open(path, "wb") as f:
        assert plan.write_range(0, plan.total_len, f).ahead_bytes == 300
