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
