"""Device-side quantization kernel tests (jnp fallback on CPU, Pallas
interpret-mode equivalence, fp8 device/host wire equivalence + golden
fixtures, and the full device-quantized gradient path)."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.ddp import ft_allreduce
from torchft_tpu.manager import Manager
from torchft_tpu.ops.pallas_quant import (
    BLOCK_ROWS,
    FP8,
    dequantize_int8_rowwise_device,
    dequantize_rowwise_device,
    quantize_int8_rowwise_device,
    quantize_rowwise_device,
    reduce_quantized_device,
)
from torchft_tpu.quantization import quantize_int8_rowwise, quantize_rowwise

from tests.test_manager import MemoryTransport, StubClient, _quorum_result

WIRE_FIXTURE = os.path.join(
    os.path.dirname(__file__), "fixtures", "quant_wire_golden.json"
)


class TestDeviceQuantKernels:
    def test_roundtrip_matches_host_reference(self) -> None:
        rng = np.random.default_rng(0)
        flat = rng.normal(size=5000).astype(np.float32)
        q, scales = quantize_int8_rowwise_device(jnp.asarray(flat), row_size=1024)
        assert q.dtype == jnp.int8
        assert q.shape[0] % BLOCK_ROWS == 0
        out = dequantize_int8_rowwise_device(q, scales, n=5000)
        max_err = np.abs(np.asarray(out) - flat).max()
        assert max_err <= np.abs(flat).max() / 127.0

        # values agree with the host (numpy) quantizer where rows overlap
        q_host, s_host = quantize_int8_rowwise(flat, row_size=1024)
        np.testing.assert_array_equal(
            np.asarray(q)[: q_host.shape[0]], q_host
        )
        np.testing.assert_allclose(
            np.asarray(scales).reshape(-1)[: s_host.shape[0]], s_host, rtol=1e-6
        )

    def test_pallas_interpret_equivalence(self) -> None:
        """The Pallas kernel (interpret mode) matches the jnp math."""
        rng = np.random.default_rng(1)
        flat = jnp.asarray(rng.normal(size=BLOCK_ROWS * 256).astype(np.float32))
        q_ref, s_ref = quantize_int8_rowwise_device(flat, row_size=256)
        q_pl, s_pl = quantize_int8_rowwise_device(
            flat, row_size=256, interpret=True
        )
        np.testing.assert_array_equal(np.asarray(q_pl), np.asarray(q_ref))
        np.testing.assert_allclose(
            np.asarray(s_pl), np.asarray(s_ref), rtol=1e-6
        )
        out_ref = dequantize_int8_rowwise_device(q_ref, s_ref, n=flat.shape[0])
        out_pl = dequantize_int8_rowwise_device(
            q_pl, s_pl, n=flat.shape[0], interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(out_pl), np.asarray(out_ref), rtol=1e-6
        )

    def test_zero_input(self) -> None:
        q, s = quantize_int8_rowwise_device(jnp.zeros(100), row_size=128)
        out = dequantize_int8_rowwise_device(q, s, n=100)
        np.testing.assert_array_equal(np.asarray(out), np.zeros(100))


class TestDeviceFp8Kernels:
    """fp8 (e4m3) device kernels: parity with the host wire format
    (reference ships fp8 quantized collectives,
    ``torchft/quantization.py:30-41``)."""

    def test_device_matches_host_wire_bytes(self) -> None:
        rng = np.random.default_rng(2)
        flat = rng.normal(size=4096).astype(np.float32) * 10.0
        q_dev, s_dev = quantize_rowwise_device(
            jnp.asarray(flat), row_size=1024, kind=FP8
        )
        q_host, s_host = quantize_rowwise(flat, row_size=1024, kind=FP8)
        rows = q_host.shape[0]
        # bit-identical payload (both sides clip then round-to-nearest-even)
        np.testing.assert_array_equal(
            np.asarray(q_dev)[:rows].view(np.uint8), q_host.view(np.uint8)
        )
        np.testing.assert_allclose(
            np.asarray(s_dev).reshape(-1)[:rows], s_host, rtol=1e-6
        )

    def test_roundtrip_error_bound(self) -> None:
        rng = np.random.default_rng(3)
        flat = rng.normal(size=3000).astype(np.float32)
        q, s = quantize_rowwise_device(jnp.asarray(flat), kind=FP8)
        out = dequantize_rowwise_device(q, s, n=3000)
        # e4m3: 3 mantissa bits → ~6% relative near the top of the range
        err = np.abs(np.asarray(out) - flat)
        assert err.max() <= np.abs(flat).max() * 0.07

    def test_pallas_interpret_equivalence_fp8(self) -> None:
        rng = np.random.default_rng(4)
        flat = jnp.asarray(
            rng.normal(size=BLOCK_ROWS * 256).astype(np.float32)
        )
        q_ref, s_ref = quantize_rowwise_device(flat, row_size=256, kind=FP8)
        q_pl, s_pl = quantize_rowwise_device(
            flat, row_size=256, kind=FP8, interpret=True
        )
        np.testing.assert_array_equal(
            np.asarray(q_pl).view(np.uint8), np.asarray(q_ref).view(np.uint8)
        )
        np.testing.assert_allclose(
            np.asarray(s_pl), np.asarray(s_ref), rtol=1e-6
        )

    def test_reduce_matches_host_reduce(self) -> None:
        from torchft_tpu.quantization import reduce_quantized

        rng = np.random.default_rng(5)
        w = 3
        contributions = [
            rng.normal(size=BLOCK_ROWS * 128).astype(np.float32)
            for _ in range(w)
        ]
        qs, scs = zip(
            *(quantize_rowwise(c, row_size=128, kind=FP8) for c in contributions)
        )
        q_host, s_host = reduce_quantized(
            np.stack(qs), np.stack(scs), kind=FP8
        )
        q_dev, s_dev = reduce_quantized_device(
            jnp.asarray(np.stack(qs)),
            jnp.asarray(np.stack(scs))[:, :, None],
            kind=FP8,
        )
        np.testing.assert_array_equal(
            np.asarray(q_dev).view(np.uint8), q_host.view(np.uint8)
        )
        np.testing.assert_allclose(
            np.asarray(s_dev).reshape(-1), s_host, rtol=1e-6
        )

    def test_reduce_interpret_equivalence_fp8(self) -> None:
        rng = np.random.default_rng(6)
        qs = []
        scs = []
        for _ in range(2):
            q, s = quantize_rowwise(
                rng.normal(size=BLOCK_ROWS * 128).astype(np.float32),
                row_size=128,
                kind=FP8,
            )
            qs.append(q)
            scs.append(s)
        args = (
            jnp.asarray(np.stack(qs)),
            jnp.asarray(np.stack(scs))[:, :, None],
        )
        q_ref, s_ref = reduce_quantized_device(*args, kind=FP8)
        q_pl, s_pl = reduce_quantized_device(*args, kind=FP8, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(q_pl).view(np.uint8), np.asarray(q_ref).view(np.uint8)
        )
        np.testing.assert_allclose(
            np.asarray(s_pl), np.asarray(s_ref), rtol=1e-6
        )


class TestWireGolden:
    """Golden-fixture lock on BOTH wire formats: a deterministic input must
    quantize to byte-identical payloads across rounds (regenerate with
    WRITE_FIXTURE=true) — the analog of the reference's quantization unit
    goldens."""

    def _wire(self):
        rng = np.random.default_rng(42)
        flat = (rng.normal(size=512) * np.logspace(-2, 2, 512)).astype(
            np.float32
        )
        out = {}
        for kind in ("int8", "fp8"):
            q, s = quantize_rowwise(flat, row_size=128, kind=kind)
            out[kind] = {
                "payload": q.view(np.uint8).reshape(-1).tolist(),
                "scales": s.astype(float).tolist(),
            }
        return out

    def test_wire_matches_fixture(self) -> None:
        wire = self._wire()
        if os.environ.get("WRITE_FIXTURE") == "true":
            with open(WIRE_FIXTURE, "w") as f:
                json.dump(wire, f)
            pytest.skip("fixture regenerated")
        with open(WIRE_FIXTURE) as f:
            expected = json.load(f)
        for kind in ("int8", "fp8"):
            assert wire[kind]["payload"] == expected[kind]["payload"], kind
            np.testing.assert_allclose(
                wire[kind]["scales"], expected[kind]["scales"], rtol=1e-6
            )

    def test_device_quantizer_matches_fixture(self) -> None:
        if not os.path.exists(WIRE_FIXTURE):
            pytest.skip("fixture not generated yet")
        rng = np.random.default_rng(42)
        flat = (rng.normal(size=512) * np.logspace(-2, 2, 512)).astype(
            np.float32
        )
        with open(WIRE_FIXTURE) as f:
            expected = json.load(f)
        for kind in ("int8", "fp8"):
            q, _s = quantize_rowwise_device(
                jnp.asarray(flat), row_size=128, kind=kind
            )
            rows = len(expected[kind]["scales"])
            got = np.asarray(q)[:rows].view(np.uint8).reshape(-1).tolist()
            assert got == expected[kind]["payload"], kind


class TestDeviceQuantizedGradientPath:
    @pytest.mark.parametrize("kind", ["int8", "fp8"])
    def test_ft_allreduce_quant_kind_env(self, kind, monkeypatch) -> None:
        """TORCHFT_QUANT_KIND selects the wire format of the
        device-quantized gradient path: the payload handed to
        ``Manager.allreduce_prequantized`` must carry the configured
        dtype, and values must still round-trip."""
        import ml_dtypes

        monkeypatch.setenv("TORCHFT_QUANT_KIND", kind)
        client = StubClient()
        client.quorum_results.append(
            _quorum_result(replica_world_size=2, max_world_size=2)
        )
        manager = Manager(
            comm=DummyCommunicator(world_size=2),
            load_state_dict=None,
            state_dict=None,
            min_replica_size=1,
            checkpoint_transport=MemoryTransport(),
            _manager_client=client,
            rank=0,
            world_size=1,
        )
        manager.start_quorum()
        wire_dtypes = []
        orig = manager.allreduce_prequantized

        def spy(q, scales, n, device=None):
            wire_dtypes.append(q.dtype)
            return orig(q, scales, n, device=device)

        monkeypatch.setattr(manager, "allreduce_prequantized", spy)
        tree = {"w": jnp.full((64, 32), 3.0, dtype=jnp.float32)}
        out = ft_allreduce(manager, tree, should_quantize=True)
        expected_dtype = (
            np.dtype(np.int8)
            if kind == "int8"
            else np.dtype(ml_dtypes.float8_e4m3fn)
        )
        assert wire_dtypes == [expected_dtype]
        # passthrough double: sum == own contribution; AVG over 2 halves it
        tol = 0.02 if kind == "int8" else 0.1  # e4m3: 3 mantissa bits
        np.testing.assert_allclose(
            np.asarray(out["w"]), np.full((64, 32), 1.5), atol=tol
        )

    def test_bad_quant_kind_fails_at_manager_startup(self, monkeypatch) -> None:
        monkeypatch.setenv("TORCHFT_QUANT_KIND", "FP9")
        with pytest.raises(ValueError, match="TORCHFT_QUANT_KIND"):
            Manager(
                comm=DummyCommunicator(world_size=1),
                load_state_dict=None,
                state_dict=None,
                min_replica_size=1,
                checkpoint_transport=MemoryTransport(),
                _manager_client=StubClient(),
                rank=0,
                world_size=1,
            )

    def test_ft_allreduce_device_quantized(self) -> None:
        client = StubClient()
        client.quorum_results.append(
            _quorum_result(replica_world_size=2, max_world_size=2)
        )
        manager = Manager(
            comm=DummyCommunicator(world_size=2),
            load_state_dict=None,
            state_dict=None,
            min_replica_size=1,
            checkpoint_transport=MemoryTransport(),
            _manager_client=client,
            rank=0,
            world_size=1,
        )
        manager.start_quorum()
        tree = {
            "w": jnp.full((64, 32), 3.0, dtype=jnp.float32),
            "b": jnp.full(100, -1.5, dtype=jnp.bfloat16),
        }
        out = ft_allreduce(manager, tree, should_quantize=True)
        # passthrough double: sum == own contribution; AVG over 2 halves it
        np.testing.assert_allclose(
            np.asarray(out["w"]), np.full((64, 32), 1.5), atol=0.02
        )
        assert out["b"].dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out["b"]).astype(np.float32), np.full(100, -0.75), atol=0.02
        )
        # shardings preserved
        assert out["w"].sharding == tree["w"].sharding


class TestDeviceQuantizedWireIsMeshIndependent:
    """Degraded mode runs a wounded replica on a smaller mesh than its
    peers.  The device-quantized stream must therefore be a function of the
    leaf shapes alone: quantized shard by shard it lined up only between
    replicas on identical meshes, and the ring summed misaligned rows (or,
    where the padded lengths differed, never committed)."""

    # (mesh axes, the spec of the 2-D leaves): a healthy 2x2 replica, the same
    # replica re-lowered onto two chips, and one on a single chip
    LAYOUTS = (
        (dict(fsdp=2, tp=2), ("fsdp", "tp")),
        (dict(fsdp=2), ("fsdp", None)),
        (dict(fsdp=1), (None, None)),
    )

    @staticmethod
    def _tree(values, axes, spec, devices):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from torchft_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(devices=devices, **axes)
        specs = {"w": P(*spec), "odd": P(*spec), "bias": P()}
        return {
            k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in values.items()
        }

    @staticmethod
    def _values(seed):
        rng = np.random.default_rng(seed)
        return {
            "w": rng.normal(size=(64, 4096)).astype(np.float32),
            # neither dimension is a multiple of the 1024-element row
            "odd": rng.normal(size=(6, 1000)).astype(np.float32),
            "bias": rng.normal(size=(100,)).astype(jnp.bfloat16),
        }

    def test_stream_is_the_same_under_every_mesh(self, monkeypatch) -> None:
        import jax

        from torchft_tpu import ddp

        class Spy:
            def allreduce_prequantized(self, q, scales, n, device=None):
                self.stream = (q, scales, n)
                self.device = device
                raise RuntimeError("only the stream is wanted")

            def report_error(self, e):
                pass

        values = self._values(0)
        streams = []
        for axes, spec in self.LAYOUTS:
            n = int(np.prod(list(axes.values())))
            tree = self._tree(values, axes, spec, jax.devices()[4 : 4 + n])
            leaves, treedef = jax.tree_util.tree_flatten(tree)
            spy = Spy()
            ddp._allreduce_pytree_device_quantized(spy, leaves, treedef)
            streams.append(spy.stream)
            # the collective's reduce is sent to one of the replica's own chips
            assert spy.device in jax.devices()[4 : 4 + n]
        for q, scales, n in streams[1:]:
            assert n == streams[0][2]
            np.testing.assert_array_equal(q, streams[0][0])
            np.testing.assert_array_equal(scales, streams[0][1])

    def test_two_replicas_on_different_meshes_average(self) -> None:
        import threading

        import jax

        from torchft_tpu.communicator import TCPCommunicator
        from torchft_tpu.lighthouse import LighthouseServer

        lighthouse = LighthouseServer(
            bind="127.0.0.1:0",
            min_replicas=2,
            join_timeout_ms=100,
            quorum_tick_ms=20,
            heartbeat_timeout_ms=1000,
        )
        values = [self._values(seed) for seed in (0, 1)]
        layouts = [
            (*self.LAYOUTS[0], jax.devices()[:4]),
            (*self.LAYOUTS[1], jax.devices()[4:6]),
        ]
        results, errors, managers = [None, None], [], []

        def replica(idx: int) -> None:
            try:
                axes, spec, devices = layouts[idx]
                tree = self._tree(values[idx], axes, spec, devices)
                manager = Manager(
                    comm=TCPCommunicator(timeout_s=20.0),
                    load_state_dict=None,
                    state_dict=None,
                    min_replica_size=2,
                    init_sync=False,  # no state to heal: one gradient only
                    replica_id=f"mesh_{idx}",
                    lighthouse_addr=lighthouse.local_address(),
                    timeout=20.0,
                    quorum_timeout=20.0,
                    connect_timeout=20.0,
                )
                managers.append(manager)
                manager.start_quorum()
                out = ft_allreduce(manager, tree, should_quantize=True)
                assert manager.errored() is None, manager.errored()
                assert manager.should_commit()
                for k in tree:
                    assert out[k].sharding == tree[k].sharding
                    assert out[k].dtype == tree[k].dtype
                results[idx] = {k: np.asarray(v) for k, v in out.items()}
            except BaseException as e:  # noqa: BLE001 — raised by the test
                errors.append(e)

        threads = [threading.Thread(target=replica, args=(i,)) for i in range(2)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120.0)
        finally:
            for m in managers:
                m.shutdown()
            lighthouse.shutdown()
        if errors:
            raise errors[0]
        for k in values[0]:
            mean = (
                values[0][k].astype(np.float32) + values[1][k].astype(np.float32)
            ) / 2
            # two int8 roundings of values within +-5: a misaligned row is
            # off by whole units
            np.testing.assert_allclose(
                results[0][k].astype(np.float32), mean, atol=0.08
            )
            np.testing.assert_array_equal(results[0][k], results[1][k])
