"""Fault-tolerant data parallelism over the replica dimension.

The reference hooks torch DDP's bucket reducer into ``manager.allreduce``
(``torchft/ddp.py:31-78``).  JAX has no module/buckets: gradients are a
pytree produced by ``jax.grad`` inside a compiled step.  The replica-dim
average runs host-side — leaves are fetched to host, flattened into one
contiguous buffer per dtype (the bucketization DDP gets from its reducer),
ring-allreduced over DCN/TCP, and pushed back to device with the original
shardings.  Compiled programs never see the replica count (SURVEY.md §7).
"""

from __future__ import annotations

import functools
import os
import threading
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.checkpointing.serialization import (
    ShardedHostArray,
    shard_key as _shard_key,
)
from torchft_tpu.manager import Manager
from torchft_tpu.obs import spans as obs_spans
from torchft_tpu.obs.flight import FlightEvent
from torchft_tpu.obs.spans import span as obs_span
from torchft_tpu.work import DummyWork, Work

# Split gradient buckets at this size (reference: TORCHFT_USE_BUCKETIZATION /
# bucket_cap_mb, ``local_sgd.py:28``); pipelines D2H transfer with the rings.
# MUST be uniform across replicas: bucket boundaries shape the collective
# sequence (mismatches fail fast via the ring's frame-size validation, like
# the reference's frozen DDP bucket layout requirement, ``ddp.py:46-62``).
# The env is read per call with the parse memoized on the raw string: the
# same raw value always yields the same cap (uniform within a process AND
# across replicas that agree on the env), while tests can flip the env to
# exercise bucket boundaries without re-importing the module.  Malformed
# values fall back to the default rather than raising into the train loop.
BUCKET_CAP_MB_ENV = "TORCHFT_BUCKET_CAP_MB"
DEFAULT_BUCKET_CAP_MB = 32


@functools.lru_cache(maxsize=None)
def _parse_bucket_cap(raw: str) -> int:
    try:
        mb = float(raw) if raw else float(DEFAULT_BUCKET_CAP_MB)
    except ValueError:
        import logging

        logging.getLogger(__name__).warning(
            "invalid %s=%r; using %d MB", BUCKET_CAP_MB_ENV, raw, DEFAULT_BUCKET_CAP_MB
        )
        mb = float(DEFAULT_BUCKET_CAP_MB)
    return max(1, int(mb * (1 << 20)))


def _bucket_cap_bytes() -> int:
    return _parse_bucket_cap(os.environ.get(BUCKET_CAP_MB_ENV, ""))


def allreduce_pytree_result(tree: Any) -> Work:
    return DummyWork(tree)


def _unique_local_shards(leaf: Any) -> Dict[Tuple, Any]:
    """This host's addressable shards deduped by canonical global index
    (replicated shards — same index on several local devices — appear once),
    in deterministic key order shared by this host's twin in every replica
    group."""
    unique: Dict[Tuple, Any] = {}
    for s in leaf.addressable_shards:
        unique.setdefault(_shard_key(s.index, leaf.shape), s)
    return dict(sorted(unique.items()))


def _assemble_sharded(
    shape: Tuple[int, ...],
    sharding: Any,
    dtype: Any,
    addressable_shards: Any,
    lookup,
) -> Any:
    """Rebuild a (possibly non-fully-addressable) jax Array from host data:
    ``lookup(shard_key, shard)`` returns the numpy block for that shard.  The
    global array is never materialized on one host."""
    per_device = []
    for s in addressable_shards:
        buf = np.asarray(lookup(_shard_key(s.index, shape), s)).astype(
            dtype, copy=False
        )
        per_device.append(jax.device_put(buf, s.device))
    return jax.make_array_from_single_device_arrays(shape, sharding, per_device)


def _host_contribution(leaf: Any) -> Tuple[np.ndarray, Any]:
    """This host's flat (1-D) contribution to the replica-dim average, plus
    a ``restore(avg_flat) -> leaf`` function.

    Fully-addressable leaves ship whole.  For multi-host arrays (a replica
    group spanning hosts, the v5p reality) each host ships only its UNIQUE
    addressable shards: host h of every replica group addresses the same
    logical region (identical mesh + shardings across groups), so
    shard-local averaging over the per-``group_rank`` DCN ring is exact —
    same math, sharded bytes.  Restore rebuilds the global array from
    per-device buffers without ever materializing it unsharded.
    """
    if not isinstance(leaf, jax.Array) or leaf.is_fully_addressable:
        arr = np.asarray(leaf)
        shape, is_jax = arr.shape, isinstance(leaf, jax.Array)
        sharding = leaf.sharding if is_jax else None

        def _restore_full(avg_flat: np.ndarray) -> Any:
            host_val = avg_flat.reshape(shape)
            if is_jax:
                return jax.device_put(host_val, sharding)
            return host_val

        return arr.reshape(-1), _restore_full

    shards = list(leaf.addressable_shards)
    unique = _unique_local_shards(leaf)
    segments: List[np.ndarray] = []
    offsets: Dict[Tuple, Tuple[int, int, tuple]] = {}
    off = 0
    for k, s in unique.items():
        data = np.asarray(s.data)
        offsets[k] = (off, data.size, data.shape)
        segments.append(data.reshape(-1))
        off += data.size
    flat = np.concatenate(segments) if segments else np.empty(0, leaf.dtype)
    shape, sharding, dtype = leaf.shape, leaf.sharding, leaf.dtype

    def _restore_sharded(avg_flat: np.ndarray) -> Any:
        def _lookup(key: Tuple, _s: Any) -> np.ndarray:
            o, n, shp = offsets[key]
            return avg_flat[o : o + n].reshape(shp)

        return _assemble_sharded(shape, sharding, dtype, shards, _lookup)

    return flat, _restore_sharded


def allreduce_pytree(
    manager: Manager,
    tree: Any,
    should_quantize: bool = False,
    stream: Optional[int] = None,
) -> Work:
    """Average a pytree of gradients across participating replicas.

    Returns a Work whose value is the averaged pytree with original leaf
    types restored (jax leaves come back as device arrays with their
    original sharding).  Error swallowing and participation zeroing happen
    inside ``manager.allreduce``.

    ``stream``, when given, marks this as an ASYNC streamed fragment submit
    (the TORCHFT_STREAM_SYNC LocalSGD scheduler): exactly one work — the
    composite covering every bucket ring AND the restore — registers in the
    Manager's stream-fence registry instead of ``_pending_works``, same
    contract as ``Manager.outer_shard_allreduce(stream=)``; the per-bucket
    works are owned by the composite and register nowhere.  Not supported
    on the device-quantized path (no streamed caller quantizes here — the
    quantized streamed wire is DiLoCo's, via ``Manager.allreduce(stream=)``).
    """

    def _streamed(w: Work) -> Work:
        return w if stream is None else manager.stream_submitted(stream, w)

    if manager.errored():
        return _streamed(allreduce_pytree_result(tree))
    if manager.allreduce_is_identity():
        # single-member quorum: averaging is the identity; skip the
        # device→host→device round trip entirely
        return _streamed(allreduce_pytree_result(tree))

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return _streamed(allreduce_pytree_result(tree))

    if stream is None and should_quantize and all(
        isinstance(l, jax.Array) and l.is_fully_addressable for l in leaves
    ):
        # (multi-host arrays fall through to the bucketed path, which ships
        # shard-local contributions; int8 wire quantization still applies
        # via manager.allreduce(should_quantize=True))
        # Quantize ON DEVICE (Pallas on TPU): only int8 payload + rowwise
        # scales cross HBM→host→DCN — ~4x fewer bytes than shipping floats
        # and quantizing host-side.
        return _allreduce_pytree_device_quantized(manager, leaves, treedef)

    original = list(leaves)
    # The round trip as ONE span, opened here on the train thread and closed
    # by the gather thread when the composite work is done; its stages are
    # child spans on three threads (this one, the communicator's op thread,
    # the gather thread) that share its ``r`` and ``step``.  DDP_SYNC, the
    # flight event of its exit, carries the summed seconds of each stage.
    recorder = manager._flight
    obs_spans.bind(recorder)  # the caller is this replica's train thread
    sync_span = obs_span("tpuft/ddp/allreduce_pytree", flight=FlightEvent.DDP_SYNC)
    sync_span.__enter__()
    stage_s = {"plan_s": 0.0, "d2h_s": 0.0, "pack_s": 0.0, "ring_wait_s": 0.0, "h2d_s": 0.0}

    def _plan() -> Tuple[List[List[int]], List[int]]:
        # Kick off every device→host transfer asynchronously up front so DMA
        # overlaps the bucket assembly and the first ring.
        for leaf in leaves:
            if isinstance(leaf, jax.Array):
                leaf.copy_to_host_async()

        # Bucket by dtype (each dtype needs its own ring), then split large
        # buckets at ``bucket_cap`` bytes and submit each as its own collective:
        # the op thread rings bucket k while we fetch/assemble bucket k+1 —
        # transfer/communication pipelining, the reference's bucket_cap_mb
        # (``local_sgd.py:28,477-566``) in jax form.
        bucket_cap = _bucket_cap_bytes()
        order: Dict[str, List[int]] = {}
        leaf_bytes: List[int] = []
        for i, leaf in enumerate(leaves):
            if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
                # bucket by what actually crosses the wire: this host's unique
                # shard bytes (identical on twin hosts, so bucket boundaries —
                # and therefore ring frame sizes — stay uniform)
                dtype_name = leaf.dtype.name
                nbytes = sum(
                    int(s.data.nbytes) for s in _unique_local_shards(leaf).values()
                )
            elif hasattr(leaf, "dtype") and hasattr(leaf, "nbytes"):
                dtype_name, nbytes = leaf.dtype.name, int(leaf.nbytes)
            else:
                arr = np.asarray(leaf)
                dtype_name, nbytes = arr.dtype.name, int(arr.nbytes)
            leaf_bytes.append(nbytes)
            order.setdefault(dtype_name, []).append(i)

        groups: List[List[int]] = []
        for _dtype_name, idxs in order.items():
            group: List[int] = []
            group_bytes = 0
            for i in idxs:
                if group and group_bytes + leaf_bytes[i] > bucket_cap:
                    groups.append(group)
                    group, group_bytes = [], 0
                group.append(i)
                group_bytes += leaf_bytes[i]
            if group:
                groups.append(group)
        return groups, leaf_bytes

    works: List[Work] = []
    bucket_layouts: List[List[Tuple[int, int, int, tuple]]] = []
    try:
        with obs_span("tpuft/ddp/plan") as stage:
            groups, leaf_bytes = _plan()
        stage_s["plan_s"] = stage.duration_s
        for bucket, group in enumerate(groups):
            # waits async copies; sharded leaves contribute local shards only
            with obs_span("tpuft/ddp/d2h", bucket=bucket) as stage:
                contribs = [_host_contribution(leaves[i]) for i in group]
            stage_s["d2h_s"] += stage.duration_s
            with obs_span("tpuft/ddp/pack", bucket=bucket) as stage:
                total = sum(c[0].size for c in contribs)
                flat = np.empty(total, dtype=contribs[0][0].dtype)
                layout = []
                off = 0
                for i, (arr, restore) in zip(group, contribs):
                    n = arr.size
                    flat[off : off + n] = arr
                    layout.append((i, off, n, restore))
                    off += n
            stage_s["pack_s"] += stage.duration_s
            # submit immediately: this bucket's ring overlaps the next
            # bucket's fetch/assembly; in_place — the bucket is ours and
            # discarded after the restore, so the ring reduces straight into
            # it (no defensive copy; on this host class that copy costs as
            # much as half the ring itself)
            with obs_span("tpuft/ddp/submit", bucket=bucket):
                works.append(
                    manager.allreduce(
                        flat,
                        should_quantize=should_quantize,
                        in_place=True,
                        register_pending=stream is None,
                    )
                )
            bucket_layouts.append(layout)
    except BaseException:
        sync_span.__exit__()
        raise

    def _gather() -> Any:
        out = list(original)
        for bucket, (work, layout) in enumerate(zip(works, bucket_layouts)):
            with obs_span("tpuft/ddp/ring_wait", bucket=bucket) as stage:
                flat = work.wait()
            stage_s["ring_wait_s"] += stage.duration_s
            with obs_span("tpuft/ddp/h2d", bucket=bucket) as stage:
                for i, off, n, restore in layout:
                    out[i] = restore(flat[off : off + n])
            stage_s["h2d_s"] += stage.duration_s
        return jax.tree_util.tree_unflatten(treedef, out)

    fut: "Future[Any]" = Future()

    def _finish() -> None:
        obs_spans.bind(recorder)
        sync_span.attach()
        try:
            value = _gather()
        except Exception as e:  # noqa: BLE001 — funnel, never raise
            manager.report_error(e)
            value = jax.tree_util.tree_unflatten(treedef, original)
        sync_span.set(
            buckets=len(works),
            bytes=sum(leaf_bytes),
            **{k: round(v, 6) for k, v in stage_s.items()},
        )
        sync_span.__exit__()
        fut.set_result(value)

    sync_span.detach()  # the gather thread carries the span from here
    threading.Thread(
        target=_finish, name="tpuft_ddp_gather", daemon=True
    ).start()
    out = Work(fut)
    # fence the WHOLE pipeline (including restore/device_put) at commit, not
    # just the wire collectives — a restore failure after the vote would
    # otherwise apply unaveraged gradients on this replica only.  Streamed
    # submits register the same composite in the stream-fence registry
    # instead, where the vote REFUSES (rather than waits) while it's in
    # flight.
    if stream is None:
        manager._register_pending(out)
    else:
        manager.stream_submitted(stream, out)
    return out


@functools.partial(jax.jit, static_argnames=("kind",))
def _quantize_leaf(leaf: jax.Array, kind: str) -> Tuple[jax.Array, jax.Array]:
    from torchft_tpu.ops.pallas_quant import quantize_rowwise_device

    return quantize_rowwise_device(leaf.reshape(-1), kind=kind)


@functools.partial(jax.jit, static_argnames=("shape", "starts"))
def _stitch(blocks: List[jax.Array], shape: Tuple, starts: Tuple) -> jax.Array:
    out = jnp.zeros(shape, blocks[0].dtype)
    for block, start in zip(blocks, starts):
        out = jax.lax.dynamic_update_slice(out, block, start)
    return out


def _on_one_device(leaf: jax.Array, turn: int) -> jax.Array:
    """``leaf`` whole, as a single-device array on one of ITS OWN devices.
    A leaf that one device already holds whole (one chip, or replicated) is
    that device's buffer, no copy.  The blocks of a leaf sharded across the
    replica's chips are copied device to device onto one of them, ``turn``
    rotating which (so neither the copies nor the quantizers pile onto the
    first chip), and stitched there; nothing passes through the host."""
    shards = _unique_local_shards(leaf)  # they tile a fully-addressable leaf
    if len(shards) == 1:
        (only,) = shards.values()
        return only.data
    devices = sorted(leaf.sharding.addressable_devices, key=lambda d: d.id)
    target = devices[turn % len(devices)]
    return _stitch(
        [jax.device_put(s.data, target) for s in shards.values()],
        shape=leaf.shape,
        starts=tuple(tuple(dim[0] for dim in key) for key in shards),
    )


def _allreduce_pytree_device_quantized(
    manager: Manager, leaves: list, treedef: Any
) -> Work:
    """Device quantize → Manager-orchestrated wire pipeline → device put.

    The wire stream is WHOLE leaves in tree order, each quantized by itself
    (its rows padded to the kernel's block), so the stream is a function of
    the leaf shapes alone and never of the replica's mesh: a wounded replica
    re-lowered onto fewer chips (degraded mode) still lines up row for row
    with its healthy peers, as the host path's whole-leaf buckets do.  That
    rules out quantizing shard by shard (a shard's 1024-element rows are not
    the leaf's).  Nor may the kernel see a sharded leaf: a bare
    ``pallas_call`` is not SPMD-partitionable and would gather the leaf onto
    EVERY chip.  So each leaf is brought whole onto one of the replica's own
    devices (:func:`_on_one_device`) and quantized there.  No float32 copy
    of the whole gradient is made: the quantizer's float temporaries are one
    leaf at a time, and the gathered leaves are at worst one more copy of
    the gradient, spread over the replica's chips.

    The fault-tolerance orchestration (quorum wait, participation zeroing,
    normalization, error funnel) lives in ``Manager.allreduce_prequantized``
    — this function only handles device-side quantization and pytree
    reassembly.  Returns a pending Work (the wire pipeline runs off-thread).
    """
    from torchft_tpu.ops.pallas_quant import ROW_SIZE
    from torchft_tpu.quantization import quant_kind

    try:
        # wire kind (int8 / fp8) from TORCHFT_QUANT_KIND; everything
        # downstream — the pipelined ring, the reduce kernels, the
        # dequantize — dispatches on the payload dtype
        kind = quant_kind()
        # dispatch every quantizer before fetching any result: the kernels
        # queue on their devices while the host copies drain in order
        quantized = [
            _quantize_leaf(_on_one_device(leaf, i), kind)
            for i, leaf in enumerate(leaves)
        ]
        # stream offset (in elements) of every leaf, from its padded rows
        offsets = [0]
        for q, _s in quantized:
            offsets.append(offsets[-1] + q.shape[0] * ROW_SIZE)
        # the only HBM→host bytes: 1-byte payload + f32 rowwise scales
        q_np = np.concatenate([np.asarray(q) for q, _s in quantized])
        s_np = np.concatenate([np.asarray(s).reshape(-1) for _q, s in quantized])
        # the collective's device-side reduce runs where this replica's
        # gradients live, not on the process-wide default device
        (device,) = quantized[0][0].devices()
        work = manager.allreduce_prequantized(
            q_np, s_np, offsets[-1], device=device
        )
    except Exception as e:  # noqa: BLE001 — errors never reach the train loop
        manager.report_error(e)
        return DummyWork(jax.tree_util.tree_unflatten(treedef, leaves))

    def _reassemble(avg: np.ndarray) -> Any:
        out = []
        for leaf, off in zip(leaves, offsets):
            whole = avg[off : off + leaf.size].reshape(leaf.shape)
            # each chip receives only its own block of the leaf
            out.append(
                _assemble_sharded(
                    leaf.shape,
                    leaf.sharding,
                    leaf.dtype,
                    leaf.addressable_shards,
                    lambda _key, s, whole=whole: whole[s.index],
                )
            )
        return jax.tree_util.tree_unflatten(treedef, out)

    out = manager.wrap_work(
        work.then(_reassemble), jax.tree_util.tree_unflatten(treedef, leaves)
    )
    manager._register_pending(out)  # fence reassembly at commit too
    return out


def ft_allreduce(manager: Manager, tree: Any, should_quantize: bool = False) -> Any:
    """Synchronous convenience: averaged pytree, or the input unchanged if
    this step already errored (the vote will discard it)."""
    return allreduce_pytree(manager, tree, should_quantize).wait()


class DistributedDataParallel:
    """Object-style facade matching the reference class name
    (``torchft/ddp.py:31-78``): holds the manager and averages gradient
    pytrees produced by a compiled step."""

    def __init__(self, manager: Manager) -> None:
        self.manager = manager

    def average_gradients(self, grads: Any, should_quantize: bool = False) -> Any:
        return ft_allreduce(self.manager, grads, should_quantize)

    def average_gradients_async(self, grads: Any, should_quantize: bool = False) -> Work:
        return allreduce_pytree(self.manager, grads, should_quantize)


def restore_like(new: Any, old: Any) -> Any:
    """Place one healed host-side leaf back on device in ``old``'s layout.

    ``new`` is what the checkpoint transport delivered: a numpy array, or a
    :class:`ShardedHostArray` when the sender was a multi-host replica group
    (its host shipped only its addressable shards — which are exactly the
    shards THIS host addresses, since mesh + shardings are identical across
    replica groups).
    """
    if isinstance(new, ShardedHostArray):
        assert isinstance(old, jax.Array), "sharded leaf healed into non-jax leaf"
        return _assemble_sharded(
            old.shape,
            old.sharding,
            old.dtype,
            old.addressable_shards,
            lambda key, _s: new.shards[key],
        )
    if isinstance(old, jax.Array):
        return jax.device_put(np.asarray(new), old.sharding)
    return new


def restore_tree_like(new_tree: Any, old_tree: Any) -> Any:
    """``restore_like`` over a pytree (``ShardedHostArray`` leaves kept
    atomic)."""
    return jax.tree_util.tree_map(
        restore_like,
        new_tree,
        old_tree,
        is_leaf=lambda x: isinstance(x, ShardedHostArray),
    )
