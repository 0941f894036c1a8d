"""Fault-tolerant data parallelism over the replica dimension.

The reference hooks torch DDP's bucket reducer into ``manager.allreduce``
(``torchft/ddp.py:31-78``).  JAX has no module/buckets: gradients are a
pytree produced by ``jax.grad`` inside a compiled step.  The replica-dim
average runs host-side — leaves are fetched to host, flattened into
contiguous buckets per dtype (the bucketization DDP gets from its reducer),
ring-allreduced over DCN/TCP, and pushed back to device with the original
shardings.  Compiled programs never see the replica count (SURVEY.md §7).

The buckets cross to the host in an order made once per tree signature
(:func:`_pipeline_order`: the smallest first, then by falling size) and a
window of bytes at a time (``_D2H_AHEAD_BYTES``): a bucket's ring is
submitted as soon as it has landed and runs on the communicator's op thread
while the next buckets still cross, so transfer and ring are two stages of a
pipeline and not two stretches in a row.  The order shapes the collective
sequence, so like the bucket cap it must agree across replicas; it follows
from the tree signature and the cap alone.

The cap is also the most ONE transfer and ONE ring carry: a leaf over it
crosses in pieces (:func:`_pieces`), contiguous ranges of its row-major
order that follow from its shape, its dtype and the cap alone, each a
bucket with a ring of its own.  A piece is sliced on the device that holds
it (from each unique shard, its part of the piece) and lands as a transfer
of its own, so the first ring starts after a cap's worth has landed and not
after the largest leaf has, and the window holds many transfers where it
held two.  The leaf's host memory stays one flat array whose parts the
pieces are, and the leaf goes back to the device in one ``device_put``
after the last of its rings.

A leaf that lies in shards on several chips of THIS process (a replica
group on one multi-chip host: fully addressable, more than one unique shard)
never becomes a whole host array.  ``np.asarray`` of such a leaf lands every
shard in a host array made anew and then writes each into a second array of
the leaf's size made anew, strided: the whole gradient a second time onto
pages nobody has touched (0.6-0.95 GB/s on the v5e's host, PERF.md section
6, PR 44).  Here each unique shard's landed host value is written straight
into its place in the leaf's part of the bucket (:func:`_direct_indices`,
``_Slot.direct``), which is kept memory from the second step on.  The
bucket's layout does not change by it: the leaf's part stays the WHOLE leaf
in row-major order, so the wire, the ring's frames, :func:`_restore` and a
peer group on another layout (one chip, a wounded group re-lowered onto
fewer chips) see the bytes they saw.  Leaves on one device, replicated
leaves and numpy leaves ship whole as before; a leaf that is not fully
addressable ships this host's shards in shard-major ``segments``, right only
between groups of identical layout.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import os
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, Hashable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.checkpointing.serialization import (
    ShardedHostArray,
    shard_key as _shard_key,
)
from torchft_tpu.communicator import RING_TIME_KEYS
from torchft_tpu.manager import Manager
from torchft_tpu.obs import spans as obs_spans
from torchft_tpu.obs.flight import FlightEvent
from torchft_tpu.obs.spans import span as obs_span
from torchft_tpu.work import DummyWork, Work

# Split gradient buckets at this size (reference: TORCHFT_USE_BUCKETIZATION /
# bucket_cap_mb, ``local_sgd.py:28``); pipelines D2H transfer with the rings.
# Between leaves, and inside a leaf that is over it (:func:`_pieces`): the
# most one device-to-host transfer and one ring carry.
# MUST be uniform across replicas: bucket boundaries shape the collective
# sequence (mismatches fail fast via the ring's frame-size validation, like
# the reference's frozen DDP bucket layout requirement, ``ddp.py:46-62``).
# The env is read per call with the parse memoized on the raw string: the
# same raw value always yields the same cap (uniform within a process AND
# across replicas that agree on the env), while tests can flip the env to
# exercise bucket boundaries without re-importing the module.  Malformed
# values fall back to the default rather than raising into the train loop.
# 16 and not the 32 of before PR 46: jax lands every transfer in a host
# array made anew, and glibc serves a request of 32 MiB or more (its mmap
# threshold's ceiling) from pages mapped anew, every one of them touched for
# the first time, where a smaller one comes back from the arena that the step
# before freed it to.  On the v5e's host 973 MB in pieces of 32 MiB land in
# 880 ms, in pieces of 8 to 31 MiB in 220-300 ms, and 16 is the round number
# with room on both sides (PERF.md section 6, PR 46: the readings).
BUCKET_CAP_MB_ENV = "TORCHFT_BUCKET_CAP_MB"
DEFAULT_BUCKET_CAP_MB = 16


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


def _direct_indices(leaf: Any) -> Optional[Tuple[Any, ...]]:
    """Where each unique shard of ``leaf`` lies in the whole leaf (numpy
    indices, in :func:`_unique_local_shards`' order), for a jax leaf whose
    shards all live in this process on more than one of its devices; None for
    every other leaf.  The unique shards of a fully addressable leaf tile it,
    and they are the ones whose copies ``copy_to_host_async`` starts (the
    first device an index, as jax's own ``_cached_index_calc`` picks)."""
    if not (isinstance(leaf, jax.Array) and leaf.is_fully_addressable):
        return None
    shards = _unique_local_shards(leaf)
    return tuple(s.index for s in shards.values()) if len(shards) > 1 else None


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


# A Manager keeps the plan and the host buckets of this many tree signatures
# (the one that came longest ago goes first), and of each this many sets that
# nobody holds.  DDP sends one tree a step; a model with state the optimizer
# does not own two under ``quantize_outer``; streamed LocalSGD one a fragment.
_KEPT_SIGNATURES = 4
_KEPT_SETS = 2
# The device-to-host copies that are under way hold at most this many bytes
# past the bucket the train thread waits for: that bucket's, and the next
# ones' until the sum passes the constant; no later bucket's copy is started.
# With every leaf's copy started at once the runtime lands them all together
# and the rings wait for the whole gradient; a transfer by itself runs at two
# thirds of the speed of two side by side, and more side by side land faster
# still (PERF.md section 6, PR 32).  Since a bucket is at most the cap (a
# leaf over it crosses in pieces) the window holds several transfers and
# still hands over its first after one cap's worth.  64 MiB and 128 MiB read
# the same round trip wherever the ring is in it (1,246 against 1,228 ms on
# four chips, 995 against 995 on one), 256 and 512 MiB hand the first large
# bucket over 15 and 55 ms later and gain nothing on the whole (PERF.md
# section 6, PR 46).  So it is the smaller: it is what a replica's slices of
# pieces in flight may hold of its device's memory, beside one cap more, and
# half as many transfers are in flight whose sources die as they land, with
# a signal 11 on record that nobody has seen twice (PERF.md section 7 (bi)).
_D2H_AHEAD_BYTES = 64 << 20


@dataclasses.dataclass(frozen=True)
class _Slot:
    """Where one leaf lies in its bucket, and what brings it back.

    A fully addressable (or non-jax) leaf ships whole: its part of the bucket
    is the leaf in row-major order.  Where such a leaf lies in shards on
    several local chips, ``direct`` says where each unique shard goes inside
    that part, and the pack writes the shards there one by one (the same
    bytes in the same places as the whole leaf's copy, without the whole
    leaf on the host in between).  For multi-host arrays
    (a replica group spanning hosts, the v5p reality) each host ships only its
    UNIQUE addressable shards: host h of every replica group addresses the
    same logical region (identical mesh + shardings across groups), so
    shard-local averaging over the per-``group_rank`` DCN ring is exact —
    same math, sharded bytes.  ``segments`` then says where each shard lies
    inside the leaf's part of the bucket, and the restore rebuilds the global
    array from per-device buffers without ever materializing it unsharded.
    """

    index: int  # the leaf's place among the tree's leaves
    offset: int  # in elements, from the bucket's start
    size: int
    shape: Tuple[int, ...]
    dtype: Any
    sharding: Any  # None: not a jax.Array, comes back as numpy
    # a ``device_put`` there may ALIAS aligned host memory (the CPU backend's
    # zero copy), so what is put from a kept bucket is copied first
    host_backed: bool
    segments: Optional[Dict[Tuple, Tuple[int, int, tuple]]]  # shard key -> (offset, size, shape)
    direct: Optional[Tuple[Any, ...]] = None  # see :func:`_direct_indices`


class _Source(NamedTuple):
    """The part of a piece that one unique shard holds."""

    place: int  # the shard's, in :func:`_unique_local_shards`' order
    starts: np.ndarray  # where the part starts in the shard (int32: :func:`_device_slice`'s operand)
    sizes: Tuple[int, ...]  # its shape
    where: Tuple[slice, ...]  # where it lies in the piece


@dataclasses.dataclass(frozen=True)
class _Piece:
    """One bucket's part of a leaf over the cap: a contiguous range of the
    leaf's row-major order (:func:`_pieces`), and where this process finds it
    on its devices."""

    shape: Tuple[int, ...]  # the leaf's rank: 1 on the fixed axes, the range, the rest whole
    sources: Tuple[_Source, ...]


@dataclasses.dataclass
class _Bucket:
    dtype: Any
    size: int  # elements
    slots: List[_Slot]
    buffer: int = 0  # which of the plan's host buffers holds it
    offset: int = 0  # in elements, from that buffer's start
    piece: Optional[_Piece] = None  # of ``slots[0]``'s leaf, whose buffer is the whole leaf
    last: bool = True  # of its buffer's buckets in the plan's order: restores the slots

    @property
    def nbytes(self) -> int:
        return self.size * self.dtype.itemsize

    @property
    def crossing(self) -> int:
        """The bytes that come from a device: what the window counts."""
        if self.piece is not None:
            return self.nbytes
        return sum(s.size for s in self.slots if s.sharding is not None) * self.dtype.itemsize


@dataclasses.dataclass
class _Plan:
    """One tree signature's buckets in the order they cross (see
    :func:`_pipeline_order`), the flat host buffers they lie in (a bucket's
    own, or for the pieces of a leaf over the cap the leaf's), and the sets
    of such buffers that were filled before and that nothing reads or writes
    now."""

    buckets: List[_Bucket]
    buffers: List[Tuple[Any, int]]  # dtype, elements
    nbytes: int  # what crosses the wire a round trip
    direct_nbytes: int  # of them, written from shards straight into a bucket
    split_nbytes: int  # of them, crossed as pieces of a leaf over the cap
    free: List[List[np.ndarray]] = dataclasses.field(default_factory=list)


class _BucketStore:
    """The plans and host buckets one Manager keeps from step to step.

    A bucket made with ``np.empty`` every step pays the first touch of every
    page every step: on the v5e's host a copy into fresh pages runs at
    0.6-0.95 GB/s against 11-20 GB/s into pages written before (PERF.md
    section 6, PR 27 and PR 30).  So a tree whose signature comes again is
    packed into the buffers of the step before.  A set is out from
    :meth:`take` until :meth:`give_back`, which only a round trip that ended
    without error calls, after its restored leaves are ready: a ring that
    failed or never ended may still write into its buffers, and they are
    never handed out again.  Lives as long as its Manager (a new life starts
    cold, as a restarted process does), and so do the round trips' gather
    threads, which give the sets back: ``Manager.shutdown`` waits for them
    (:meth:`join`).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._plans: "collections.OrderedDict[Hashable, _Plan]" = collections.OrderedDict()
        self._gathers: List[threading.Thread] = []  # started and not seen ended

    def plan(self, signature: Hashable, leaves: List[Any], bucket_cap: int) -> _Plan:
        with self._lock:
            plan = self._plans.get(signature)
            if plan is not None:
                self._plans.move_to_end(signature)
                return plan
        plan = _make_plan(leaves, bucket_cap)
        with self._lock:
            self._plans[signature] = plan
            while len(self._plans) > _KEPT_SIGNATURES:
                self._plans.popitem(last=False)
        return plan

    def take(self, plan: _Plan) -> Optional[List[np.ndarray]]:
        with self._lock:
            return plan.free.pop() if plan.free else None

    def give_back(self, plan: _Plan, buffers: List[np.ndarray]) -> None:
        with self._lock:
            if len(plan.free) < _KEPT_SETS:
                plan.free.append(buffers)

    def started(self, gather: threading.Thread) -> None:
        with self._lock:
            self._gathers = [t for t in self._gathers if t.is_alive()] + [gather]

    def join(self, timeout: float) -> None:
        """Wait, ``timeout`` seconds in all, for the gather threads that are
        still at work.  One lives on after its Work is done (it waits for the
        restored leaves and gives the set back) and is a daemon: past its
        Manager's end the interpreter could take the runtime down under it."""
        deadline = time.monotonic() + timeout
        with self._lock:
            gathers, self._gathers = self._gathers, []
        for gather in gathers:
            gather.join(max(0.0, deadline - time.monotonic()))

    def kept_bytes(self) -> int:
        with self._lock:
            return sum(
                int(b.nbytes) for p in self._plans.values() for bufs in p.free for b in bufs
            )


def _bucket_store(manager: Manager) -> _BucketStore:
    store = manager._host_buckets
    if store is None:
        store = manager._host_buckets = _BucketStore()
    return store


def _array_like(leaf: Any) -> Any:
    """``leaf`` if it says its own dtype and size, else as numpy sees it (a
    Python scalar)."""
    if hasattr(leaf, "dtype") and hasattr(leaf, "nbytes"):
        return leaf
    return np.asarray(leaf)


def _leaf_signature(leaf: Any) -> Hashable:
    if isinstance(leaf, jax.Array):
        return (leaf.shape, leaf.dtype.name, leaf.sharding)
    leaf = _array_like(leaf)
    return (tuple(leaf.shape), leaf.dtype.name, None)


def _pipeline_order(nbytes: List[int]) -> List[int]:
    """The order in which buckets of these sizes cross to the host and are
    rung: a pure function of the sizes (ties by place in the tree), so every
    replica with the same tree and cap derives the same one.

    Transfer and ring are the two stages of a flow shop whose times both
    follow the bytes.  The transfer is the slower a byte, so the whole takes
    the transfers' sum plus the ring of the LAST bucket to land: buckets go
    by falling size, and the tail is the smallest one's ring (by rising size
    the tail is the largest one's).  The smallest of all goes first instead:
    it costs the transfer nothing, its copy waits for the gradient program
    in the large one's stead, and the op thread rings it, both replicas
    meeting in a ring, while the first large one crosses."""
    falling = sorted(range(len(nbytes)), key=lambda b: (-nbytes[b], b))
    return falling[-1:] + falling[:-1]


def _pieces(shape: Tuple[int, ...], itemsize: int, cap: int) -> List[Tuple[Tuple[Any, ...], int, int]]:
    """A leaf of ``shape`` cut into contiguous ranges of its row-major order
    of at most ``cap`` bytes each: ``(index, start, size)``, the numpy index
    of the range in the leaf (fixed indices of the leading axes, then a slice
    of ONE axis), where it starts and how many elements it holds.

    A pure function of the shape, the element size and the cap, never of how
    the leaf lies on any replica's chips: every replica cuts the wire at the
    same places.  The cut axis is the first whose single index fits the cap
    (rows ``a:b`` of a ``[rows, columns]`` matrix, ``[i, a:b, :]`` of a stack
    of them); its ranges are as many as the cap asks for and as equal as
    they can be, so a leaf's pieces sort side by side in
    :func:`_pipeline_order`.  A piece is over the cap only where one element
    is."""
    inner = [int(np.prod(shape[k + 1 :], dtype=np.int64)) for k in range(len(shape))]
    axis = next((k for k, n in enumerate(inner) if n * itemsize <= cap), len(shape) - 1)
    rows, row = shape[axis], inner[axis]
    count = -(-rows // max(1, cap // (row * itemsize)))
    base, extra = divmod(rows, count)
    out = []
    for at, lead in enumerate(np.ndindex(*shape[:axis])):
        a = 0
        for p in range(count):
            b = a + base + (p < extra)
            out.append((lead + (slice(a, b),), (at * rows + a) * row, (b - a) * row))
            a = b
    return out


def _piece_of(leaf: Any, shape: Tuple[int, ...], index: Tuple[Any, ...]) -> _Piece:
    """The piece ``index`` of ``leaf`` (see :func:`_pieces`) as this process
    finds it: each unique shard's intersection with it."""
    lead, cut = index[:-1], index[-1]
    box = [(i, i + 1) for i in lead] + [(cut.start, cut.stop)] + [(0, n) for n in shape[len(index) :]]
    sources = []
    for place, shard in enumerate(_unique_local_shards(leaf).values()):
        spans = [s.indices(n)[:2] for s, n in zip(shard.index, shape)]
        both = [(max(p0, s0), min(p1, s1)) for (p0, p1), (s0, s1) in zip(box, spans)]
        if all(lo < hi for lo, hi in both):
            sources.append(
                _Source(
                    place,
                    np.asarray([lo - s0 for (lo, _), (s0, _) in zip(both, spans)], np.int32),
                    tuple(hi - lo for lo, hi in both),
                    tuple(slice(lo - p0, hi - p0) for (lo, hi), (p0, _) in zip(both, box)),
                )
            )
    return _Piece(shape=tuple(p1 - p0 for p0, p1 in box), sources=tuple(sources))


def _make_plan(leaves: List[Any], bucket_cap: int) -> _Plan:
    """Bucket by dtype (each dtype needs its own ring), then split large
    buckets at ``bucket_cap`` bytes, between leaves and inside a leaf that is
    over it (:func:`_pieces`; a numpy leaf and a leaf that is not fully
    addressable stay whole), and put the buckets in :func:`_pipeline_order`.
    Each is submitted as its own collective as soon as it has landed and is
    packed, while the copies of the next ``_D2H_AHEAD_BYTES`` are under way
    and the later ones not yet started: the op thread rings bucket k while
    bucket k+1 crosses — transfer / communication pipelining, the
    reference's bucket_cap_mb (``local_sgd.py:28,477-566``) in jax form.  The
    order, like the cap, shapes the collective sequence and follows from the
    tree signature alone."""
    order: Dict[str, List[int]] = {}
    described: List[Tuple[Any, int, Tuple[int, ...], Any]] = []
    for i, leaf in enumerate(leaves):
        segments = None
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            # bucket by what actually crosses the wire: this host's unique
            # shard bytes (identical on twin hosts, so bucket boundaries —
            # and therefore ring frame sizes — stay uniform)
            dtype, shape = leaf.dtype, leaf.shape
            segments, size = {}, 0
            for key, s in _unique_local_shards(leaf).items():
                segments[key] = (size, int(s.data.size), tuple(s.data.shape))
                size += int(s.data.size)
        else:
            leaf = _array_like(leaf)
            dtype, shape, size = leaf.dtype, tuple(leaf.shape), int(leaf.size)
        described.append((np.dtype(dtype), size, shape, segments))
        order.setdefault(dtype.name, []).append(i)

    buckets: List[_Bucket] = []
    buffers: List[Tuple[Any, int]] = []
    direct_nbytes = split_nbytes = 0

    def _close(bucket: _Bucket) -> None:
        bucket.buffer = len(buffers)
        buffers.append((bucket.dtype, bucket.size))
        buckets.append(bucket)

    for idxs in order.values():
        dtype = described[idxs[0]][0]
        bucket = _Bucket(dtype, 0, [])
        for i in idxs:
            _dtype, size, shape, segments = described[i]
            leaf = leaves[i]
            sharding = leaf.sharding if isinstance(leaf, jax.Array) else None
            # one transfer and one ring carry the cap at most
            split = bool(shape) and sharding is not None and segments is None and size * dtype.itemsize > bucket_cap
            if bucket.slots and (split or (bucket.size + size) * dtype.itemsize > bucket_cap):
                _close(bucket)
                bucket = _Bucket(dtype, 0, [])
            slot = _Slot(
                index=i,
                offset=bucket.size,
                size=size,
                shape=shape,
                dtype=dtype,
                sharding=sharding,
                host_backed=sharding is not None
                and any(d.platform == "cpu" for d in sharding.device_set),
                segments=segments,
                direct=_direct_indices(leaf),
            )
            direct_nbytes += size * dtype.itemsize if slot.direct is not None else 0
            if not split:
                bucket.slots.append(slot)
                bucket.size += size
                continue
            # the leaf's buffer is the whole leaf; its pieces are parts of it
            split_nbytes += size * dtype.itemsize
            for index, start, count in _pieces(shape, dtype.itemsize, bucket_cap):
                buckets.append(
                    _Bucket(
                        dtype, count, [slot], buffer=len(buffers), offset=start,
                        piece=_piece_of(leaf, shape, index),
                    )
                )
            buffers.append((dtype, size))
        if bucket.slots:
            _close(bucket)
    buckets = [buckets[b] for b in _pipeline_order([b.nbytes for b in buckets])]
    ends = {bucket.buffer: at for at, bucket in enumerate(buckets)}
    for at, bucket in enumerate(buckets):
        bucket.last = ends[bucket.buffer] == at
    return _Plan(
        buckets=buckets,
        buffers=buffers,
        nbytes=sum(b.nbytes for b in buckets),
        direct_nbytes=direct_nbytes,
        split_nbytes=split_nbytes,
    )


@functools.partial(jax.jit, static_argnames=("sizes",))
def _device_slice(block: jax.Array, starts: Any, sizes: Tuple[int, ...]) -> jax.Array:
    """``block[starts : starts + sizes]`` on the ONE device that holds
    ``block``; the start is an operand, so a leaf's pieces share a program."""
    return jax.lax.dynamic_slice(block, [starts[k] for k in range(len(sizes))], sizes)


def _start_copies(leaves: List[Any], bucket: _Bucket) -> List[Any]:
    """Start the device-to-host copies of one bucket (on a leaf that is not
    fully addressable: of its addressable shards, which are what
    :func:`_to_host` reads).  A piece's parts are sliced here, each on the
    device of the shard it lies in (a slice of the leaf itself would be a
    program over the group's mesh), and returned: device memory beside the
    gradients until they have landed."""
    if bucket.piece is None:
        arrays = [leaves[slot.index] for slot in bucket.slots if slot.sharding is not None]
    else:
        shards = [s.data for s in _unique_local_shards(leaves[bucket.slots[0].index]).values()]
        arrays = [
            shards[src.place] if src.sizes == shards[src.place].shape
            else _device_slice(shards[src.place], src.starts, src.sizes)
            for src in bucket.piece.sources
        ]
    for array in arrays:
        array.copy_to_host_async()
    return arrays


def _to_host(leaf: Any, slot: _Slot) -> List[np.ndarray]:
    """This host's contribution of one leaf in the order of its place in the
    bucket (waits for the leaf's asynchronous copy): flat, except for a
    ``direct`` slot, whose unique shards come as they landed, one host array
    each in ``slot.direct``'s order.  ``np.asarray`` of the leaf itself would
    assemble them in a whole-leaf array made anew, the leaf's size in fresh
    pages a second time; the pack writes them into the bucket instead, whose
    part for this leaf is that same row-major whole."""
    if slot.direct is not None:
        return [np.asarray(s.data) for s in _unique_local_shards(leaf).values()]
    if slot.segments is None:
        return [np.asarray(leaf).reshape(-1)]
    shards = _unique_local_shards(leaf)
    return [np.asarray(shards[key].data).reshape(-1) for key in slot.segments]


def _land(leaves: List[Any], bucket: _Bucket, crossing: List[Any]) -> List[List[np.ndarray]]:
    """Wait for one bucket's copies (``crossing``: what :func:`_start_copies`
    returned for it): its host values, a list a slot, or the parts of its
    piece.  Sharded leaves contribute local shards only."""
    if bucket.piece is not None:
        return [[np.asarray(array) for array in crossing]]
    return [_to_host(leaves[slot.index], slot) for slot in bucket.slots]


def _pack(bucket: _Bucket, flat: np.ndarray, hosts: List[List[np.ndarray]]) -> None:
    """Write one bucket's landed host values (a list a slot, or the parts of
    a piece) into ``flat``, its part of a host buffer."""
    if bucket.piece is not None:
        part = flat.reshape(bucket.piece.shape)
        for src, block in zip(bucket.piece.sources, hosts[0]):
            part[src.where] = block
        return
    for slot, parts in zip(bucket.slots, hosts):
        if slot.direct is not None:
            whole = flat[slot.offset : slot.offset + slot.size].reshape(slot.shape)
            for index, block in zip(slot.direct, parts):
                whole[index] = block
            continue
        off = slot.offset
        for arr in parts:
            flat[off : off + arr.size] = arr
            off += arr.size


def _restore(leaf: Any, slot: _Slot, avg_flat: np.ndarray, aliased: bool) -> Any:
    """The averaged leaf in ``leaf``'s type and layout.  ``aliased``: the
    average lies in a kept bucket, which the next step overwrites, so nothing
    that is returned may share its memory."""
    if slot.sharding is None:
        host_val = avg_flat.reshape(slot.shape)
        return host_val.copy() if aliased else host_val
    copy = aliased and slot.host_backed
    if slot.segments is None:
        host_val = avg_flat.reshape(slot.shape)
        return jax.device_put(host_val.copy() if copy else host_val, slot.sharding)

    def _lookup(key: Tuple, _s: Any) -> np.ndarray:
        o, n, shp = slot.segments[key]
        block = avg_flat[o : o + n].reshape(shp)
        return block.copy() if copy else block

    return _assemble_sharded(
        slot.shape, slot.sharding, slot.dtype, leaf.addressable_shards, _lookup
    )


def _ring_account(before: Dict[str, Any], after: Dict[str, Any]) -> Dict[str, Any]:
    """What a round trip's rings sent and where their time went: the
    difference of two ``Manager.ring_counters()`` readings, as DDP_SYNC
    carries it.  ``ring_bytes`` on every lane and ``striped_bytes`` on the
    lanes other than lane 0 (what striping moved off the one stream).
    ``ring_rx_s``, ``ring_add_s``, ``ring_tx_s``: a lane's seconds in recv, in
    the reduce's add and in send, the MEAN over the lanes that sent bytes in
    the round trip (lanes run beside each other on equal parts, so the mean
    is a lane's share of the wall); ``ring_reduce_s``, ``ring_average_s``,
    ``ring_gather_s``, ``ring_tail_s``: the op thread's in the two phases, the
    division's own pass (the Python tier's between them; on the native tier the
    stand-alone division pass: rings of one member, so 0 here while
    ``ring_add_s`` carries the division) and the steps' tails.  A
    reconfiguration in between starts the counts anew: such a round trip
    records no bytes and no seconds, and a communicator that counts no time
    records none.  ``ring_calls``: the ring calls the op thread made for the
    round trip (one a bucket on the per-call path, ONE where the rings are a
    session), and ``ring_wait_push_s``: a session's op thread waiting for
    the train thread's next bucket, outside both phases; each where the
    communicator counts it."""
    account: Dict[str, Any] = {"ring_bytes": 0, "striped_bytes": 0}
    if before["epoch"] != after["epoch"]:
        return account
    sent = [
        max(0, b - a)
        for a, b in zip(before.get("lane_tx_bytes") or [], after.get("lane_tx_bytes") or [])
    ]
    account.update(ring_bytes=sum(sent), striped_bytes=sum(sent[1:]))
    if "ring_calls" in before and "ring_calls" in after:
        account["ring_calls"] = after["ring_calls"] - before["ring_calls"]
    if "ring_wait_push_s" in before and "ring_wait_push_s" in after:
        account["ring_wait_push_s"] = round(after["ring_wait_push_s"] - before["ring_wait_push_s"], 6)
    if not all(k in before and k in after for k in RING_TIME_KEYS):
        return account
    busy = [lane for lane, n in enumerate(sent) if n]
    for key in RING_TIME_KEYS:
        a, b = before[key], after[key]
        if key.startswith("lane_"):  # lane_rx_s -> ring_rx_s
            spent = sum(b[lane] - a[lane] for lane in busy) / len(busy) if busy else 0.0
            account[f"ring_{key[len('lane_'):]}"] = round(spent, 6)
        else:
            account[key] = round(b - a, 6)
    return account


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

    The leaves are packed into flat host buckets of the cap's size at most,
    a ring each; a leaf over the cap is several of them, the parts of one
    buffer (:func:`_pieces`).  ``manager`` keeps the plan and the buffers of
    a tree whose signature comes again (tree structure, each leaf's shape,
    dtype and sharding, the bucket cap) for its life (:class:`_BucketStore`):
    host memory of the size of the gradients it averages (this host's share),
    written every step and allocated once.  The Work's value never aliases
    them.

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

    # The round trip as ONE span, opened here on the train thread and closed
    # by the gather thread when the composite work is done; its stages are
    # child spans on three threads (this one, the communicator's op thread,
    # the gather thread) that share its ``r`` and ``step``.  DDP_SYNC, the
    # flight event of its exit, carries the summed seconds of each stage.
    recorder = manager._flight
    obs_spans.bind(recorder)  # the caller is this replica's train thread
    sync_span = obs_span("tpuft/ddp/allreduce_pytree", flight=FlightEvent.DDP_SYNC)
    sync_span.__enter__()
    stage_s = {
        "plan_s": 0.0, "d2h_s": 0.0, "pack_s": 0.0, "ring_wait_s": 0.0, "h2d_s": 0.0,
        "first_submit_s": 0.0,  # from the round trip's start to the first bucket's submit
    }

    ring_before = manager.ring_counters()
    store = _bucket_store(manager)
    works: List[Work] = []  # a ring each, where the rings are calls of their own
    session = None  # the round trip's rings as ONE call of the op thread, where the tier has it
    flats: List[np.ndarray] = []  # each bucket's part of its host buffer, in the plan's order
    buffers: List[Any] = []  # the plan's host buffers: a kept set, or made as the buckets come
    kept: Optional[List[np.ndarray]] = None
    try:
        with obs_span("tpuft/ddp/plan") as stage:
            bucket_cap = _bucket_cap_bytes()
            plan = store.plan(
                (treedef, bucket_cap, tuple(_leaf_signature(l) for l in leaves)),
                leaves,
                bucket_cap,
            )
        stage_s["plan_s"] = stage.duration_s
        if not should_quantize:
            # by what the communicator offers and this call asks, never by a
            # knob: the op thread enters the session here and rings each
            # bucket as it is pushed, where it took them one op at a time
            session = manager.ring_session(len(plan.buckets))
        asked = 0  # buckets whose copies to the host have been started
        ahead = 0  # the bytes of them that have not landed
        flying: Dict[int, List[Any]] = {}  # what crosses, by bucket: a piece's slices live here alone
        for b, bucket in enumerate(plan.buckets):
            with obs_span("tpuft/ddp/d2h", bucket=b) as stage:
                # this bucket's copies are under way and the next ones' until
                # their bytes pass the window, no later one's: they land in
                # the plan's order, and bucket b's ring runs on the op thread
                # while buckets b+1 .. still cross
                while asked < len(plan.buckets) and (asked <= b or ahead < _D2H_AHEAD_BYTES):
                    flying[asked] = _start_copies(leaves, plan.buckets[asked])
                    ahead += plan.buckets[asked].crossing
                    asked += 1
                # waits for the copies; a piece's slices leave the device here
                hosts = _land(leaves, bucket, flying.pop(b))
                ahead -= bucket.crossing
            stage_s["d2h_s"] += stage.duration_s
            with obs_span("tpuft/ddp/pack", bucket=b) as stage:
                if b == 0:
                    # as late as can be: the set of the step before comes
                    # back when its restored leaves are ready on the device,
                    # and that step's vote and update, this step's quorum and
                    # gradient program and the first bucket's copy lie between.
                    # A ``direct`` slot's shards need no set sooner either:
                    # they wait above as landed host values and are written
                    # here.  A take before the first wait would find the set
                    # of the step before still out whenever its restores took
                    # longer than the vote and two dispatches, pack that step
                    # cold and hold a second set (``_KEPT_SETS`` is 2: the
                    # tree's size in host memory once more) from then on.
                    kept = store.take(plan)
                    buffers = kept if kept is not None else [None] * len(plan.buffers)
                if buffers[bucket.buffer] is None:
                    dtype, size = plan.buffers[bucket.buffer]
                    buffers[bucket.buffer] = np.empty(size, dtype=dtype)
                flat = buffers[bucket.buffer][bucket.offset : bucket.offset + bucket.size]
                _pack(bucket, flat, hosts)
            stage_s["pack_s"] += stage.duration_s
            flats.append(flat)
            # submit immediately: this bucket's ring overlaps the next
            # bucket's fetch/assembly; in_place — the bucket is ours until
            # the restore is done, so the ring reduces straight into it (no
            # defensive copy; on this host class that copy costs as much as
            # half the ring itself)
            with obs_span("tpuft/ddp/submit", bucket=b) as stage:
                if b == 0:
                    stage_s["first_submit_s"] = stage.t0 - sync_span.t0
                if session is not None:
                    session.push(flat)
                    continue
                works.append(
                    manager.allreduce(
                        flat,
                        should_quantize=should_quantize,
                        in_place=True,
                        register_pending=stream is None,
                    )
                )
    except BaseException:
        if session is not None:
            session.close()  # the buckets not pushed never start, as rings never submitted
        sync_span.__exit__()
        raise

    def _gather() -> List[Any]:
        out = list(leaves)
        for b, bucket in enumerate(plan.buckets):
            with obs_span("tpuft/ddp/ring_wait", bucket=b) as stage:
                if session is not None:
                    session.wait(b)  # rung in place; a failed one rides through as it is
                    flat = flats[b]
                else:
                    flat = works[b].wait()
            stage_s["ring_wait_s"] += stage.duration_s
            with obs_span("tpuft/ddp/h2d", bucket=b) as stage:
                aliased = np.may_share_memory(flat, flats[b])
                if bucket.piece is not None:
                    # the leaf goes back whole, from its buffer, after the
                    # last of its pieces' rings
                    if not aliased:
                        flats[b][:] = flat
                    flat, aliased = buffers[bucket.buffer], True
                if bucket.last:
                    for slot in bucket.slots:
                        avg = flat[slot.offset : slot.offset + slot.size]
                        out[slot.index] = _restore(leaves[slot.index], slot, avg, aliased)
            stage_s["h2d_s"] += stage.duration_s
        return out

    fut: "Future[Any]" = Future()

    def _finish() -> None:
        nonlocal leaves
        obs_spans.bind(recorder)
        sync_span.attach()
        restored: Optional[List[Any]] = None
        try:
            restored = _gather()
        except Exception as e:  # noqa: BLE001 — funnel, never raise
            manager.report_error(e)
        sync_span.set(
            buckets=len(flats),
            warm_buckets=0 if kept is None else len(flats),
            bytes=plan.nbytes,
            direct_bytes=plan.direct_nbytes,
            split_bytes=plan.split_nbytes,
            **_ring_account(ring_before, manager.ring_counters()),
            **{k: round(v, 6) for k, v in stage_s.items()},
        )
        sync_span.__exit__()
        value = jax.tree_util.tree_unflatten(treedef, leaves if restored is None else restored)
        # this thread lives on below: the gradients as they came are the
        # caller's to free (a copy of them on the device beside the average)
        leaves = []
        fut.set_result(value)
        # The buckets go back to the store only now, off the train thread's
        # path, and only from a round trip in which every ring ended without
        # error (one that failed or timed out may still be receiving into its
        # bucket) and whose restored leaves are ready (``device_put`` reads
        # the bucket until then).
        failed = session.swallowed if session is not None else next(
            (w.swallowed for w in works if w.swallowed is not None), None
        )
        if restored is None or failed is not None:
            return
        try:
            jax.block_until_ready([x for x in restored if isinstance(x, jax.Array)])
        except Exception:  # noqa: BLE001 — the next round trip will say so
            return
        store.give_back(plan, buffers)

    sync_span.detach()  # the gather thread carries the span from here
    gather = threading.Thread(target=_finish, name="tpuft_ddp_gather", daemon=True)
    gather.start()
    store.started(gather)
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
