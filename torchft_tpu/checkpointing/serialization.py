"""Streaming serialization for pytrees of jax/numpy arrays.

The reference streams ``torch.save``-serialized state dicts
(``torchft/checkpointing/_serialization.py:14-39``); here the state is an
arbitrary pytree whose array leaves are jax Arrays or numpy arrays.  The
format separates the (pickled) tree skeleton from raw array payloads so
multi-MB tensors stream as straight buffer copies with no pickle overhead:

``TFTC`` magic + version, skeleton (pickle with array leaves replaced by
placeholders), then per-array: dtype tag, shape, raw little-endian bytes.

Like the reference (which pickles tensor metadata over its transports,
``pg_transport.py:32-146``), the skeleton uses pickle and therefore assumes
the same trust model: checkpoint peers are other replicas of the same job
inside the cluster, never untrusted parties.

jax arrays are materialized to host numpy on save (``jax.device_get``) and
returned as numpy on load — the consumer decides placement/sharding
(``jax.device_put`` with a NamedSharding) because the healing replica's mesh
layout, not the sender's, governs where shards land.
"""

from __future__ import annotations

import hashlib
import io
import os
import pickle
import struct
import threading
import time
from dataclasses import dataclass, field
from typing import Any, BinaryIO, Iterator, List, NamedTuple, Optional, Sequence, Set, Tuple

import numpy as np

MAGIC = b"TFTC\x01"

# Target striped-heal chunk size.  Smaller chunks stripe/steal at finer
# granularity (better load balance, cheaper mid-heal failover) at the cost
# of more requests/frames; the default keeps per-chunk overhead <1% on
# multi-MB transfers.
HEAL_CHUNK_MB_ENV = "TORCHFT_HEAL_CHUNK_MB"
DEFAULT_HEAL_CHUNK_BYTES = 4 << 20


def heal_chunk_bytes() -> int:
    mb = os.environ.get(HEAL_CHUNK_MB_ENV)
    if mb:
        return max(1 << 16, int(float(mb) * (1 << 20)))
    return DEFAULT_HEAL_CHUNK_BYTES


# How far ahead of the leaf a send waits for the next leaves' device-to-host
# transfers are started (:meth:`PytreePlan.host_leaves`): this many leaves
# and this many payload bytes at most, the leaf next in line always.  Both
# are constants, not knobs, chosen by a reading on the chip (v5e, the kill
# cell's 2.9 GB state of 37 leaves of 0.02-268 MB over loopback, the median
# fetch of six; ``scripts/heal_serve_probe.py``, PERF.md section 6, PR 36):
# nothing ahead 7.57 s; 4 leaves 5.69 s; 8 leaves 5.20 s; 12 and 16 leaves
# within 512 MiB 5.11 and 5.16 s; 16 within 1 GiB 5.35 s and every leaf at
# once 5.35 s.  Few transfers in flight run slower than many (0.51 GB/s for
# one 268 MB leaf by itself, 0.72-0.80 for twelve), but with everything in
# flight the leaf the send waits for lands among the others: it waited 1.36
# s for leaves where 8 within 512 MiB waited 0.69 s.
_D2H_AHEAD_LEAVES = 8
_D2H_AHEAD_BYTES = 512 << 20


def chunk_ranges(
    header_len: int, leaf_nbytes: List[int], target_bytes: int
) -> List[Tuple[int, int]]:
    """Deterministic chunk boundaries over the serialized stream.

    The stream is a sequence of units — the header, then one (8-byte length
    + payload) per array.  Whole units pack greedily up to ``target_bytes``;
    a unit larger than the target splits at target granularity from its own
    start.  Boundaries are therefore a pure function of the tree structure
    and leaf sizes, so every peer holding the same state at the same step
    produces the SAME ranges over byte-identical content — the property that
    lets a healer assemble one buffer from many peers' streams.
    """
    target = max(1, int(target_bytes))
    units = [header_len] + [8 + n for n in leaf_nbytes]
    chunks: List[Tuple[int, int]] = []
    off = 0
    cur_start = 0
    cur = 0  # bytes accumulated in the open chunk
    for unit in units:
        if unit > target:
            if cur:
                chunks.append((cur_start, off))
            start = off
            while start < off + unit:
                stop = min(off + unit, start + target)
                chunks.append((start, stop))
                start = stop
            off += unit
            cur_start, cur = off, 0
            continue
        off += unit
        cur += unit
        if cur >= target:
            chunks.append((cur_start, off))
            cur_start, cur = off, 0
    if cur:
        chunks.append((cur_start, off))
    return chunks


def as_byte_view(arr: np.ndarray) -> memoryview:
    """Raw little-endian bytes of a contiguous array; works for extension
    dtypes (bfloat16, fp8) that reject ``memoryview.cast``."""
    return memoryview(np.ascontiguousarray(arr).reshape(-1).view(np.uint8))


def _resolve_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        # extension dtypes (bfloat16, float8_*) register via ml_dtypes
        import ml_dtypes  # noqa: F401

        return np.dtype(getattr(ml_dtypes, name))


@dataclass
class _ArrayPlaceholder:
    index: int
    dtype: str
    shape: Tuple[int, ...]


def shard_key(index: Tuple, shape: Tuple[int, ...]) -> Tuple:
    """Canonical, host-order-independent key for a shard's global index
    (a tuple of resolved ``(start, stop, step)`` per dimension)."""
    key = []
    for dim, sl in enumerate(index):
        if isinstance(sl, slice):
            key.append(sl.indices(shape[dim]))
        else:  # integer index
            key.append((int(sl), int(sl) + 1, 1))
    return tuple(key)


@dataclass
class _ShardedArrayPlaceholder:
    """Skeleton marker for a non-fully-addressable jax Array: this HOST's
    unique shards ride as separate payload arrays keyed by global index."""

    shape: Tuple[int, ...]
    dtype: str
    entries: List[Tuple[Tuple, _ArrayPlaceholder]]


@dataclass
class ShardedHostArray:
    """Host-local deserialized form of a multi-host (non-fully-addressable)
    jax Array: shard data keyed by canonical global index.  Convert back to
    a device array with ``torchft_tpu.ddp.restore_like`` against an existing
    array that carries the target sharding — sender host h and receiver
    host h address identical regions (same mesh + specs across replica
    groups), so the keys match exactly."""

    shape: Tuple[int, ...]
    dtype: str
    shards: dict  # shard_key -> np.ndarray


def _is_array_leaf(x: Any) -> bool:
    if isinstance(x, np.ndarray):
        return True
    # jax.Array without importing jax at module import time
    return type(x).__module__.startswith("jax") and hasattr(x, "__array__")


def _is_multihost_jax_array(x: Any) -> bool:
    return (
        type(x).__module__.startswith("jax")
        and hasattr(x, "is_fully_addressable")
        and not x.is_fully_addressable
    )


def _is_shard(leaf: Any) -> bool:
    """jax Shard: carries its array in ``.data`` and is not itself
    array-like.  The ``__array__`` check must come FIRST: probing ``.data``
    on a numpy extension-dtype array (e.g. ml_dtypes bfloat16, as produced
    by ``np.asarray`` of a bf16 jax array — DiLoCo fragment backups) raises
    ValueError out of ``hasattr``, since buffers cannot carry dtype 'E'."""
    return not hasattr(leaf, "__array__") and hasattr(leaf, "data")


def materialize_leaf(leaf: Any) -> np.ndarray:
    """Host numpy view/copy of a collected leaf (jax arrays device_get
    here, NOT at extraction time: the lazy plan asks for a leaf's host copy
    when a send comes near it, ``_D2H_AHEAD_LEAVES`` leaves ahead of the one
    being written.  What stays on the host AFTER the send is jax's affair:
    on a TPU ``np.asarray`` keeps the host value on the array for as long
    as the array lives, here as long as the plan, so a survivor holds every
    leaf it has served until the plan is dropped: ``docs/operations.md``
    section 7)."""
    if isinstance(leaf, np.ndarray):
        return leaf
    if _is_shard(leaf):
        return np.asarray(leaf.data)
    return np.asarray(leaf)


def _leaf_meta(leaf: Any) -> Tuple[str, Tuple[int, ...]]:
    """(dtype name, shape) without materializing the leaf on host."""
    if _is_shard(leaf):
        leaf = leaf.data
    return np.dtype(leaf.dtype).name, tuple(leaf.shape)


def _extract_arrays(obj: Any, arrays: List[Any]) -> Any:
    """Deep-copy the container skeleton, swapping array leaves for
    placeholders (handles dict/list/tuple; other types pickle as-is).

    ``arrays`` collects the RAW leaves (numpy arrays, jax Arrays, jax
    Shards) — call :func:`materialize_leaf` to get host bytes for one."""
    if _is_multihost_jax_array(obj):
        # ship only this host's unique addressable shards; the receiving
        # twin host reassembles them into its identical sharding layout
        shape = tuple(obj.shape)
        unique: dict = {}
        for s in obj.addressable_shards:
            unique.setdefault(shard_key(s.index, shape), s)
        entries: List[Tuple[Tuple, _ArrayPlaceholder]] = []
        for k in sorted(unique):
            dtype_name, sshape = _leaf_meta(unique[k])
            entries.append(
                (k, _ArrayPlaceholder(index=len(arrays), dtype=dtype_name, shape=sshape))
            )
            arrays.append(unique[k])
        return _ShardedArrayPlaceholder(
            shape=shape, dtype=obj.dtype.name, entries=entries
        )
    if _is_array_leaf(obj):
        # dtype.name (not .str) so extension dtypes like bfloat16 round-trip
        dtype_name, shape = _leaf_meta(obj)
        placeholder = _ArrayPlaceholder(
            index=len(arrays), dtype=dtype_name, shape=shape
        )
        arrays.append(obj)
        return placeholder
    if isinstance(obj, dict):
        return {k: _extract_arrays(v, arrays) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        mapped = [_extract_arrays(v, arrays) for v in obj]
        if isinstance(obj, list):
            return mapped
        # preserve NamedTuple types (optax optimizer states are namedtuples)
        if hasattr(obj, "_fields"):
            return type(obj)(*mapped)
        return tuple(mapped)
    return obj


def _restore_arrays(obj: Any, arrays: List[np.ndarray]) -> Any:
    if isinstance(obj, _ArrayPlaceholder):
        return arrays[obj.index]
    if isinstance(obj, _ShardedArrayPlaceholder):
        return ShardedHostArray(
            shape=obj.shape,
            dtype=obj.dtype,
            shards={k: arrays[ph.index] for k, ph in obj.entries},
        )
    if isinstance(obj, dict):
        return {k: _restore_arrays(v, arrays) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        mapped = [_restore_arrays(v, arrays) for v in obj]
        if isinstance(obj, list):
            return mapped
        if hasattr(obj, "_fields"):
            return type(obj)(*mapped)
        return tuple(mapped)
    return obj


class RangeSent(NamedTuple):
    """What :meth:`PytreePlan.write_range` did beside the writing."""

    # seconds the send was blocked waiting for a leaf to reach the host
    d2h_s: float
    # written payload bytes of the leaves whose device-to-host transfer had
    # been started before the send came to wait for them
    ahead_bytes: int


@dataclass
class PytreePlan:
    """Serialization plan: everything needed to stream a pytree while only
    the leaf being written and the few after it (``_D2H_AHEAD_LEAVES``) are
    wanted on the host.

    ``header`` is the byte prefix (magic + skeleton + array count); each
    leaf then rides as an 8-byte length + raw bytes.  ``total_len`` lets a
    server send Content-Length before generating a byte of payload."""

    header: bytes
    leaves: List[Any]
    leaf_nbytes: List[int]
    total_len: int
    # one-leaf D2H memo: several striped range requests cut the same large
    # leaf, and each write_range would otherwise device_get the whole leaf
    # again; the memo holds the most recent materialization
    _memo: Optional[Tuple[int, np.ndarray]] = None
    # the leaves whose transfer a send has started (host_leaves): a second
    # send over them, beside the first or after it, does not ask again
    _asked: Set[int] = field(default_factory=set)
    _lock: threading.Lock = field(default_factory=threading.Lock)
    # one a leaf: of two sends that come to one leaf together, one brings it
    # to the host and the other finds it in the memo
    _leaf_locks: List[threading.Lock] = field(default_factory=list)

    def __post_init__(self) -> None:
        self._leaf_locks = [threading.Lock() for _ in self.leaves]

    def header_digest(self) -> str:
        """Digest of the byte prefix (magic + skeleton + count).  Striped
        healers compare it across sources: peers serving the same step must
        agree byte-for-byte or assembling one buffer from many streams would
        silently corrupt."""
        return hashlib.sha256(self.header).hexdigest()

    def chunk_ranges(
        self, target_bytes: Optional[int] = None
    ) -> List[Tuple[int, int]]:
        return chunk_ranges(
            len(self.header), self.leaf_nbytes, target_bytes or heal_chunk_bytes()
        )

    def _materialize(self, index: int) -> np.ndarray:
        with self._leaf_locks[index]:
            with self._lock:
                if self._memo is not None and self._memo[0] == index:
                    return self._memo[1]
            arr = materialize_leaf(self.leaves[index])
            assert arr.nbytes == self.leaf_nbytes[index], (arr.nbytes, self.leaf_nbytes[index])
            with self._lock:
                self._memo = (index, arr)
            return arr

    def _start_transfer(self, index: int) -> None:
        """Start leaf ``index``'s device-to-host transfer and do not wait
        for it; nothing for a numpy leaf (it is on the host) or a leaf some
        send has asked for before."""
        leaf = self.leaves[index]
        start = getattr(leaf.data if _is_shard(leaf) else leaf, "copy_to_host_async", None)
        if start is None:
            return
        with self._lock:
            if index in self._asked:
                return
            self._asked.add(index)
        start()

    def host_leaves(self, indices: Sequence[int]) -> Iterator[Tuple[np.ndarray, bool]]:
        """The leaves ``indices`` on the host, one after the other, each with
        whether its device-to-host transfer had been started before this
        send came to wait for it.

        Before the send blocks on leaf k the transfers of the leaves after
        it in ``indices`` are started, ``_D2H_AHEAD_LEAVES`` of them and
        ``_D2H_AHEAD_BYTES`` at most (the next one always), in order and
        with leaf k first in line: they land while the caller writes leaf k.
        What the input shows decides the rest: no transfer is ever asked for
        a leaf outside ``indices`` or for a numpy leaf, and a send with no
        further leaf to bring (one leaf, a range inside a leaf, the last
        leaf) asks nothing and does what ``np.asarray`` does."""
        asked = 0  # indices[:asked] have had their transfer started
        for k, i in enumerate(indices):
            ahead = i in self._asked
            upto, flying = k + 1, 0
            for j in indices[k + 1 : k + 1 + _D2H_AHEAD_LEAVES]:
                flying += self.leaf_nbytes[j]
                if upto > k + 1 and flying > _D2H_AHEAD_BYTES:
                    break
                upto += 1
            if upto > k + 1:
                for j in indices[asked:upto]:
                    self._start_transfer(j)
                asked = upto
            yield self._materialize(i), ahead

    def write_range(self, start: int, stop: int, stream: BinaryIO) -> RangeSent:
        """Stream bytes [start, stop) of the serialized form, bringing to
        the host only the leaves whose payload overlaps the range (chunked
        HTTP fetches), the next ones while the last is being written
        (:meth:`host_leaves`)."""

        def emit(chunk, at: int) -> int:
            lo, hi = max(start, at), min(stop, at + len(chunk))
            if lo < hi:
                stream.write(memoryview(chunk)[lo - at : hi - at])
            return max(0, hi - lo)

        emit(self.header, 0)
        # the leaves whose frame (length + payload) the range touches: which
        # leaf, where its frame starts, whether its PAYLOAD is touched
        framed: List[Tuple[int, int, bool]] = []
        at = len(self.header)
        for i, nbytes in enumerate(self.leaf_nbytes):
            if at >= stop:
                break
            if at + 8 + nbytes > start:
                framed.append((i, at, max(start, at + 8) < min(stop, at + 8 + nbytes)))
            at += 8 + nbytes
        hosts = self.host_leaves([i for i, _, payload in framed if payload])
        d2h_s, ahead_bytes = 0.0, 0
        for i, at, payload in framed:
            emit(struct.pack("<Q", self.leaf_nbytes[i]), at)
            if payload:
                t0 = time.monotonic()
                leaf, ahead = next(hosts)
                d2h_s += time.monotonic() - t0
                wrote = emit(as_byte_view(leaf), at + 8)
                ahead_bytes += wrote if ahead else 0
        return RangeSent(d2h_s, ahead_bytes)


def _snapshot_leaf(leaf: Any) -> Any:
    """Point-in-time snapshot of one collected leaf without bringing it to
    host: numpy copies on host (the train loop may mutate it in place, e.g.
    LocalSGD host params); jax arrays/shards copy ON DEVICE (HBM-to-HBM) —
    a mere reference would die when a donating jit (HSDPTrainer's update,
    ``parallel/hsdp.py``) consumes the original buffer mid-stream."""
    if isinstance(leaf, np.ndarray):
        return leaf.copy()
    import jax.numpy as jnp

    if _is_shard(leaf):
        return jnp.copy(leaf.data)  # jax Shard -> single-device array copy
    return jnp.copy(leaf)


def plan_pytree(state: Any, snapshot: bool = False) -> PytreePlan:
    """Build the streaming plan for ``state``.

    ``snapshot`` makes the plan a point-in-time checkpoint that stays valid
    while training continues: numpy leaves are host-copied, jax leaves are
    device-copied (see :func:`_snapshot_leaf`); host bytes still materialize
    leaf by leaf during streaming."""
    arrays: List[Any] = []
    skeleton = _extract_arrays(state, arrays)
    if snapshot:
        arrays = [_snapshot_leaf(a) for a in arrays]
    payload = pickle.dumps(skeleton, protocol=pickle.HIGHEST_PROTOCOL)
    header = (
        MAGIC
        + struct.pack("<I", len(payload))
        + payload
        + struct.pack("<I", len(arrays))
    )
    leaf_nbytes = []
    for leaf in arrays:
        dtype_name, shape = _leaf_meta(leaf)
        nbytes = _resolve_dtype(dtype_name).itemsize
        for d in shape:
            nbytes *= d
        leaf_nbytes.append(nbytes)
    total = len(header) + sum(8 + n for n in leaf_nbytes)
    return PytreePlan(
        header=header, leaves=arrays, leaf_nbytes=leaf_nbytes, total_len=total
    )


def save_pytree(state: Any, stream: BinaryIO) -> None:
    """Stream-serialize: the leaves come to the host as they are written,
    the next few while the last is on its way out (``PytreePlan.host_leaves``)."""
    plan = plan_pytree(state)
    plan.write_range(0, plan.total_len, stream)


def _read_exact(stream: BinaryIO, n: int) -> bytes:
    out = b""
    while len(out) < n:
        chunk = stream.read(n - len(out))
        if not chunk:
            raise EOFError("truncated checkpoint stream")
        out += chunk
    return out


def load_pytree(stream: BinaryIO, leaf_hook: Any = None) -> Any:
    """Inverse of :func:`save_pytree`, reading payloads straight into
    preallocated arrays (``readinto``, no intermediate copies).

    ``leaf_hook(arr) -> Any``, if given, maps each array right after its
    bytes arrive — e.g. ``jax.device_put`` with the healing replica's target
    sharding — so the host copy of each leaf can be dropped as soon as the
    next one starts arriving (in-place-on-arrival heal)."""
    magic = _read_exact(stream, len(MAGIC))
    if magic != MAGIC:
        raise ValueError(f"bad checkpoint magic {magic!r}")
    (skel_len,) = struct.unpack("<I", _read_exact(stream, 4))
    skeleton = pickle.loads(_read_exact(stream, skel_len))
    (narrays,) = struct.unpack("<I", _read_exact(stream, 4))

    placeholders: List[_ArrayPlaceholder] = [None] * narrays  # type: ignore[list-item]

    def _collect(obj: Any) -> None:
        if isinstance(obj, _ArrayPlaceholder):
            placeholders[obj.index] = obj
        elif isinstance(obj, _ShardedArrayPlaceholder):
            for _, ph in obj.entries:
                placeholders[ph.index] = ph
        elif isinstance(obj, dict):
            for v in obj.values():
                _collect(v)
        elif isinstance(obj, (list, tuple)):
            for v in obj:
                _collect(v)

    _collect(skeleton)

    arrays: List[np.ndarray] = []
    for i in range(narrays):
        ph = placeholders[i]
        assert ph is not None, f"missing placeholder for array {i}"
        (nbytes,) = struct.unpack("<Q", _read_exact(stream, 8))
        dtype = _resolve_dtype(ph.dtype)
        arr = np.empty(ph.shape, dtype=dtype)
        if nbytes != arr.nbytes:
            raise ValueError(
                f"array {i}: payload {nbytes} bytes != expected {arr.nbytes}"
            )
        view = as_byte_view(arr)
        read_into = stream.readinto if hasattr(stream, "readinto") else None
        off = 0
        while off < nbytes:
            if read_into is not None:
                n = read_into(view[off:])
                if not n:
                    raise EOFError("truncated checkpoint stream")
            else:
                chunk = stream.read(min(1 << 20, nbytes - off))
                if not chunk:
                    raise EOFError("truncated checkpoint stream")
                view[off : off + len(chunk)] = chunk
                n = len(chunk)
            off += n
        arrays.append(arr if leaf_hook is None else leaf_hook(arr))

    return _restore_arrays(skeleton, arrays)


def array_chunk_ranges(
    nbytes_list: List[int], target_bytes: int
) -> List[Tuple[int, int, int]]:
    """Chunk index at RAW array-payload granularity: ``(array_index, start,
    stop)`` byte ranges within each array's buffer, each at most
    ``target_bytes`` long.  Used by the comm-transport striped heal, whose
    chunks land directly in the final (preallocated) array buffers — no
    serialized-stream reassembly pass.  Deterministic given identical array
    metas, which same-step peers share by construction."""
    target = max(1, int(target_bytes))
    out: List[Tuple[int, int, int]] = []
    for ai, n in enumerate(nbytes_list):
        start = 0
        while start < n:
            stop = min(n, start + target)
            out.append((ai, start, stop))
            start = stop
    return out


def balanced_shares(sizes: List[int], num_shares: int) -> List[List[int]]:
    """Deterministic byte-balanced assignment of chunk indices to shares
    (greedy longest-first onto the least-loaded share, ties to the lowest
    index).  Plain ``idx % num_shares`` can hand one source most of the
    bytes when chunk sizes are uneven — the heal then runs at the slowest
    share's pace.  Every peer computes the SAME assignment from the same
    chunk table, which is what lets senders and the healer agree without a
    negotiation round-trip."""
    order = sorted(range(len(sizes)), key=lambda i: (-sizes[i], i))
    loads = [0] * num_shares
    shares: List[List[int]] = [[] for _ in range(num_shares)]
    for i in order:
        target = min(range(num_shares), key=lambda s: (loads[s], s))
        shares[target].append(i)
        loads[target] += sizes[i]
    return [sorted(s) for s in shares]


class ViewReader:
    """Minimal read/readinto stream over a memoryview (no BytesIO copy) —
    the zero-copy way to ``load_pytree`` an assembled striped-heal buffer."""

    def __init__(self, view: memoryview) -> None:
        self._view = view
        self._off = 0

    def read(self, n: int = -1) -> bytes:
        if n < 0:
            n = len(self._view) - self._off
        out = bytes(self._view[self._off : self._off + n])
        self._off += len(out)
        return out

    def readinto(self, out) -> int:
        n = min(len(out), len(self._view) - self._off)
        out[:n] = self._view[self._off : self._off + n]
        self._off += n
        return n


def dumps_pytree(state: Any) -> bytes:
    buf = io.BytesIO()
    save_pytree(state, buf)
    return buf.getvalue()


def loads_pytree(data: bytes) -> Any:
    return load_pytree(io.BytesIO(data))
