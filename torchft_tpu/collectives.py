"""Quantized collectives: int8/fp8 allreduce over the replica dimension.

The reference pipeline (``torchft/collectives.py:297-415``): quantize →
``alltoall`` chunks → local dequant-reduce-requant → allgather → dequant.
Per-rank bytes drop from ~2·n·4 (f32 ring) to ~2·n·1 + scales — the win that
makes DiLoCo pseudogradient syncs viable over DCN bandwidth
(``local_sgd.py`` ``should_quantize``).

Two overlap mechanisms (the analog of the reference chaining its pipeline on
a side CUDA stream, ``collectives.py:369-415``):

- the whole pipeline runs off-thread and returns a pending Work, so DiLoCo's
  τ-delay actually overlaps the sync with training;
- within the pipeline, the buffer is split into fixed-size row windows
  walked in a deterministic schedule — ``a2a(0), a2a(1), ag(0), a2a(2),
  ag(1), …`` — so while the op thread drives window ``w+1``'s alltoall and
  window ``w-1``'s allgather over the wire, the caller thread
  dequant-sum-requants window ``w``.  The schedule is identical on every
  rank (the op queue executes in submission order and frames are
  tag-checked), so windows can never cross.

The reduce step runs on device when a TPU is present (fused Pallas
dequant-sum-requant, ``ops/pallas_quant.py reduce_quantized_device`` — the
twin of the reference's ``fused_reduce_fp8``, ``quantization.py:638``): the
host round-trips int8 shards only, never float32.  Elsewhere it runs as
vectorized numpy.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, List, Optional, Tuple, Union

import numpy as np

from torchft_tpu import wire
from torchft_tpu.communicator import Communicator, CommunicatorError
from torchft_tpu.obs.spans import span as obs_span
from torchft_tpu.quantization import (
    DEFAULT_ROW_SIZE,
    FP8,
    INT8,
    dequantize_rowwise,
    quantize_rowwise,
    reduce_quantized,
    wire_dtype,
)
from torchft_tpu.wire import (
    DEVICE_QUANT_PIPELINE_TAG_BASE,
    OUTER_SHARD_TAG_BASE,
    QUANT_PIPELINE_TAG_BASE,
    QUANT_RING_TAG,
)
from torchft_tpu.work import DummyWork, Work

logger = logging.getLogger(__name__)

Buffers = Union[np.ndarray, List[np.ndarray]]

# Rows per pipeline window are sized so one window's payload is about this
# many bytes; smaller windows overlap wire and reduce at finer grain but pay
# more per-frame overhead.
WINDOW_MB_ENV = "TORCHFT_QUANT_WINDOW_MB"
DEFAULT_WINDOW_MB = 4.0

# Device-side fused reduce: "1" forces on, "0" forces off, unset/auto uses
# the TPU when present and the window is big enough to amortize transfers.
DEVICE_REDUCE_ENV = "TORCHFT_QUANT_DEVICE_REDUCE"
_DEVICE_REDUCE_MIN_BYTES = 256 << 10


def _window_rows(row_size: int) -> int:
    try:
        mb = float(os.environ.get(WINDOW_MB_ENV, "") or DEFAULT_WINDOW_MB)
    except ValueError:
        mb = DEFAULT_WINDOW_MB
    return max(1, int(mb * (1 << 20)) // row_size)


def _kind_of(q: np.ndarray) -> str:
    return INT8 if q.dtype == np.int8 else FP8


def _use_device_reduce(shard_bytes: int) -> bool:
    mode = os.environ.get(DEVICE_REDUCE_ENV, "")
    if mode == "0":
        return False
    if mode == "1":
        return True
    try:
        import jax

        return (
            jax.default_backend() == "tpu"
            and shard_bytes >= _DEVICE_REDUCE_MIN_BYTES
        )
    except Exception:  # pragma: no cover - jax is a hard dependency
        return False


# two-byte wire-format header leading every packed shard: both kinds are
# 1 byte/element with identical geometry, so a TORCHFT_QUANT_KIND mismatch
# across replicas would otherwise reinterpret peers' bytes silently —
# garbage gradients instead of an error.  header[0] is a nonzero magic so a
# headerless legacy payload (int8-quantized gradients are mostly near zero,
# making a leading 0 byte common) fails LOUDLY instead of parsing 8 bytes
# shifted; header[1] is the kind tag.
_WIRE_MAGIC = 0xA7
_KIND_TAG = {INT8: 1, FP8: 2}
_TAG_KIND = {v: k for k, v in _KIND_TAG.items()}


_HDR = 8  # 8-byte header (magic + kind + reserved) keeps the f32 scales view aligned


def _pack(q: np.ndarray, scales: np.ndarray) -> np.ndarray:
    """Header + payload + scales in one uint8 buffer so one collective
    carries all three."""
    header = np.zeros(_HDR, dtype=np.uint8)
    header[0] = _WIRE_MAGIC
    header[1] = _KIND_TAG[_kind_of(q)]
    return np.concatenate(
        [
            header,
            np.ascontiguousarray(q).reshape(-1).view(np.uint8),
            scales.view(np.uint8),
        ]
    )


def _unpack(
    buf: np.ndarray, rows: int, row_size: int, kind: str
) -> Tuple[np.ndarray, np.ndarray]:
    if int(buf[0]) != _WIRE_MAGIC:
        raise CommunicatorError(
            "quantized-wire header magic mismatch: peer payload does not "
            "start with the framed header (mixed-version replica group? "
            "all groups must run the same torchft_tpu wire build)"
        )
    got = _TAG_KIND.get(int(buf[1]))
    if got != kind:
        raise CommunicatorError(
            f"quantized-wire kind mismatch: peer sent {got!r}, this replica "
            f"is configured for {kind!r} (check TORCHFT_QUANT_KIND agrees "
            "across all replica groups)"
        )
    payload = rows * row_size
    return (
        buf[_HDR : _HDR + payload].view(wire_dtype(kind)).reshape(rows, row_size),
        buf[_HDR + payload :].view(np.float32),
    )


def _reduce_shards(
    qs: np.ndarray, scs: np.ndarray, kind: str, device: Optional[Any] = None
) -> Tuple[np.ndarray, np.ndarray]:
    """Dequant-sum-requant ``w`` shards; on a TPU both wire kinds run as
    the fused Pallas kernel so only 1-byte payloads cross HBM (fp8 falls
    back to XLA-compiled jnp on chips whose Mosaic can't lower the dtype —
    see ``pallas_quant.pallas_verdict``).  The kernel runs on ``device``:
    a caller whose data lives on a chip names that chip, so replicas
    sharing a process never pile onto one.  Only the device-quantized
    gradient path (``allreduce_prequantized``) has a chip to name; the
    callers whose input is host memory (``allreduce_quantized``,
    ``reduce_scatter_quantized``) pass None and get the process's first
    local device."""
    if _use_device_reduce(qs[0].nbytes):
        import jax

        from torchft_tpu.ops.pallas_quant import BLOCK_ROWS, reduce_quantized_device

        w, rows, row_size = qs.shape
        pad = (-rows) % BLOCK_ROWS
        if pad:
            qs = np.concatenate(
                [qs, np.zeros((w, pad, row_size), qs.dtype)], axis=1
            )
            scs = np.concatenate([scs, np.zeros((w, pad), np.float32)], axis=1)
        device = device or jax.local_devices()[0]
        q_dev, s_dev = reduce_quantized_device(
            jax.device_put(qs, device),
            jax.device_put(scs[:, :, None], device),
            kind=kind,
        )
        q_host = np.asarray(q_dev)[:rows]
        s_host = np.asarray(s_dev).reshape(-1)[:rows]
        return q_host, s_host
    return reduce_quantized(qs, scs, kind)


# ---------------------------------------------------------------------------
# single-window core (shared with reduce_scatter and kept as the fallback)
# ---------------------------------------------------------------------------


def _quantized_reduce_scatter_sync(
    comm: Communicator, flat: np.ndarray, row_size: int, tag: int, kind: str = INT8
) -> Tuple[np.ndarray, np.ndarray, int, int]:
    """Core shared by both quantized collectives: quantize, pad rows to an
    equal per-rank share, alltoall, dequant-sum-requant our shard.

    Returns (reduced q shard, its scales, total unpadded rows, rows/rank).
    """
    q, scales = quantize_rowwise(flat, row_size, kind)
    return _prequantized_reduce_scatter_sync(comm, q, scales, tag)


def _prequantized_reduce_scatter_sync(
    comm: Communicator, q: np.ndarray, scales: np.ndarray, tag: int
) -> Tuple[np.ndarray, np.ndarray, int, int]:
    """Same core for input already quantized (e.g. on-device by the Pallas
    kernel, so only 1-byte payload + scales ever crossed HBM→host)."""
    kind = _kind_of(q)
    ws = comm.size()
    row_size = q.shape[1]
    rows = q.shape[0]
    rows_per_rank = -(-rows // ws)
    padded_rows = rows_per_rank * ws
    if padded_rows != rows:
        q = np.concatenate(
            [q, np.zeros((padded_rows - rows, row_size), q.dtype)]
        )
        scales = np.concatenate(
            [scales, np.zeros(padded_rows - rows, np.float32)]
        )

    chunks = [
        _pack(
            q[p * rows_per_rank : (p + 1) * rows_per_rank],
            scales[p * rows_per_rank : (p + 1) * rows_per_rank],
        )
        for p in range(ws)
    ]
    gathered = comm.alltoall(chunks, tag=tag).wait()

    qs, scs = zip(*(_unpack(g, rows_per_rank, row_size, kind) for g in gathered))
    q_red, s_red = _reduce_shards(np.stack(qs), np.stack(scs), kind)
    return q_red, s_red, rows, rows_per_rank


def _allgather_reduced_shards(
    comm: Communicator,
    q_red: np.ndarray,
    s_red: np.ndarray,
    rows: int,
    rows_per_rank: int,
    row_size: int,
    n: int,
    tag: int,
    pipeline_err: Optional[BaseException],
    kind: str = INT8,
) -> np.ndarray:
    """Shared tail of the single-window allreduce: allgather the reduced
    shards and dequantize.  Always participates in the allgather — even
    after an upstream failure (``pipeline_err``), a zero shard is
    contributed so healthy peers are never wedged — then re-raises."""
    all_shards = comm.allgather(_pack(q_red, s_red), tag=tag).wait()
    if pipeline_err is not None:
        raise pipeline_err
    qs_full, ss_full = zip(
        *(_unpack(s, rows_per_rank, row_size, kind) for s in all_shards)
    )
    q_full = np.concatenate(qs_full)[:rows]
    s_full = np.concatenate(ss_full)[:rows]
    return dequantize_rowwise(q_full, s_full, n, np.float32)


def _zero_shard(
    rows: int, row_size: int, ws: int, kind: str = INT8
) -> Tuple[np.ndarray, np.ndarray, int, int]:
    """Zero contribution with the shard geometry peers expect (``rows`` must
    equal the unpadded row count every rank derived from its own input)."""
    rows_per_rank = -(-rows // ws)
    return (
        np.zeros((rows_per_rank, row_size), wire_dtype(kind)),
        np.zeros(rows_per_rank, np.float32),
        rows,
        rows_per_rank,
    )


# ---------------------------------------------------------------------------
# windowed pipelined allreduce
# ---------------------------------------------------------------------------


def _allreduce_pipelined_sync(
    comm: Communicator,
    q: np.ndarray,
    scales: np.ndarray,
    n: int,
    tag_base: int,
    device: Optional[Any] = None,
) -> np.ndarray:
    """SUM-allreduce of quantized rows with window-level overlap.

    Deterministic per-rank schedule (identical everywhere, so the single op
    thread pairs frames correctly):

        submit a2a(0)
        for w: wait a2a(w); submit a2a(w+1); reduce(w); submit ag(w)
        for w: wait ag(w); dequantize into the output

    While the caller reduces window ``w``, the op thread drives ``a2a(w+1)``
    then ``ag(w-1)`` over the sockets.  Any stage failure degrades that
    window (and the rest of the schedule, if the communicator died) to zero
    shards so peers never wedge, then the first error re-raises at the end —
    same containment contract as the single-window path.
    """
    kind = _kind_of(q)
    ws = comm.size()
    rows, row_size = q.shape
    win = _window_rows(row_size)
    windows: List[Tuple[int, int]] = [
        (start, min(start + win, rows)) for start in range(0, rows, win)
    ]
    W = len(windows)
    # window tags are allocated 2 per window from tag_base; past the span
    # declared in wire.USER_TAG_ALLOCATIONS they spill into neighboring
    # allocations (pairing stays unambiguous today only because ops are
    # serialized per epoch and a2a/ag tags differ in parity — see the
    # registry comment).  Warn loudly so giant payloads get a bigger
    # TORCHFT_QUANT_WINDOW_MB instead of relying on that accident.
    span = next(
        (
            s
            for b, s in wire.USER_TAG_ALLOCATIONS.values()
            if b == tag_base
        ),
        None,
    )
    if span is not None and 2 * W > span:
        logger.warning(
            "quantized pipeline needs %d windows (%d tags) but tag base %d "
            "has a span of only %d — raise TORCHFT_QUANT_WINDOW_MB to "
            "shrink the window count",
            W,
            2 * W,
            tag_base,
            span,
        )
    err: Optional[BaseException] = None
    out = np.empty(rows * row_size, dtype=np.float32)

    # one padded staging scratch (q rows + their scales), sized for the
    # largest window and reused across windows — the previous per-window
    # np.concatenate allocated fresh padding buffers every window.  Reuse is
    # safe while earlier windows' collectives are still in flight because
    # ``_pack`` copies the rows into the wire buffer before submission.
    max_padded = max(
        (-(-(stop - start) // ws) * ws for start, stop in windows), default=0
    )
    pad_q: Optional[np.ndarray] = None
    pad_s: Optional[np.ndarray] = None

    def _submit_a2a(w: int) -> Work:
        nonlocal pad_q, pad_s
        start, stop = windows[w]
        wq, wsc = q[start:stop], scales[start:stop]
        wrows = stop - start
        rows_per_rank = -(-wrows // ws)
        padded = rows_per_rank * ws
        if padded != wrows:
            if pad_q is None:
                pad_q = np.empty((max_padded, row_size), q.dtype)
                pad_s = np.empty(max_padded, np.float32)
            pad_q[:wrows] = wq
            pad_q[wrows:padded] = 0
            pad_s[:wrows] = wsc
            pad_s[wrows:padded] = 0.0
            wq, wsc = pad_q[:padded], pad_s[:padded]
        chunks = [
            _pack(
                wq[p * rows_per_rank : (p + 1) * rows_per_rank],
                wsc[p * rows_per_rank : (p + 1) * rows_per_rank],
            )
            for p in range(ws)
        ]
        return comm.alltoall(chunks, tag=tag_base + 2 * w)

    def _rows_per_rank(w: int) -> int:
        start, stop = windows[w]
        return -(-(stop - start) // ws)

    a2a_work = _submit_a2a(0)
    ag_works: List[Work] = []
    for w in range(W):
        rows_per_rank = _rows_per_rank(w)
        try:
            gathered = a2a_work.wait()
        except BaseException as e:  # noqa: BLE001 — degrade, keep schedule
            err = err or e
            gathered = None
        if w + 1 < W:
            a2a_work = _submit_a2a(w + 1)
        if gathered is not None:
            try:
                qs, scs = zip(
                    *(
                        _unpack(g, rows_per_rank, row_size, kind)
                        for g in gathered
                    )
                )
                q_red, s_red = _reduce_shards(
                    np.stack(qs), np.stack(scs), kind, device
                )
            except BaseException as e:  # noqa: BLE001
                err = err or e
                gathered = None
        if gathered is None:
            q_red = np.zeros((rows_per_rank, row_size), wire_dtype(kind))
            s_red = np.zeros(rows_per_rank, np.float32)
        ag_works.append(
            comm.allgather(_pack(q_red, s_red), tag=tag_base + 2 * w + 1)
        )

    for w, work in enumerate(ag_works):
        start, stop = windows[w]
        rows_per_rank = _rows_per_rank(w)
        try:
            all_shards = work.wait()
            qs_full, ss_full = zip(
                *(
                    _unpack(s, rows_per_rank, row_size, kind)
                    for s in all_shards
                )
            )
            q_full = np.concatenate(qs_full)[: stop - start]
            s_full = np.concatenate(ss_full)[: stop - start]
            out[start * row_size : stop * row_size] = dequantize_rowwise(
                q_full, s_full, (stop - start) * row_size, np.float32
            )
        except BaseException as e:  # noqa: BLE001
            err = err or e
            out[start * row_size : stop * row_size] = 0.0

    if err is not None:
        raise err
    return out[:n]


# ---------------------------------------------------------------------------
# sharded outer sync: chunk-pipelined reduce_scatter → update → allgather
# ---------------------------------------------------------------------------

# Bytes of the FULL flat buffer covered by one pipeline chunk (each chunk's
# per-shard slice is this divided by the shard count).  Smaller chunks start
# the outer update sooner and overlap at finer grain; larger chunks amortize
# the per-exchange RTT gates — on wan_1g-class links (10 ms RTT) chunks
# below ~8 MB cost more in frame gates than the overlap buys back.
OUTER_CHUNK_MB_ENV = "TORCHFT_OUTER_CHUNK_MB"
DEFAULT_OUTER_CHUNK_MB = 16.0
# Pipeline depth cap: tags are allocated 2 per chunk from the sharded-sync
# tag base, and a deeper pipeline stops paying for itself anyway.
_MAX_OUTER_CHUNKS = 64
_OUTER_TAG_BASE = OUTER_SHARD_TAG_BASE


def _outer_chunk_ranges(
    per: int, unit: int, gsize: int, max_chunks: int = _MAX_OUTER_CHUNKS
) -> List[Tuple[int, int]]:
    """Pipeline chunk ranges WITHIN one shard's [0, per) element extent,
    unit-aligned so quantization rows never split; identical on every
    replica (pure function of the layout).  ``max_chunks`` bounds the
    pipeline depth to the caller's tag window (2 tags per chunk)."""
    try:
        mb = float(
            os.environ.get(OUTER_CHUNK_MB_ENV, "") or DEFAULT_OUTER_CHUNK_MB
        )
    except ValueError:
        mb = DEFAULT_OUTER_CHUNK_MB
    # per-shard slice of one chunk, in elements (f32), unit-aligned
    want = int(mb * (1 << 20)) // 4 // max(1, gsize)
    want = max(unit, want // unit * unit)
    floor = -(-per // (max_chunks * unit)) * unit  # cap chunk count
    step = max(want, floor, unit)
    return [(c, min(c + step, per)) for c in range(0, per, step)]


def outer_shard_layout(
    n: int, gsize: int, should_quantize: bool, row_size: int = DEFAULT_ROW_SIZE
) -> Tuple[int, int, int]:
    """Per-replica shard layout of a flat ``n``-element f32 buffer over
    ``gsize`` shard owners: returns ``(padded, per, unit)`` elements where
    every shard is exactly ``per`` elements, ``padded = per * gsize``, and
    boundaries are ``unit``-aligned (16 f32 = 64 B raw; one quantization
    row when the wire is quantized, so each byte is quantized exactly once
    and no row straddles shards).  Thin wrapper over the wire-level
    :func:`communicator.outer_shard_parts` (mirrored in ``native/comm.h``)
    so shard ownership stays tier-uniform."""
    from torchft_tpu.communicator import outer_shard_parts

    unit = row_size if should_quantize else 16
    parts = outer_shard_parts(n * 4, gsize, unit * 4)
    per = (parts[0][1] - parts[0][0]) // 4
    return per * gsize, per, unit


def outer_sharded_sync(
    comm: Communicator,
    flat: np.ndarray,
    update_cb: Callable[[int, int, np.ndarray], np.ndarray],
    num_participants: int,
    should_quantize: bool = False,
    kind: str = INT8,
    row_size: int = DEFAULT_ROW_SIZE,
    timings: Optional[dict] = None,
    tap: Optional[Callable[[np.ndarray], None]] = None,
    weight: Optional[float] = None,
    tag_base: int = _OUTER_TAG_BASE,
    tag_span: int = wire.OUTER_SHARD_TAG_SPAN,
) -> np.ndarray:
    """ZeRO-1-style sharded outer sync: chunk-pipelined
    ``reduce_scatter → sharded outer update → allgather(update)``.

    ``flat`` is this replica's f32 pseudo-gradient (length n).  The buffer
    is split into deterministic per-owner shards (:func:`outer_shard_layout`)
    and each shard into pipeline chunks; per chunk the schedule is

        alltoall(pseudo-grad slices)         # the reduce-scatter
        avg = Σ contributions / participants
        delta = update_cb(lo, hi, avg)       # the sharded outer step
        allgather(delta)                     # owners' updates, fanned out

    with chunk ``c+1``'s alltoall submitted before chunk ``c``'s update
    runs, so the outer optimizer computes while later chunks are still
    reducing on the op thread — the ``reduce_scatter_then`` hook.  Every
    replica applies the identical wire-format delta (its own included), so
    params stay bit-identical across replicas.

    Hierarchical topologies compose: the host reduces once over shared
    memory, HOST LEADERS run the chunk pipeline (shards owned per host via
    ``leader_comm``), and the allgathered delta shm-broadcasts back out —
    non-leaders move zero socket bytes and own no shard (``update_cb`` is
    never invoked on them).

    When quantized, the pseudo-gradient is rowwise-quantized ONCE for the
    whole buffer (each byte quantized exactly once — shard and chunk
    boundaries are row-aligned) and the delta rides the wire as one more
    rowwise pass; error containment matches the pipelined allreduce: a
    failed chunk degrades to a zero delta so peers never wedge, then the
    first error re-raises after the schedule completes.

    Returns the f32 delta of length ``len(flat)`` (apply as
    ``params = backup + delta``).  Fills ``timings`` (if given) with
    ``scatter_s`` / ``update_s`` / ``gather_s`` / ``wall_s`` /
    ``overlap_ratio``.

    ``tap``, if given, observes the assembled delta (identical bytes on
    every replica by construction — the allgather fans out ONE wire-format
    update) right before it is returned: the hot-spare delta feed rides
    this hook so parked observers can keep a shadow bit-exact without
    participating in the collective.  A tap failure never fails the sync.

    ``tag_base`` / ``tag_span`` frame the chunk collectives: the default is
    the legacy OUTER_SHARD window (byte-identical to the pre-stream path);
    the streamed fragment scheduler passes a rotating per-fragment
    STREAM_OUTER window (``wire.stream_frag_tag_window``) so consecutive
    streamed syncs can never alias tags.  The pipeline depth is capped at
    ``tag_span // 2`` chunks (2 tags per chunk).

    ``weight``, if given, turns the sync into a capacity-WEIGHTED sum
    (degraded-mode fleets): this replica's contribution is pre-scaled by
    its normalized capacity share before quantization/transport and the
    ``num_participants`` division drops out (weights sum to 1 across the
    fleet by construction — every rank must pass a weight, or none).  The
    delta stays bit-identical across replicas exactly as before: the
    weighting changes the bytes each rank CONTRIBUTES, never how the
    summed wire-format delta is applied.
    """
    t_wall = time.perf_counter()
    if weight is not None:
        flat = np.asarray(flat, dtype=np.float32) * np.float32(weight)
        num_participants = 1  # weighted contributions need no division
    n = flat.size
    tm = {"scatter_s": 0.0, "update_s": 0.0, "gather_s": 0.0}
    topo = _hier_topology(comm)
    err: Optional[BaseException] = None
    delta_full: Optional[np.ndarray] = None

    if topo is None:
        gsize = max(1, comm.size())
        group: Communicator = comm
        contrib: Optional[np.ndarray] = np.asarray(flat, dtype=np.float32)
        owns = True
    else:
        # intra-host reduce once; leaders shard the outer step per host
        gsize = len(topo["leader_ring"])
        owns = bool(topo["is_leader"])
        contrib = None
        try:
            contrib = comm.intra_reduce(  # type: ignore[attr-defined]
                np.asarray(flat, dtype=np.float32)
            ).wait()
        except BaseException as e:  # noqa: BLE001 — degrade, keep schedule
            err = e
        group = comm.leader_comm() if owns else comm  # type: ignore[attr-defined]

    padded, per, unit = outer_shard_layout(n, gsize, should_quantize, row_size)

    if owns:
        try:
            if contrib is None:
                raise err or CommunicatorError("intra-host reduce failed")
            with obs_span("tpuft/outer_shard/pipeline"):
                delta_full = _outer_sharded_pipeline(
                    group,
                    contrib,
                    padded,
                    per,
                    unit,
                    update_cb,
                    num_participants,
                    should_quantize,
                    kind,
                    row_size,
                    tm,
                    tag_base=tag_base,
                    tag_span=tag_span,
                )
        except BaseException as e:  # noqa: BLE001
            err = err or e
            delta_full = np.zeros(padded, dtype=np.float32)

    if topo is not None:
        # members receive the delta; leaders always broadcast (zeros after a
        # failure) so host peers are never wedged — same containment
        # contract as the hierarchical quantized allreduce
        delta_full = comm.intra_broadcast(  # type: ignore[attr-defined]
            delta_full, padded, np.float32
        ).wait()
    if err is not None:
        raise err
    assert delta_full is not None
    tm["wall_s"] = time.perf_counter() - t_wall
    busy = tm["scatter_s"] + tm["update_s"] + tm["gather_s"]
    tm["overlap_ratio"] = round(busy / tm["wall_s"], 4) if tm["wall_s"] > 0 else 0.0
    if timings is not None:
        timings.update({k: round(v, 6) for k, v in tm.items()})
    if tap is not None:
        try:
            tap(delta_full[:n])
        except Exception:  # noqa: BLE001 — observers must not fail the sync
            pass
    return delta_full[:n]


def _outer_sharded_pipeline(
    group: Communicator,
    contrib: np.ndarray,
    padded: int,
    per: int,
    unit: int,
    update_cb: Callable[[int, int, np.ndarray], np.ndarray],
    num_participants: int,
    should_quantize: bool,
    kind: str,
    row_size: int,
    tm: dict,
    tag_base: int = _OUTER_TAG_BASE,
    tag_span: int = wire.OUTER_SHARD_TAG_SPAN,
) -> np.ndarray:
    """Shard-owner body of :func:`outer_sharded_sync` over ``group`` (the
    flat communicator, or the leader view on hierarchical topologies)."""
    gsize = max(1, group.size())
    gidx = group.rank() if gsize > 1 else 0
    buf = np.zeros(padded, dtype=np.float32)
    buf[: contrib.size] = contrib
    chunks = _outer_chunk_ranges(per, unit, gsize, max_chunks=tag_span // 2)
    inv = 1.0 / max(1, num_participants)
    delta_full = np.empty(padded, dtype=np.float32)
    err: Optional[BaseException] = None

    q_full: Optional[np.ndarray] = None
    s_full: Optional[np.ndarray] = None
    if should_quantize:
        # quantize the whole contribution ONCE; every a2a slice below is a
        # row-aligned view of this single pass
        q_full, s_full = quantize_rowwise(buf, row_size, kind)

    if gsize == 1 or getattr(group, "is_passthrough", False):
        # degenerate single-owner group: no wire, but keep the per-chunk
        # schedule (and, when quantized, the wire-format round trip) so the
        # numerics match the multi-owner path's contract
        for c0, c1 in chunks:
            if should_quantize:
                assert q_full is not None and s_full is not None
                rows = slice(c0 // row_size, c1 // row_size)
                avg = dequantize_rowwise(
                    q_full[rows], s_full[rows], c1 - c0, np.float32
                )
                avg *= inv
            else:
                avg = buf[c0:c1] * inv
            t0 = time.perf_counter()
            delta = np.asarray(update_cb(c0, c1, avg), dtype=np.float32)
            tm["update_s"] += time.perf_counter() - t0
            if should_quantize:
                dq, ds = quantize_rowwise(delta, row_size, kind)
                delta = dequantize_rowwise(dq, ds, c1 - c0, np.float32)
            delta_full[c0:c1] = delta
        return delta_full

    my_base = gidx * per

    def _submit_a2a(ci: int) -> Work:
        c0, c1 = chunks[ci]
        if should_quantize:
            assert q_full is not None and s_full is not None
            parts = [
                _pack(
                    q_full[(p * per + c0) // row_size : (p * per + c1) // row_size],
                    s_full[(p * per + c0) // row_size : (p * per + c1) // row_size],
                )
                for p in range(gsize)
            ]
        else:
            parts = [buf[p * per + c0 : p * per + c1] for p in range(gsize)]
        return group.alltoall(parts, tag=tag_base + 2 * ci)

    a2a_work = _submit_a2a(0)
    ag_works: List[Work] = []
    for ci, (c0, c1) in enumerate(chunks):
        rows = (c1 - c0) // row_size
        t0 = time.perf_counter()
        try:
            gathered = a2a_work.wait()
        except BaseException as e:  # noqa: BLE001 — degrade, keep schedule
            err = err or e
            gathered = None
        tm["scatter_s"] += time.perf_counter() - t0
        if ci + 1 < len(chunks):
            a2a_work = _submit_a2a(ci + 1)
        delta: Optional[np.ndarray] = None
        if gathered is not None:
            try:
                if should_quantize:
                    qs, scs = zip(
                        *(_unpack(g, rows, row_size, kind) for g in gathered)
                    )
                    acc = np.einsum(
                        "wrc,wr->rc",
                        np.stack(qs).astype(np.float32),
                        np.stack(scs),
                    ).reshape(-1)
                else:
                    acc = np.sum(np.stack(gathered), axis=0)
                acc *= inv
                t0 = time.perf_counter()
                with obs_span("tpuft/outer_shard/chunk_update", chunk=ci):
                    delta = np.asarray(
                        update_cb(my_base + c0, my_base + c1, acc),
                        dtype=np.float32,
                    )
                tm["update_s"] += time.perf_counter() - t0
            except BaseException as e:  # noqa: BLE001
                err = err or e
                delta = None
        if delta is None:
            delta = np.zeros(c1 - c0, dtype=np.float32)
        if should_quantize:
            dq, ds = quantize_rowwise(delta, row_size, kind)
            ag_works.append(
                group.allgather(_pack(dq, ds), tag=tag_base + 2 * ci + 1)
            )
        else:
            ag_works.append(
                group.allgather(delta, tag=tag_base + 2 * ci + 1)
            )

    for ci, work in enumerate(ag_works):
        c0, c1 = chunks[ci]
        rows = (c1 - c0) // row_size
        t0 = time.perf_counter()
        try:
            all_deltas = work.wait()
        except BaseException as e:  # noqa: BLE001
            err = err or e
            all_deltas = None
        tm["gather_s"] += time.perf_counter() - t0
        for p in range(gsize):
            dst = delta_full[p * per + c0 : p * per + c1]
            if all_deltas is None:
                dst[:] = 0.0
            elif should_quantize:
                # every replica (the owner included) applies the WIRE
                # delta, so params stay bit-identical across replicas
                try:
                    dq, ds = _unpack(all_deltas[p], rows, row_size, kind)
                    dst[:] = dequantize_rowwise(dq, ds, c1 - c0, np.float32)
                except BaseException as e:  # noqa: BLE001
                    err = err or e
                    dst[:] = 0.0
            else:
                dst[:] = all_deltas[p]

    if err is not None:
        raise err
    return delta_full


def _hier_topology(comm: Communicator) -> Optional[dict]:
    """The epoch's ACTIVE hierarchical topology (uniform across ranks), or
    None for flat tiers/epochs."""
    fn = getattr(comm, "hier_topology", None)
    return fn() if callable(fn) else None


def _hier_allreduce_quantized_sync(
    comm: Communicator,
    topo: dict,
    flat: np.ndarray,
    row_size: int,
    kind: str,
    tag_base: int,
    device: Optional[Any] = None,
) -> np.ndarray:
    """Topology-aware quantized SUM-allreduce: reduce float32 once per host
    over shared memory, quantize ONCE PER HOST, run the windowed pipeline
    only among host leaders, shm-broadcast the dequantized sum back out.
    Int8 wire bytes drop by the local-group factor on top of the 4x from
    quantization, and non-leaders never touch the DCN.

    Numerics differ from the flat pipeline (host contributions are summed
    in f32 BEFORE quantization — strictly less quantization error), so the
    contract vs the true sum is the same quantized tolerance, not
    bit-equality with the flat path."""
    # any stage failure degrades toward zeros but KEEPS the shm schedule —
    # skipping the broadcast would leave host peers spinning until their
    # deadline (the underlying shm ops run on the op thread even when a
    # wrapper fails only the returned future), then re-raises so the step
    # is voted down; same containment contract as the flat pipeline
    err: Optional[BaseException] = None
    host_sum: Optional[np.ndarray] = None
    try:
        host_sum = comm.intra_reduce(flat).wait()  # type: ignore[attr-defined]
    except BaseException as e:  # noqa: BLE001
        err = e
    out: Optional[np.ndarray] = None
    if topo["is_leader"]:
        try:
            if host_sum is None:
                raise err or CommunicatorError("intra-host reduce failed")
            q, scales = quantize_rowwise(host_sum, row_size, kind)
            lead = comm.leader_comm()  # type: ignore[attr-defined]
            if lead.size() > 1:
                out = _allreduce_pipelined_sync(
                    lead, q, scales, flat.size, tag_base=tag_base, device=device
                )
            else:
                # single host: the wire round-trip degenerates but the
                # quantization error stays observable, like ws==1 flat
                out = dequantize_rowwise(q, scales, flat.size, np.float32)
        except BaseException as e:  # noqa: BLE001
            err = err or e
            out = np.zeros(flat.size, dtype=np.float32)
    summed = comm.intra_broadcast(  # type: ignore[attr-defined]
        out, flat.size, np.float32
    ).wait()
    if err is not None:
        raise err
    return summed


def _allreduce_quantized_sync(
    comm: Communicator, arrays: List[np.ndarray], row_size: int, kind: str = INT8
) -> List[np.ndarray]:
    layout = [(a.shape, a.dtype, a.size) for a in arrays]
    flat = np.concatenate(
        [np.asarray(a, dtype=np.float32).reshape(-1) for a in arrays]
    )
    topo = _hier_topology(comm)
    if topo is not None:
        summed = _hier_allreduce_quantized_sync(
            comm, topo, flat, row_size, kind, tag_base=QUANT_PIPELINE_TAG_BASE
        )
    else:
        q, scales = quantize_rowwise(flat, row_size, kind)
        summed = _allreduce_pipelined_sync(
            comm, q, scales, flat.size, tag_base=QUANT_PIPELINE_TAG_BASE
        )

    out: List[np.ndarray] = []
    off = 0
    for shape, dtype, size in layout:
        out.append(
            summed[off : off + size].reshape(shape).astype(dtype, copy=False)
        )
        off += size
    return out


def allreduce_prequantized(
    comm: Communicator,
    q: np.ndarray,
    scales: np.ndarray,
    n: int,
    device: Optional[Any] = None,
) -> np.ndarray:
    """SUM-allreduce of an already-quantized stream (1-byte rows + f32
    rowwise scales, e.g. produced on device by ``ops.pallas_quant``);
    returns the dequantized float32 sum of length ``n``.  ``device`` is the
    chip the stream was quantized on: the windowed reduce kernels run there
    (see :func:`_reduce_shards`).  Synchronous — callers layer
    Work/threading on top (``Manager.allreduce_prequantized``)."""
    scales = np.asarray(scales).reshape(-1)
    if comm.size() == 1 or getattr(comm, "is_passthrough", False):
        return dequantize_rowwise(q, scales, n, np.float32)
    topo = _hier_topology(comm)
    if topo is not None:
        # prequantized input on a hierarchical topology: dequantize locally
        # (host-side f32, the shm hop is cheap) and take the once-per-host
        # requantize path — leaders alone quantize for the DCN
        flat = dequantize_rowwise(q, scales, n, np.float32)
        return _hier_allreduce_quantized_sync(
            comm, topo, flat, q.shape[1], _kind_of(q),
            tag_base=DEVICE_QUANT_PIPELINE_TAG_BASE, device=device,
        )
    return _allreduce_pipelined_sync(
        comm, q, scales, n, tag_base=DEVICE_QUANT_PIPELINE_TAG_BASE,
        device=device,
    )


def allreduce_quantized(
    comm: Communicator,
    buffers: Buffers,
    row_size: int = DEFAULT_ROW_SIZE,
    kind: str = INT8,
) -> Work:
    """SUM-allreduce through a 1-byte wire format (int8 default, fp8
    optional): the Work's value mirrors ``buffers`` with summed float values
    (the Manager divides by participants afterwards, exactly like the
    unquantized path).

    Accuracy: rowwise int8 carries ~2-3 decimal digits; intended for DiLoCo
    pseudogradients where the outer optimizer tolerates it (the reference
    ships fp8 with the same caveat — pass ``kind="fp8"`` for that format).
    """
    single = isinstance(buffers, np.ndarray)
    arrays: List[np.ndarray] = [buffers] if single else list(buffers)

    if comm.size() == 1 or getattr(comm, "is_passthrough", False):
        # single member (or a passthrough test double): the sum is our own
        # contribution; round-trip through the wire format so quantization
        # error stays observable in tests
        out = []
        for a in arrays:
            flat = np.asarray(a, dtype=np.float32).reshape(-1)
            q, s = quantize_rowwise(flat, row_size, kind)
            out.append(
                dequantize_rowwise(q, s, flat.size, np.float32)
                .reshape(a.shape)
                .astype(a.dtype, copy=False)
            )
        return DummyWork(out[0] if single else out)

    fut: Future = Future()

    def _run() -> None:
        try:
            out = _allreduce_quantized_sync(comm, arrays, row_size, kind)
            fut.set_result(out[0] if single else out)
        except BaseException as e:  # noqa: BLE001
            fut.set_exception(e)

    threading.Thread(
        target=_run, name="tpuft_quantized_allreduce", daemon=True
    ).start()
    return Work(fut)


def reduce_scatter_quantized(
    comm: Communicator,
    buffers: Buffers,
    row_size: int = DEFAULT_ROW_SIZE,
    kind: str = INT8,
) -> Work:
    """Quantized reduce-scatter (``collectives.py:159-294``): each rank gets
    the dequantized sum of its row-shard only (flat float32)."""
    single = isinstance(buffers, np.ndarray)
    arrays: List[np.ndarray] = [buffers] if single else list(buffers)
    flat = np.concatenate(
        [np.asarray(a, dtype=np.float32).reshape(-1) for a in arrays]
    )
    if comm.size() == 1 or getattr(comm, "is_passthrough", False):
        q, s = quantize_rowwise(flat, row_size, kind)
        return DummyWork(dequantize_rowwise(q, s, flat.size, np.float32))

    fut: Future = Future()

    def _run() -> None:
        try:
            topo = _hier_topology(comm)
            if topo is not None:
                # hierarchical: once-per-host quantized allreduce, then
                # requantize the full sum and slice this rank's row-shard —
                # same shard geometry as the flat alltoall path
                summed = _hier_allreduce_quantized_sync(
                    comm, topo, flat, row_size, kind, tag_base=QUANT_RING_TAG
                )
                q_full, s_full = quantize_rowwise(summed, row_size, kind)
                ws = comm.size()
                rows_per_rank = -(-q_full.shape[0] // ws)
                r = comm.rank()
                q_red = np.zeros((rows_per_rank, row_size), wire_dtype(kind))
                s_red = np.zeros(rows_per_rank, np.float32)
                shard = q_full[r * rows_per_rank : (r + 1) * rows_per_rank]
                q_red[: shard.shape[0]] = shard
                s_red[: shard.shape[0]] = s_full[
                    r * rows_per_rank : r * rows_per_rank + shard.shape[0]
                ]
            else:
                q_red, s_red, _rows, rows_per_rank = (
                    _quantized_reduce_scatter_sync(
                        comm, flat, row_size, tag=QUANT_RING_TAG, kind=kind
                    )
                )
            total = (q_red.astype(np.float32) * s_red[:, None]).reshape(-1)
            fut.set_result(total)
        except BaseException as e:  # noqa: BLE001
            fut.set_exception(e)

    threading.Thread(
        target=_run, name="tpuft_quantized_reduce_scatter", daemon=True
    ).start()
    return Work(fut)
