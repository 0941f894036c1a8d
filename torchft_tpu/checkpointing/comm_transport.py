"""Checkpoint transport over the data-plane communicator.

Twin of the reference's PGTransport (``torchft/checkpointing/pg_transport.py``):
instead of a side HTTP channel, healing weights ride the same communicator
fabric as gradients — useful when DCN bandwidth between specific peers is
provisioned for the collective fabric, and required parity for deployments
that disallow extra listening ports.

Protocol per (src → dst) pair, tags offset into a dedicated range:

1. one framed metadata blob: pickled skeleton + per-array dtype/shape
   (the reference ships a pickled ``_StateDictMeta`` first, tags 1/2)
2. one framed raw-byte payload per array (tags 3+i there; base+1+i here)

``recv_checkpoint`` can optionally receive **in place** into the numpy
buffers of an existing state dict (``pg_transport.py:235-305``), avoiding
allocation for large models.
"""

from __future__ import annotations

import logging
import pickle
import threading
import time
from typing import Dict, List, Optional, Tuple, TypeVar

import numpy as np

from torchft_tpu.checkpointing.serialization import (
    _extract_arrays,
    _leaf_meta,
    _restore_arrays,
    _resolve_dtype,
    array_chunk_ranges,
    as_byte_view,
    balanced_shares,
    heal_chunk_bytes,
    materialize_leaf,
)
from torchft_tpu import wire
from torchft_tpu.checkpointing.transport import CheckpointTransport
from torchft_tpu.communicator import Communicator
from torchft_tpu.observability import HealMetrics

logger = logging.getLogger(__name__)

T = TypeVar("T")

# tag namespace distinct from collectives (1000s/2000s), broadcast (3000s),
# alltoall (4000s), allgather (5000s) — allocated centrally in wire.py
_TAG_BASE = wire.HEAL_TAG_BASE

# Striped-heal tag offsets inside one step's 10M-wide tag range.  Distinct
# from the legacy per-array tags (base + 1 + i) so a striped healer paired
# with a legacy sender fails loudly on a tag mismatch instead of
# misreading frames.
_S_META_OFF = 7_000_000  # src → dst: pickled chunk index
_S_CHUNK_OFF = 7_000_001  # src → dst: + chunk_idx, raw chunk bytes
_S_CTRL_OFF = 8_000_000  # dst → src: pickled ("need", [idx...]) / ("done",)


class CommTransport(CheckpointTransport[T]):
    """Checkpoint transport over ``Communicator.send_bytes/recv_bytes``.

    The communicator must be the manager's (re)configured one — send/recv
    pair up between the quorum's replica ranks exactly like the reference's
    PG send/recv.  Per-step tag salting keeps a late transfer from a
    previous heal from pairing with a new one.
    """

    def __init__(self, comm: Communicator, timeout: float = 60.0) -> None:
        self._comm = comm
        self._timeout = timeout
        # striped-heal bookkeeping (see HTTPTransport for the same surface):
        # metrics of the most recent striped recv, and a chaos threshold
        # (``chaos.arm_heal_source_kill``) that makes this source abort its
        # communicator after serving ~N bytes of a striped heal
        self.last_heal_metrics: Optional[HealMetrics] = None
        self.chaos_die_after_bytes: Optional[int] = None
        self.chaos_arm: Optional[threading.Event] = None
        self.chaos_fired = threading.Event()

    def metadata(self) -> str:
        return "<comm>"

    @staticmethod
    def _tags(step: int) -> int:
        # wide per-step strides: even million-leaf state dicts can't bleed
        # into the next step's tag range.  Salted by the FULL step (tags are
        # uint64 on both tiers) so a transfer stale by any number of steps
        # can never alias a newer one.
        return _TAG_BASE * 1000 + step * wire.HEAL_STEP_TAG_STRIDE

    # submission window: at most this many leaves' host copies are alive at
    # once while streaming a heal (the sends pipeline; the window caps RSS)
    _SEND_WINDOW_LEAVES = 4

    def send_checkpoint(
        self, dst_ranks: List[int], step: int, state_dict: T, timeout: float
    ) -> None:
        import time as _time

        arrays: List[object] = []
        skeleton = _extract_arrays(state_dict, arrays)
        meta = pickle.dumps(
            (skeleton, [_leaf_meta(a) for a in arrays]),
            protocol=pickle.HIGHEST_PROTOCOL,
        )
        base = self._tags(step)
        deadline = _time.monotonic() + timeout
        # leaves materialize to host lazily, one at a time, and are sent
        # zero-copy from their buffer; a bounded window of in-flight sends
        # overlaps D2H of leaf k+1 with the wire of leaf k while capping
        # peak extra host RSS at ~_SEND_WINDOW_LEAVES leaves
        works: List[tuple] = []
        for dst in dst_ranks:
            works.append((self._comm.send_bytes(meta, dst, tag=base), meta))
        for i, leaf in enumerate(arrays):
            blob = as_byte_view(materialize_leaf(leaf))
            for dst in dst_ranks:
                works.append(
                    (self._comm.send_bytes(blob, dst, tag=base + 1 + i), blob)
                )
            while len(works) > self._SEND_WINDOW_LEAVES * len(dst_ranks):
                work, _keepalive = works.pop(0)
                work.wait(timeout=max(0.0, deadline - _time.monotonic()))
        for work, _keepalive in works:
            work.wait(timeout=max(0.0, deadline - _time.monotonic()))
        logger.info(
            "sent checkpoint step=%d (%d arrays) to ranks %s",
            step,
            len(arrays),
            dst_ranks,
        )

    def recv_checkpoint(
        self,
        src_rank: int,
        metadata: str,
        step: int,
        timeout: float,
        into: Optional[T] = None,
    ) -> T:
        base = self._tags(step)
        t0 = time.monotonic()
        meta_blob = self._comm.recv_bytes(src_rank, tag=base).wait(timeout=timeout)
        skeleton, array_meta = pickle.loads(meta_blob)

        # optional in-place landing zone: matching numpy leaves of `into`
        inplace: List[Optional[np.ndarray]] = [None] * len(array_meta)
        if into is not None:
            existing: List[np.ndarray] = []
            _extract_arrays(into, existing)
            for i, ((dtype_name, shape), arr) in enumerate(
                zip(array_meta, existing)
            ):
                if (
                    isinstance(arr, np.ndarray)
                    and arr.dtype.name == dtype_name
                    and arr.shape == tuple(shape)
                    and arr.flags.c_contiguous
                    and arr.flags.writeable
                ):
                    inplace[i] = arr

        arrays: List[np.ndarray] = []
        for i, (dtype_name, shape) in enumerate(array_meta):
            target = inplace[i]
            if target is None:
                target = np.empty(tuple(shape), dtype=_resolve_dtype(dtype_name))
            try:
                # zero-copy: land the payload straight in the target buffer
                got = self._comm.recv_bytes_into(
                    src_rank, target.reshape(-1).view(np.uint8), tag=base + 1 + i
                ).wait(timeout=timeout)
                if got != target.nbytes:
                    raise ValueError(
                        f"checkpoint array {i}: payload {got} bytes != "
                        f"expected {target.nbytes}"
                    )
            except NotImplementedError:
                blob = self._comm.recv_bytes(src_rank, tag=base + 1 + i).wait(
                    timeout=timeout
                )
                as_byte_view(target)[:] = blob
            arrays.append(target)
        nbytes = len(meta_blob) + sum(int(a.nbytes) for a in arrays)
        self.last_heal_metrics = HealMetrics(
            step=step,
            num_sources=1,
            bytes_total=nbytes,
            duration_s=time.monotonic() - t0,
            per_source_bytes={src_rank: nbytes},
        )
        logger.info(
            "received checkpoint step=%d (%d arrays) from rank %d",
            step,
            len(arrays),
            src_rank,
        )
        return _restore_arrays(skeleton, arrays)

    # ------------------------------------------------------------------
    # striped healing
    # ------------------------------------------------------------------
    #
    # Unlike the legacy per-array framing, striped mode splits the RAW
    # array payloads into a chunk-addressable index
    # (``serialization.array_chunk_ranges``): every chunk is a byte range
    # of one array's buffer, so the healer lands frames from all sources
    # DIRECTLY in the final preallocated arrays — no serialized-stream
    # reassembly or post-load pass.  Chunk→source assignment is the
    # deterministic byte-balanced ``serialization.balanced_shares`` over
    # the canonical source list, computed identically on every peer; a
    # dead source's chunks are re-requested from a survivor over the
    # dst→src control channel (pull semantics grafted onto a push fabric).

    def send_checkpoint_striped(
        self,
        dst_ranks: List[int],
        step: int,
        state_dict: T,
        timeout: float,
        source_index: int = 0,
        num_sources: int = 1,
    ) -> None:
        if num_sources <= 1:
            self.send_checkpoint(dst_ranks, step, state_dict, timeout)
            return
        arrays: List[object] = []
        skeleton = _extract_arrays(state_dict, arrays)
        array_meta = [_leaf_meta(a) for a in arrays]
        sizes = [
            _resolve_dtype(d).itemsize * int(np.prod(s, dtype=np.int64))
            for d, s in array_meta
        ]
        chunks = array_chunk_ranges(sizes, heal_chunk_bytes())
        meta_blob = pickle.dumps(
            {"skeleton": skeleton, "array_meta": array_meta, "chunks": chunks},
            protocol=pickle.HIGHEST_PROTOCOL,
        )
        shares = balanced_shares([e - s for _, s, e in chunks], num_sources)
        own = shares[source_index]
        deadline = time.monotonic() + timeout

        def _serve_dst(dst: int) -> None:
            base = self._tags(step)
            sent_bytes = 0
            # one-array materialization memo: a share's chunks are sorted,
            # so ranges of the same array are served back to back
            memo: Dict[int, np.ndarray] = {}

            def _chunk_view(i: int) -> memoryview:
                ai, start, stop = chunks[i]
                if ai not in memo:
                    memo.clear()
                    memo[ai] = materialize_leaf(arrays[ai])
                return as_byte_view(memo[ai])[start:stop]

            def _send_chunks(indices: List[int]) -> None:
                nonlocal sent_bytes
                window: List[tuple] = []
                for i in indices:
                    # the chaos trip wire honors its arm gate: bytes served
                    # before the event is set neither count nor kill
                    armed = self.chaos_arm is None or self.chaos_arm.is_set()
                    if (
                        armed
                        and self.chaos_die_after_bytes is not None
                        and sent_bytes >= self.chaos_die_after_bytes
                    ):
                        self.chaos_fired.set()
                        self._comm.abort("chaos: heal source killed mid-transfer")
                        raise ConnectionError(
                            "chaos: heal source killed mid-transfer"
                        )
                    blob = _chunk_view(i)
                    window.append(
                        (
                            self._comm.send_bytes(
                                blob, dst, tag=base + _S_CHUNK_OFF + i
                            ),
                            blob,
                        )
                    )
                    if armed:
                        sent_bytes += len(blob)
                    while len(window) > self._SEND_WINDOW_LEAVES:
                        work, _keep = window.pop(0)
                        work.wait(timeout=max(0.0, deadline - time.monotonic()))
                for work, _keep in window:
                    work.wait(timeout=max(0.0, deadline - time.monotonic()))

            self._comm.send_bytes(meta_blob, dst, tag=base + _S_META_OFF).wait(
                timeout=max(0.0, deadline - time.monotonic())
            )
            _send_chunks(own)
            # steal-service loop: answer ("need", [...]) re-requests for a
            # dead peer source's chunks until the healer says done (or the
            # deadline passes — e.g. the healer itself died).  NB the ctrl
            # recv is an ordinary op bounded by the communicator's op
            # timeout: deployments must keep comm timeout_s >= the heal
            # timeout (the Manager constructs both from the same knob)
            while time.monotonic() < deadline:
                try:
                    ctrl = pickle.loads(
                        self._comm.recv_bytes(dst, tag=base + _S_CTRL_OFF).wait(
                            timeout=max(0.0, deadline - time.monotonic())
                        )
                    )
                except Exception as e:  # noqa: BLE001 — healer gone: stop serving
                    logger.info(
                        "striped heal: control channel to dst %d closed (%s)",
                        dst,
                        e,
                    )
                    return
                if ctrl[0] == "done":
                    return
                assert ctrl[0] == "need", ctrl
                _send_chunks(list(ctrl[1]))

        if len(dst_ranks) == 1:
            _serve_dst(dst_ranks[0])
        else:
            errors: List[BaseException] = []

            def _run_serve(dst: int) -> None:
                try:
                    _serve_dst(dst)
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    errors.append(e)

            threads = [
                threading.Thread(
                    target=_run_serve,
                    args=(dst,),
                    name=f"tpuft_heal_src_{dst}",
                    daemon=True,
                )
                for dst in dst_ranks
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=max(0.0, deadline - time.monotonic()))
            # a failed or stuck serve must surface to the manager's error
            # funnel, not masquerade as a completed heal-send
            if errors:
                raise errors[0]
            stuck = [t.name for t in threads if t.is_alive()]
            if stuck:
                raise TimeoutError(
                    f"striped serve still running at deadline: {stuck}"
                )
        logger.info(
            "served striped checkpoint step=%d share %d/%d (%d/%d chunks) to %s",
            step,
            source_index,
            num_sources,
            len(own),
            len(chunks),
            dst_ranks,
        )

    def recv_checkpoint_striped(
        self,
        sources: List[Tuple[int, Optional[str]]],
        step: int,
        timeout: float,
        into: Optional[T] = None,
    ) -> T:
        """Striped heal over the communicator fabric.

        ``sources`` must be the CANONICAL ordered source list from the
        quorum — every sender computes its chunk share positionally against
        the same list, dead entries included.  Chunk frames from all
        sources are drained CONCURRENTLY by one select-driven op
        (``Communicator.heal_drain``) straight into the final array buffers
        (``into``'s matching arrays are reused in place, like the legacy
        path); per-chunk recv ops would serialize on the op thread and cap
        the heal at one link's bandwidth."""
        if len(sources) <= 1:
            src_rank, _meta = sources[0]
            return self.recv_checkpoint(
                src_rank, "<comm>", step, timeout, into=into
            )

        base = self._tags(step)
        deadline = time.monotonic() + timeout
        t0 = time.monotonic()
        src_ranks = [r for r, _ in sources]
        num_sources = len(src_ranks)

        def _remaining() -> float:
            return max(0.0, deadline - time.monotonic())

        # meta phase: every source pushes the same chunk index first; the
        # recv OPS serialize on the communicator's op thread (a wedged-but-
        # connected source therefore stalls this phase until the op watchdog
        # aborts — the documented wedge degradation), but the FRAMES arrive
        # concurrently so the common case is one quick pass; adopt the
        # first, verify the rest, mark dead sources (closed sockets error
        # fast and fail over)
        index: Optional[dict] = None
        dead: Dict[int, BaseException] = {}
        meta_works = [
            (s_rank, self._comm.recv_bytes(s_rank, tag=base + _S_META_OFF))
            for s_rank in src_ranks
        ]
        for s_rank, work in meta_works:
            try:
                meta = pickle.loads(work.wait(timeout=_remaining()))
                if index is None:
                    index = meta
                elif (
                    meta["array_meta"] != index["array_meta"]
                    or meta["chunks"] != index["chunks"]
                ):
                    raise ValueError(
                        f"source rank {s_rank} serves a different checkpoint "
                        f"than the adopted index"
                    )
            except Exception as e:  # noqa: BLE001 — source-level failover
                logger.warning(
                    "striped heal: no index from source rank %d (%s)", s_rank, e
                )
                dead[s_rank] = e
        if index is None:
            raise next(iter(dead.values()))

        skeleton = index["skeleton"]
        array_meta = index["array_meta"]
        chunks: List[Tuple[int, int, int]] = [tuple(c) for c in index["chunks"]]

        # final landing buffers, reusing matching arrays of ``into`` in
        # place exactly like the legacy single-source path
        inplace: List[Optional[np.ndarray]] = [None] * len(array_meta)
        if into is not None:
            existing: List[np.ndarray] = []
            _extract_arrays(into, existing)
            for i, ((dtype_name, shape), arr) in enumerate(
                zip(array_meta, existing)
            ):
                if (
                    isinstance(arr, np.ndarray)
                    and arr.dtype.name == dtype_name
                    and arr.shape == tuple(shape)
                    and arr.flags.c_contiguous
                    and arr.flags.writeable
                ):
                    inplace[i] = arr
        arrays: List[np.ndarray] = [
            inplace[i]
            if inplace[i] is not None
            else np.empty(tuple(shape), dtype=_resolve_dtype(dtype_name))
            for i, (dtype_name, shape) in enumerate(array_meta)
        ]
        chunk_views = [
            as_byte_view(arrays[ai])[start:stop] for ai, start, stop in chunks
        ]

        shares = balanced_shares([e - s for _, s, e in chunks], num_sources)
        expected = {
            src_ranks[i]: shares[i]
            for i in range(num_sources)
            if src_ranks[i] not in dead
        }
        orphans = [
            c
            for i in range(num_sources)
            if src_ranks[i] in dead
            for c in shares[i]
        ]

        try:
            drain = self._comm.heal_drain(
                chunk_views,
                expected,
                orphans,
                chunk_tag=lambda i: base + _S_CHUNK_OFF + i,
                ctrl_tag=base + _S_CTRL_OFF,
                make_need=lambda idxs: pickle.dumps(("need", list(idxs))),
                done_blob=pickle.dumps(("done",)),
                timeout_s=_remaining(),
            )
        except NotImplementedError:
            # tier without a concurrent drain: degrade to the single-source
            # heal from the first live source rather than a slow serialized
            # multi-recv that cannot beat one link anyway
            alive = [r for r in src_ranks if r not in dead]
            logger.warning(
                "striped heal: communicator has no heal_drain; falling back "
                "to single-source heal from rank %s",
                alive[0] if alive else src_ranks[0],
            )
            return self.recv_checkpoint(
                alive[0] if alive else src_ranks[0],
                "<comm>",
                step,
                timeout=_remaining(),
                into=into,
            )
        res = drain.wait(timeout=_remaining())
        dead.update(res["dead"])  # type: ignore[arg-type]

        total_bytes = sum(len(v) for v in chunk_views)
        self.last_heal_metrics = HealMetrics(
            step=step,
            num_sources=num_sources,
            bytes_total=total_bytes,
            duration_s=time.monotonic() - t0,
            per_source_bytes={
                f"rank{p}": n
                for p, n in res["per_source"].items()  # type: ignore[union-attr]
                if n
            },
            failed_sources=[f"rank{p}" for p in sorted(dead)],
            stolen_chunks=int(res["stolen"]),  # type: ignore[call-overload]
        )
        logger.info(
            "striped heal step=%d: %d bytes from %d/%d sources in %.3fs",
            step,
            total_bytes,
            num_sources - len(dead),
            num_sources,
            self.last_heal_metrics.duration_s,
        )
        return _restore_arrays(skeleton, arrays)

    def disallow_checkpoint(self) -> None:
        pass

    def shutdown(self, wait: bool = True) -> None:
        pass
