"""LocalSGD and (Streaming) DiLoCo: communication-reduced fault-tolerant DP.

Behavioral twins of the reference wrappers (``torchft/local_sgd.py``):

- :class:`LocalSGD` (``local_sgd.py:45-172``): train locally for
  ``sync_every`` steps, then average *parameters* across replicas and commit.
- :class:`DiLoCo` (``local_sgd.py:175-795``): the DiLoCo / Streaming DiLoCo
  algorithm — keep a host-side backup of the globally-synced parameters;
  every ``sync_every`` steps compute **pseudogradients** (backup − local),
  average them across replicas (optionally int8-quantized over DCN), step an
  **outer optimizer** on the backup, and mix local/global by
  ``fragment_update_alpha``.  The model is split into fragments whose syncs
  are staggered and overlapped with training (the streaming variant's τ =
  ``fragment_sync_delay``).

jax adaptation: model state lives in a mutable ``holder`` mapping
(``{"params": pytree, ...}``) — the same object registered with the Manager
for healing.  Fragments are index sets over the flattened params, split by
byte size rather than by module boundaries (the reference carves fragments
with torch pipelining; leaf groups are the natural jax equivalent).  Backups
are host numpy (the reference pins them to CPU, ``local_sgd.py:241-253``);
pseudogradient math runs on host, the outer optimizer step runs through
optax.

Degraded fleets (wire v5): when the quorum carries wounded replicas, the
outer reduce both wrappers ride (``Manager.allreduce`` for LocalSGD and the
legacy DiLoCo path, ``Manager.outer_shard_allreduce`` for the sharded one)
automatically becomes a capacity-WEIGHTED average — each replica's
pseudogradient counts by its capacity share, matching the
capacity-proportional data shard it actually trained on
(``data.DistributedSampler(capacities=...)``).  Nothing here changes:
the weighting is a pure pre-scale of each replica's contribution, the
allgathered wire-format delta stays bit-identical across replicas, and the
``_OuterShard`` layout is untouched (a wound never bumps ``quorum_id``, so
no reshard fires; the shard geometry depends on membership, not capacity).
"""

from __future__ import annotations

import logging
import os
import pickle
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import numpy as np

from torchft_tpu import knobs, wire
from torchft_tpu.ddp import allreduce_pytree
from torchft_tpu.manager import Manager
from torchft_tpu.obs.spans import span as obs_span

logger = logging.getLogger(__name__)

# Sharded outer optimizer (ZeRO-1 over the replica dimension):
#   auto/1 — the outer sync runs as a chunk-pipelined
#            reduce_scatter → sharded outer update → allgather(delta):
#            each replica (each HOST on hierarchical topologies) holds only
#            its shard of the outer optimizer state, updates it the moment
#            its reduce-scatter chunk lands (while later chunks are still
#            on the wire), and the updates fan back out as deltas applied
#            identically everywhere.  Outer compute and optimizer memory
#            divide by the shard count; membership changes reshard.
#   0      — the legacy replicated path, byte-for-byte: allreduce the full
#            pseudo-gradient, every replica runs the identical full outer
#            update.
OUTER_SHARD_ENV = "TORCHFT_OUTER_SHARD"

# reshard-exchange collective tags (allgather wire tags 5880/5881 — clear
# of the sharded pipeline's 900+ chunk tag range and every legacy tag base;
# allocated centrally in wire.USER_TAG_ALLOCATIONS)
_RESHARD_LEN_TAG = wire.RESHARD_LEN_TAG
_RESHARD_BLOB_TAG = wire.RESHARD_BLOB_TAG


def _tri_state_mode(env_name: str) -> str:
    """Parse an auto/0/1 mode knob (live-read: the drills flip these
    mid-process)."""
    raw = knobs.get_str(env_name, "auto").strip().lower()
    if raw in ("", "auto"):
        return "auto"
    if raw in ("1", "true", "on"):
        return "1"
    if raw in ("0", "false", "off"):
        return "0"
    raise ValueError(f"unparseable {env_name}={raw!r} (auto|0|1)")


def _outer_shard_mode() -> str:
    return _tri_state_mode(OUTER_SHARD_ENV)


# Streamed outer sync (zero-overhead DiLoCo fragments):
#   auto — stream when the operator set a staleness budget
#          (TORCHFT_STREAM_MAX_STALENESS >= 1) and the sync cadence has
#          room for it; otherwise the legacy blocking schedule.  The
#          staleness bar is an algorithmic hyperparameter (it decides how
#          many inner steps run against pre-sync params before the delta
#          lands), so auto never picks one silently.
#   1    — force streaming with a derived default bar when none is set;
#          falls back (loudly) to blocking when the cadence has no room
#          (per-fragment sync_every - delay - 1 < 1).
#   0    — the legacy blocking path, byte-for-byte (golden-fixture pinned).
STREAM_SYNC_ENV = "TORCHFT_STREAM_SYNC"
STREAM_MAX_STALENESS_ENV = "TORCHFT_STREAM_MAX_STALENESS"
DEFAULT_STREAM_STALENESS = 4


def _stream_mode() -> str:
    return _tri_state_mode(STREAM_SYNC_ENV)


def stream_stall_for(per_frag_sync: int, delay: int) -> int:
    """The effective bounded-staleness bar, in inner steps, for one
    fragment's streamed sync — 0 means streaming is off (blocking path).

    The bar is clamped to the schedule's room: the barrier must fire
    strictly before the NEXT fragment's prepare point (``per_frag_sync -
    delay`` steps into the next round) so at most one streamed sync is
    ever in flight and the round's quorum/vote protocol stays sequential.
    A pure function of env + the (uniform, ctor-validated) cadence, so
    every replica derives the identical schedule — the apply point being
    deterministic is what keeps replicas bit-identical."""
    mode = _stream_mode()
    if mode == "0":
        return 0
    room = per_frag_sync - delay - 1
    bar = knobs.get_int(STREAM_MAX_STALENESS_ENV, 0)
    if mode == "auto":
        return min(bar, room) if bar >= 1 and room >= 1 else 0
    # mode == "1": forced — derive a bar when none is set
    if room < 1:
        logger.warning(
            "%s=1 but the sync cadence has no staleness room "
            "(per-fragment sync_every=%d, delay=%d): falling back to the "
            "blocking outer sync",
            STREAM_SYNC_ENV,
            per_frag_sync,
            delay,
        )
        return 0
    return min(bar if bar >= 1 else DEFAULT_STREAM_STALENESS, room)


class _OuterShard:
    """This owner's shard of one fragment's outer optimizer state.

    The flat f32 element space of the fragment is split into deterministic
    equal shards (``collectives.outer_shard_layout``, 64-byte / row aligned,
    mirrored in ``native/comm.h``); this object holds the optax state for
    ONE shard as numpy leaves, serves per-chunk slices to the pipelined
    sync (``update_cb``), stages the updated state until the commit vote,
    and re-partitions on membership change.

    Resharding: whenever the quorum id moved since the layout was built,
    every replica contributes its (meta, state-shard) over two allgathers
    (lengths, then padded pickles) and reassembles the new shard from
    whichever contributions cover each element range.  Ranges owned by a
    replica that died are re-initialized fresh (momentum history is the
    only loss — parameters are replicated everywhere and unaffected); a
    healed replica contributes the shard it received in the checkpoint, so
    a kill/rejoin cycle conserves every surviving byte of state."""

    def __init__(self, outer_tx: Any, n: int, should_quantize: bool) -> None:
        self._outer_tx = outer_tx
        self._n = n
        self._quant = should_quantize
        # (quorum_id, gsize, gidx, per, owns) of the current layout
        self.meta: Optional[Dict[str, Any]] = None
        self._state_leaves: Optional[List[Any]] = None
        self._state_treedef: Optional[Any] = None
        self._staged: Optional[List[Any]] = None
        # (meta, leaves) recovered from a healing checkpoint, contributed at
        # the next reshard (our own rank may differ from the source's)
        self._loaded: List[Tuple[Dict[str, Any], List[Any]]] = []

    # -- layout ----------------------------------------------------------

    def _fresh_leaves(self, per: int) -> Tuple[List[Any], Any]:
        state = self._outer_tx.init(np.zeros(per, dtype=np.float32))
        leaves, treedef = jax.tree_util.tree_flatten(state)
        return [
            np.array(l, copy=True) if getattr(l, "shape", None) == (per,) else l
            for l in map(np.asarray, leaves)
        ], treedef

    def _is_shard_leaf(self, leaf: Any, per: int) -> bool:
        return getattr(leaf, "shape", None) == (per,)

    def maybe_reshard(self, manager: Manager) -> None:
        """(Re)build this owner's shard for the current quorum.  Gated on
        the quorum id alone — a shared fact, so every replica enters (or
        skips) the collective exchange in lock-step; steady-state syncs
        skip everything."""
        qid = manager._quorum_id
        if self.meta is not None and self.meta["q"] == qid:
            return
        from torchft_tpu.collectives import outer_shard_layout

        gsize, gidx, owns = manager.outer_shard_group()
        _padded, per, unit = outer_shard_layout(self._n, gsize, self._quant)
        meta = {
            "q": qid,
            "gsize": gsize,
            "gidx": gidx,
            "per": per,
            "n": self._n,
            "owns": owns,
        }
        contribs = self._export_contribs()
        comm = manager._comm
        if comm.size() > 1 and not getattr(comm, "is_passthrough", False):
            blob = pickle.dumps(contribs)
            try:
                lens = comm.allgather(
                    np.array([len(blob)], dtype=np.int64), tag=_RESHARD_LEN_TAG
                ).wait()
                maxlen = max(int(np.asarray(l).reshape(-1)[0]) for l in lens)
                padded_blob = np.zeros(max(1, maxlen), dtype=np.uint8)
                padded_blob[: len(blob)] = np.frombuffer(blob, dtype=np.uint8)
                blobs = comm.allgather(padded_blob, tag=_RESHARD_BLOB_TAG).wait()
                contribs = []
                for l, b in zip(lens, blobs):
                    size = int(np.asarray(l).reshape(-1)[0])
                    try:
                        contribs.extend(pickle.loads(bytes(bytearray(b[:size]))))
                    except Exception:  # noqa: BLE001 — skip a bad peer blob
                        logger.warning("outer-shard reshard: bad peer blob")
            except Exception as e:  # noqa: BLE001 — the sync right after
                # this will surface comm errors; reshard falls back to the
                # locally-held contributions (peers' shards re-init fresh)
                logger.warning("outer-shard reshard exchange failed: %s", e)
                contribs = self._export_contribs()
        self._rebuild(contribs, meta)

    def _export_contribs(self) -> List[Tuple[Dict[str, Any], List[Any]]]:
        out = list(self._loaded)
        if self.meta is not None and self._state_leaves is not None:
            out.append((dict(self.meta), self._state_leaves))
        return out

    def _rebuild(
        self,
        contribs: List[Tuple[Dict[str, Any], List[Any]]],
        meta: Dict[str, Any],
    ) -> None:
        self._loaded = []
        self._staged = None
        self.meta = meta
        if not meta["owns"]:
            self._state_leaves, self._state_treedef = None, None
            return
        per = meta["per"]
        leaves, treedef = self._fresh_leaves(per)
        my_lo, my_hi = meta["gidx"] * per, meta["gidx"] * per + per
        for cmeta, cleaves in contribs:
            if cmeta.get("n") != self._n or not cmeta.get("owns", True):
                continue
            cper = cmeta["per"]
            c_lo = cmeta["gidx"] * cper
            lo, hi = max(my_lo, c_lo), min(my_hi, c_lo + cper)
            if lo >= hi or len(cleaves) != len(leaves):
                continue
            for j, (mine, theirs) in enumerate(zip(leaves, cleaves)):
                theirs = np.asarray(theirs)
                if self._is_shard_leaf(mine, per) and self._is_shard_leaf(
                    theirs, cper
                ):
                    mine[lo - my_lo : hi - my_lo] = theirs[lo - c_lo : hi - c_lo]
                elif getattr(theirs, "shape", None) == ():
                    # scalar leaves (step counts): keep the max seen so a
                    # recovered shard never rewinds schedules
                    leaves[j] = np.maximum(np.asarray(leaves[j]), theirs)
        self._state_leaves, self._state_treedef = leaves, treedef

    # -- sync ------------------------------------------------------------

    def make_update_cb(self, backup_flat: np.ndarray):
        """Per-chunk outer update for the pipelined sync: slices this
        shard's state, steps the outer optimizer on the chunk, stages the
        new state (adopted only on commit), returns the delta."""
        assert self.meta is not None and self.meta["owns"]
        assert self._state_leaves is not None
        per = self.meta["per"]
        base = self.meta["gidx"] * per
        old = self._state_leaves
        treedef = self._state_treedef
        self._staged = [
            np.array(l, copy=True) if self._is_shard_leaf(l, per) else l
            for l in old
        ]
        staged = self._staged
        tx = self._outer_tx

        def _cb(lo: int, hi: int, avg: np.ndarray) -> np.ndarray:
            s, e = lo - base, hi - base
            # chunks slice the ORIGINAL state (scalar leaves update from
            # the same pre-sync value on every chunk — consistent)
            state_slice = jax.tree_util.tree_unflatten(
                treedef,
                [l[s:e] if self._is_shard_leaf(l, per) else l for l in old],
            )
            updates, new_state = tx.update(
                avg, state_slice, backup_flat[lo:hi]
            )
            for j, leaf in enumerate(jax.tree_util.tree_leaves(new_state)):
                leaf = np.asarray(leaf)
                if self._is_shard_leaf(staged[j], per):
                    staged[j][s:e] = leaf
                else:
                    staged[j] = leaf
            return np.asarray(updates, dtype=np.float32)

        return _cb

    def commit_stage(self) -> None:
        if self._staged is not None:
            self._state_leaves = self._staged
        self._staged = None

    def abort_stage(self) -> None:
        self._staged = None

    # -- checkpoint round trip -------------------------------------------

    def save_state(self) -> Optional[Dict[str, Any]]:
        if self.meta is None:
            return None
        return {
            "meta": dict(self.meta),
            "leaves": self._state_leaves,
        }

    def load_state(self, state: Optional[Dict[str, Any]]) -> None:
        """A healed checkpoint carries the SOURCE's shard; hold it as a
        reshard contribution (the heal always rides a quorum change, so
        the next sync reshards and routes every range to its new owner)."""
        if not state or state.get("leaves") is None:
            return
        self._loaded.append((state["meta"], state["leaves"]))
        self.meta = None  # force reshard at the next sync


def _like_leaf(value: np.ndarray, ref: Any) -> Any:
    """Return ``value`` with the container type/placement of ``ref``."""
    if isinstance(ref, jax.Array):
        return jax.device_put(value, ref.sharding)
    return value


def partition_leaves(
    params: Any, num_fragments: int
) -> List[List[int]]:
    """Split the flattened leaves of ``params`` into ``num_fragments``
    contiguous groups of roughly equal byte size."""
    leaves = jax.tree_util.tree_leaves(params)
    if len(leaves) < num_fragments:
        raise ValueError(
            f"cannot split {len(leaves)} leaves into {num_fragments} fragments"
        )
    sizes = [int(np.asarray(leaf).nbytes) for leaf in leaves]
    total = sum(sizes)
    target = total / max(num_fragments, 1)
    groups: List[List[int]] = [[] for _ in range(num_fragments)]
    acc, g = 0.0, 0
    for i, size in enumerate(sizes):
        groups[g].append(i)
        acc += size
        # advance AFTER placing, based on accumulated bytes including this
        # leaf, and never leave fewer leaves than remaining groups
        remaining_leaves = len(leaves) - (i + 1)
        remaining_groups = num_fragments - (g + 1)
        if g < num_fragments - 1 and (
            acc >= target * (g + 1) or remaining_leaves <= remaining_groups
        ):
            g += 1
    assert all(groups), "internal error: empty fragment"
    return groups


class LocalSGD:
    """Parameter-averaging LocalSGD (``local_sgd.py:45-172``).

    Usage::

        local_sgd = LocalSGD(manager, holder, sync_every=32)
        with local_sgd:
            for batch in data:
                ...inner optimizer step on holder...
                local_sgd.step()
    """

    def __init__(self, manager: Manager, holder: Dict[str, Any], sync_every: int) -> None:
        if sync_every < 1:
            raise ValueError("sync_every must be >= 1")
        self._manager = manager
        self._holder = holder
        self._sync_every = sync_every
        self._local_step = 0
        # streamed sync (TORCHFT_STREAM_SYNC): LocalSGD is one whole-model
        # "fragment" — the parameter average streams under the next inner
        # steps and applies at the bounded-staleness barrier (inner
        # progress during the stall is overwritten by the committed
        # average, the same semantic as DiLoCo's alpha=0 apply)
        self._stream_stall = stream_stall_for(sync_every, 0)
        self._stream_work = None

    def __enter__(self) -> "LocalSGD":
        return self

    def __exit__(self, *exc: object) -> bool:
        # drain a streamed sync submitted within the final stall window:
        # abandoning it would end the run one committed average short of
        # the blocking schedule at the same step count and leave an open
        # quorum + a dangling stream-fence entry on the Manager
        if self._stream_work is not None:
            self._apply_streamed()
        return False

    def step(self) -> Optional[bool]:
        """Call after every inner optimizer step; returns the commit decision
        on sync steps (at the staleness barrier when streaming), None
        otherwise."""
        self._local_step += 1
        committed: Optional[bool] = None
        if (
            self._stream_work is not None
            and self._local_step >= self._stream_stall
        ):
            committed = self._apply_streamed()
        if self._local_step < self._sync_every:
            return committed
        self._local_step = 0
        if self._stream_stall > 0:
            self._manager.start_quorum()
            with obs_span("tpuft/stream/submit", frag=0):
                # stream=0 registers the composite work in the Manager's
                # stream-fence registry (FRAG_SUBMIT rides it)
                self._stream_work = allreduce_pytree(
                    self._manager, self._holder["params"], stream=0
                )
            return committed
        return self.sync()

    def _apply_streamed(self) -> bool:
        """Bounded-staleness barrier of a streamed parameter average: wait
        the (by now usually drained) collective, vote, and adopt the
        committed average."""
        work, self._stream_work = self._stream_work, None
        with obs_span("tpuft/stream/barrier", frag=0):
            averaged = work.wait()
        committed = self._manager.should_commit()
        self._manager.stream_resolved(0, committed)
        if committed:
            self._holder["params"] = averaged
        return committed

    def sync(self) -> bool:
        """Average parameters across replicas and commit
        (``local_sgd.py:129-172``).

        Routed through ``ddp.allreduce_pytree``'s bucketed pipeline — the
        same path DiLoCo fragments ride: a bucket's device→host copies start
        asynchronously (``copy_to_host_async``) a bucket ahead of the one
        being waited for, each bucket's ring runs while the next ones cross, and
        the rings reduce ``in_place`` in the staging buffers (the live
        params are never aliased).  The old path shipped the whole model as
        one blocking collective with synchronous host copies."""
        self._manager.start_quorum()
        work = allreduce_pytree(self._manager, self._holder["params"])
        averaged = work.wait()
        committed = self._manager.should_commit()
        if committed:
            self._holder["params"] = averaged
        return committed


class _Fragment:
    """One streaming fragment (``_StreamingDiLoCoFragment``,
    ``local_sgd.py:175-566``): backup params, pseudogradients, outer
    optimizer state, alpha mixing."""

    def __init__(
        self,
        manager: Manager,
        holder: Dict[str, Any],
        index: int,
        leaf_idxs: List[int],
        outer_tx: Any,
        should_quantize: bool,
        fragment_update_alpha: float,
    ) -> None:
        self._manager = manager
        self._holder = holder
        self._index = index
        self._leaf_idxs = leaf_idxs
        self._outer_tx = outer_tx
        self._should_quantize = should_quantize
        self._alpha = fragment_update_alpha
        self._work = None
        self._sharded_inflight = False
        # True while a TORCHFT_STREAM_SYNC submit is in flight: the work
        # lives in the Manager's stream-fence registry and perform_sync
        # reports the FRAG_COMMIT/FRAG_ABORT outcome when it resolves
        self._stream_inflight = False

        # cache the pytree layout once: the treedef (reused for every
        # unflatten), and this fragment's per-leaf (shape, dtype, flat
        # offset) over its f32 element space — sync rounds re-read leaf
        # VALUES via tree_leaves but never re-derive structure
        leaves, self._treedef = jax.tree_util.tree_flatten(holder["params"])
        backup = [np.asarray(leaves[i]) for i in self._leaf_idxs]
        self.backup: List[np.ndarray] = [np.array(a, copy=True) for a in backup]
        self._leaf_meta: List[Tuple[int, int, tuple, Any]] = []
        off = 0
        for a in backup:
            self._leaf_meta.append((off, a.size, a.shape, a.dtype))
            off += a.size
        self._n = off
        # padded f32 scratch for pseudo-gradient / backup assembly, reused
        # across sync rounds (grown once to the sharded layout's padded
        # size; the same trick _allreduce_pipelined_sync uses)
        self._psg_scratch: Optional[np.ndarray] = None
        self._backup_scratch: Optional[np.ndarray] = None

        # full replicated outer state exists ONLY on the legacy path — in
        # sharded mode each owner's slice lives in _OuterShard and this
        # stays None (the ZeRO-1 memory division), allocated lazily if a
        # sync ever runs with TORCHFT_OUTER_SHARD=0
        self.outer_state = (
            outer_tx.init(self.backup) if _outer_shard_mode() == "0" else None
        )
        self._shard = _OuterShard(outer_tx, self._n, should_quantize)

        # fragment state rides the healing checkpoint
        # (``local_sgd.py:255-286``)
        key = f"StreamingDiLoCoFragment_{index}"
        manager.register_state_dict_fn(key, self._load_state, self._save_state)

    def _save_state(self) -> Dict[str, Any]:
        return {
            "backup": self.backup,
            "outer_state": self.outer_state,
            "outer_shard": self._shard.save_state(),
        }

    def _load_state(self, state: Dict[str, Any]) -> None:
        self.backup = [np.asarray(a) for a in state["backup"]]
        self.outer_state = state.get("outer_state")
        self._shard.load_state(state.get("outer_shard"))

    def _current_local(self) -> List[np.ndarray]:
        leaves = jax.tree_util.tree_leaves(self._holder["params"])
        return [np.asarray(leaves[i]) for i in self._leaf_idxs]

    def save_parameters(self) -> None:
        self.backup = [np.array(a, copy=True) for a in self._current_local()]

    def _sharded(self) -> bool:
        return _outer_shard_mode() != "0"

    def _scratch(self, padded: int) -> Tuple[np.ndarray, np.ndarray]:
        if self._psg_scratch is None or self._psg_scratch.size < padded:
            self._psg_scratch = np.zeros(padded, dtype=np.float32)
            self._backup_scratch = np.zeros(padded, dtype=np.float32)
        assert self._backup_scratch is not None
        return self._psg_scratch[:padded], self._backup_scratch[:padded]

    def prepare_sync(self, stream: bool = False) -> None:
        """pseudogradient = backup − local, then async average
        (``local_sgd.py:401-420``).  With ``stream=True`` the submit rides
        the Manager's stream-fence registry (and, on the sharded path, the
        fragment's rotating STREAM_OUTER tag window): inner compute
        continues against pre-sync params and the caller applies the delta
        at its bounded-staleness barrier via :meth:`perform_sync`."""
        local = self._current_local()
        assert self._work is None, "fragment already has an allreduce in flight"
        self._stream_inflight = stream
        with obs_span(
            "tpuft/stream/submit" if stream else "tpuft/diloco/prepare",
            frag=self._index,
        ):
            if self._sharded():
                self._prepare_sync_sharded(local, stream)
                return
            pseudograds = [b - l for b, l in zip(self.backup, local)]
            # in_place: pseudograds are freshly computed for this call and
            # only the returned average is read afterwards
            self._work = self._manager.allreduce(
                pseudograds,
                should_quantize=self._should_quantize,
                in_place=True,
                stream=self._index if stream else None,
            )

    def _prepare_sync_sharded(
        self, local: List[np.ndarray], stream: bool = False
    ) -> None:
        """Sharded outer sync: assemble the flat pseudo-gradient, (re)build
        this owner's shard for the current quorum, and hand the per-chunk
        outer update to the pipelined reduce_scatter→update→allgather."""
        from torchft_tpu.collectives import outer_shard_layout

        self._shard.maybe_reshard(self._manager)
        meta = self._shard.meta
        gsize = meta["gsize"] if meta is not None else 1
        padded, _per, _unit = outer_shard_layout(
            self._n, max(1, gsize), self._should_quantize
        )
        psg, backup_flat = self._scratch(padded)
        for (off, size, _shape, _dtype), b, l in zip(
            self._leaf_meta, self.backup, local
        ):
            seg = backup_flat[off : off + size]
            seg[:] = b.reshape(-1)
            p = psg[off : off + size]
            p[:] = seg
            p -= l.reshape(-1)
        psg[self._n :] = 0.0
        backup_flat[self._n :] = 0.0

        update_cb = (
            self._shard.make_update_cb(backup_flat)
            if meta is not None and meta["owns"]
            else _no_shard_cb
        )
        self._sharded_inflight = True
        self._work = self._manager.outer_shard_allreduce(
            psg[: self._n],
            update_cb,
            should_quantize=self._should_quantize,
            stream=self._index if stream else None,
        )

    def perform_sync(self) -> bool:
        """Wait for the result, vote, and apply the outer step
        (``local_sgd.py:422-475``).  On a streamed sync this is the
        bounded-staleness barrier: the wait is ~free when the collectives
        drained under the stalled inner steps, and the vote runs only
        after the work resolved (the Manager's stream fence would
        otherwise force it False)."""
        assert self._work is not None, "prepare_sync must run first"
        streamed = self._stream_inflight
        with obs_span(
            "tpuft/stream/barrier" if streamed else "tpuft/diloco/perform",
            frag=self._index,
        ):
            result = self._work.wait()
        self._work = None
        sharded = self._sharded_inflight
        self._sharded_inflight = False
        self._stream_inflight = False

        local = self._current_local()
        committed = self._manager.should_commit()
        if streamed:
            self._manager.stream_resolved(self._index, committed)

        leaves = jax.tree_util.tree_leaves(self._holder["params"])
        if committed and sharded and result is not None:
            # delta = the allgathered sharded outer update, identical bytes
            # on every replica: global = backup + delta
            delta = result
            global_params = []
            for (off, size, shape, dtype), b in zip(self._leaf_meta, self.backup):
                g = (
                    b.reshape(-1).astype(np.float32) + delta[off : off + size]
                ).astype(dtype, copy=False).reshape(shape)
                global_params.append(g)
            self._apply_global(leaves, global_params, local)
            self._shard.commit_stage()
            # hot spares: the committed delta (identical bytes on every
            # replica) feeds parked spares' shadows — warm channel (a)
            self._manager.publish_staged_outer_delta(self._index)
        elif committed and not sharded:
            import optax

            averaged = result
            if self.outer_state is None:
                self.outer_state = self._outer_tx.init(self.backup)
            updates, self.outer_state = self._outer_tx.update(
                averaged, self.outer_state, self.backup
            )
            global_params = optax.apply_updates(self.backup, updates)
            global_params = [np.asarray(g) for g in global_params]
            self._apply_global(leaves, global_params, local)
        else:
            # failed sync: reset to the last globally-consistent state so we
            # never overtrain on unsynced data (``local_sgd.py:785-790``)
            if sharded:
                self._shard.abort_stage()
            for j, i in enumerate(self._leaf_idxs):
                leaves[i] = _like_leaf(self.backup[j], leaves[i])
        self._holder["params"] = jax.tree_util.tree_unflatten(
            self._treedef, leaves
        )
        return committed

    def _apply_global(
        self,
        leaves: List[Any],
        global_params: List[np.ndarray],
        local: List[np.ndarray],
    ) -> None:
        """model = (1−α)·global + α·local (``local_sgd.py:366-384``)."""
        for j, i in enumerate(self._leaf_idxs):
            mixed = (
                global_params[j]
                if self._alpha == 0.0
                else (1.0 - self._alpha) * global_params[j]
                + self._alpha * local[j]
            ).astype(local[j].dtype)
            leaves[i] = _like_leaf(mixed, leaves[i])
        self.backup = global_params


def _no_shard_cb(lo: int, hi: int, avg: np.ndarray) -> np.ndarray:
    raise AssertionError(
        "outer update callback invoked on a replica that owns no shard"
    )


class DiLoCo:
    """(Streaming) DiLoCo (``local_sgd.py:569-795``).

    Usage::

        manager = Manager(..., use_async_quorum=False)
        diloco = DiLoCo(manager, holder, outer_tx=optax.sgd(0.7, momentum=0.9,
                        nesterov=True), sync_every=20, num_fragments=2)
        with diloco:
            for batch in data:
                ...inner optimizer step on holder...
                diloco.step()
    """

    def __init__(
        self,
        manager: Manager,
        holder: Dict[str, Any],
        outer_tx: Union[Any, List[Any]],
        sync_every: int,
        num_fragments: int = 1,
        fragments: Optional[List[List[int]]] = None,
        should_quantize: bool = False,
        fragment_sync_delay: int = 0,
        fragment_update_alpha: float = 0.0,
    ) -> None:
        if manager._use_async_quorum:
            raise ValueError(
                "DiLoCo requires synchronous quorum: construct the Manager "
                "with use_async_quorum=False"
            )
        if fragments is None:
            fragments = partition_leaves(holder["params"], num_fragments)
        n = len(fragments)
        if sync_every < n:
            raise ValueError("Only 1 fragment can be synchronized at a time")
        if sync_every % n != 0:
            raise ValueError("sync_every must be divisible by the fragment count")
        self._sync_every = sync_every // n
        if fragment_sync_delay >= self._sync_every:
            raise ValueError("Fragment must be synced before it is reduced again")
        if not 0.0 <= fragment_update_alpha <= 1.0:
            raise ValueError("fragment_update_alpha must be between 0 and 1")

        self._manager = manager
        self._holder = holder
        self._local_step = 0
        self._fragment_sync_delay = fragment_sync_delay
        # streamed outer sync: the effective bounded-staleness bar (inner
        # steps between a fragment's sync point and its delta applying; 0 =
        # legacy blocking schedule).  Resolved ONCE at construction — the
        # schedule must be identical on every replica and stable for the
        # run, like the cadence itself.
        self._stream_stall = stream_stall_for(
            self._sync_every, fragment_sync_delay
        )
        # the fragment whose streamed sync is awaiting its barrier (at most
        # one: the bar is clamped below the next prepare point)
        self._stream_pending_frag: Optional[int] = None

        outer_txs = (
            outer_tx if isinstance(outer_tx, list) else [outer_tx] * n
        )
        if len(outer_txs) != n:
            raise ValueError("need one outer optimizer per fragment")
        self._fragments = [
            _Fragment(
                manager,
                holder,
                i,
                leaf_idxs,
                outer_txs[i],
                should_quantize,
                fragment_update_alpha,
            )
            for i, leaf_idxs in enumerate(fragments)
        ]

    def __enter__(self) -> "DiLoCo":
        return self

    def __exit__(self, *exc: object) -> bool:
        # drain a streamed sync whose sync step already passed but whose
        # staleness barrier hasn't fired: abandoning it would end the run
        # one committed round short of the blocking schedule and leave a
        # dangling stream-fence entry.  (A fragment merely PREPARED —
        # sync step not yet reached — is abandoned exactly like the
        # blocking schedule abandons it.)
        if self._stream_pending_frag is not None:
            frag = self._stream_pending_frag
            self._stream_pending_frag = None
            self._fragments[frag].perform_sync()
        return False

    def _current_fragment(self) -> int:
        """All replicas must prepare/sync fragments in the same order to
        avoid cross-replica deadlock (``local_sgd.py:745-763``)."""
        return self._manager.current_step() % len(self._fragments)

    def pre_step(self):
        """Guard the holder against concurrent checkpoint reads while the
        inner optimizer mutates it (the reference's inner optimizer
        pre-hook, ``local_sgd.py:716-720``).  Returns a context manager so
        the lock is released even when the inner step raises::

            with diloco.pre_step():
                ...inner optimizer step...
            diloco.step()
        """
        import contextlib

        manager = self._manager

        @contextlib.contextmanager
        def _guard():
            manager.disallow_state_dict_read()
            try:
                yield
            finally:
                manager.allow_state_dict_read()

        return _guard()

    def streaming(self) -> bool:
        """True when the streamed scheduler is engaged (TORCHFT_STREAM_SYNC
        resolved against this cadence at construction)."""
        return self._stream_stall > 0

    def step(self) -> Optional[bool]:
        """Call after every inner optimizer step (the reference's optimizer
        post-hook, ``local_sgd.py:745-795``); returns the commit decision on
        sync steps, None otherwise.

        Streamed schedule (``TORCHFT_STREAM_SYNC``): the sync step no
        longer blocks — the fragment's reduce_scatter → sharded update →
        allgather keeps draining on its background path while inner
        compute continues against pre-sync params, and the identical
        wire-format delta applies ``stall`` inner steps later at the
        bounded-staleness barrier (where the commit decision is returned).
        The barrier position is a pure function of the cadence, so every
        replica applies at the same inner step — replicas stay
        bit-identical, exactly as on the blocking path."""
        self._manager.allow_state_dict_read()
        self._local_step += 1

        committed: Optional[bool] = None
        if (
            self._stream_pending_frag is not None
            and self._local_step >= self._stream_stall
        ):
            # bounded-staleness barrier: resolve the streamed fragment
            # BEFORE this round's prepare can open a new quorum (the bar
            # is clamped strictly below the prepare point)
            frag = self._stream_pending_frag
            self._stream_pending_frag = None
            logger.info(
                "Stream barrier fragment=%d step=%d manager_step=%d",
                frag,
                self._local_step,
                self._manager.current_step(),
            )
            committed = self._fragments[frag].perform_sync()

        if self._local_step == self._sync_every - self._fragment_sync_delay:
            # quorum + overlap the pseudogradient allreduce with the next τ
            # inner steps
            self._manager.start_quorum()
            fragment = self._current_fragment()
            logger.info(
                "Preparing fragment=%d step=%d", fragment, self._local_step
            )
            self._fragments[fragment].prepare_sync(stream=self.streaming())
            if self._fragment_sync_delay > 0:
                return committed

        if self._local_step < self._sync_every:
            return committed

        assert self._local_step == self._sync_every, (
            f"local_step={self._local_step} overran sync_every={self._sync_every}"
        )
        fragment = self._current_fragment()
        if self.streaming():
            # the sync step streams: hand the fragment to the stall window
            # and keep training — perform_sync runs at the barrier above
            self._stream_pending_frag = fragment
            self._local_step = 0
            return committed
        logger.info(
            "Syncing fragment=%d step=%d manager_step=%d",
            fragment,
            self._local_step,
            self._manager.current_step(),
        )
        committed = self._fragments[fragment].perform_sync()
        self._local_step = 0
        return committed
