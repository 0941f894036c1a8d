"""The program's one span API: named windows on the profiler's clock.

``span(name, **attrs)`` is a context manager around one stage of the
protocol.  While a profiler session is on, every span enters a
``jax.profiler.TraceAnnotation`` and so lies in the SAME ``.xplane.pb`` as
the device's operations, on one clock, with its attributes as the event's
stats.  With no session on it makes no annotation and calls nothing of the
profiler's native module: whether a session is on is read from jax's own
Python record of it (what ``jax.profiler.start_trace`` and ``trace`` set),
one attribute.  An annotation a span with none listening was measured on
the v5e to go with stalled steps (seconds in which the whole process stands
still: 6 of 16 runs against 0 of 24 without, on machines that show the
short freezes at all; PERF.md section 6, PR 26), so a span that began
before the session did is simply not in its trace, and neither is one
inside a capture that a profiler SERVER took (jax keeps no record of
those).  Names are ``tpuft/<layer>/<stage>``::

    tpuft/step/grad, tpuft/step/update            (HSDPTrainer.train_step)
    tpuft/manager/quorum                           (quorum thread: the RPC)
    └─ tpuft/manager/comm_configure
       └─ tpuft/comm/rendezvous
    tpuft/manager/fence, tpuft/manager/should_commit
    tpuft/ddp/allreduce_pytree                     (train thread, then gather)
    ├─ tpuft/ddp/plan, tpuft/ddp/d2h, tpuft/ddp/pack        (train thread)
    ├─ tpuft/ddp/submit                            (train thread, one a bucket)
    ├─ tpuft/comm/op                               (op thread, one a collective)
    │  └─ tpuft/comm/lane_window
    └─ tpuft/ddp/ring_wait, tpuft/ddp/h2d          (gather thread)
    tpuft/heal/snapshot, tpuft/heal/serve          (survivor)
    tpuft/heal/fetch, tpuft/heal/apply             (new life)
    tpuft/outer_shard/*, tpuft/stream/*            (DiLoCo / LocalSGD)

On the native tier a round trip's rings are ONE call of the op thread
(``tpuft/comm/session``, ``pieces=``), which can open no span while it is
inside; the session keeps each piece's start and end in C on
``time.monotonic``'s clock and the binding hands them to :func:`emit` when the
call returns: one ``tpuft/comm/op`` a piece with rising ``k``, in the
``TORCHFT_FLIGHT_SPANS`` buffer.  A profiler's trace shows the session's one
span and no ``tpuft/comm/op`` under it (a span that has already ended cannot
be annotated), so whoever reads ``tpuft/comm/op`` from a trace finds none
where the session ran; DDP_SYNC's ring counters say what the rings took.

Beneath ``tpuft/comm/op`` there is no span: a ring's 4 MiB quanta are too many
for one each.  The communicator counts its own seconds there, always
(``Communicator.lane_stats()``: a lane's in recv, in the reduce's add and in
send, the op thread's in the ring's two phases, the division between them and
the tail), and DDP_SYNC carries a round trip's differences.

**Device operations with names of their own.**  A Mosaic kernel is named by
its ``pallas_call``'s ``name=`` and is ``%<name>.N`` among a trace's device
operations; these are the names the program gives out::

    flash_fwd, flash_dq, flash_dkv     (ops/flash_attention.py)
    flash_win_fwd, flash_win_dq, flash_win_dkv
                                       (the same with a window: the grid
                                        holds the window's blocks alone)
    eva_fwd, eva_dq, eva_dkv           (the same over two key sources under
                                        one softmax, ``eva_attention``: chunk
                                        summaries and a window's tokens)
    kda_fwd, kda_bwd                   (ops/kda.py: the chunked delta rule)
    gdn_fwd, gdn_bwd                   (ops/gdn.py: the same with one decay a
                                        head, unbounded, and value heads that
                                        share a key head)
    selscan_fwd, selscan_bwd           (ops/selscan.py: a selective scan with a
                                        decay for every (channel, state) pair,
                                        the recurrence itself on the vector
                                        unit with the state in VMEM)
    ...gmm..., ...tgmm...              (parallel/moe.py RoutedExperts: jax's
                                        megablox kernels, named after the
                                        jitted functions around them)

**The parts of the compiled step.**  A device operation that XLA makes has
no name of ours, only the path of ``jax.named_scope``s it was traced under
(``op_name`` in the compiled program, ``tf_op`` in a trace's event metadata).
:class:`part` opens one of these sixteen scopes (``with part("ffn"):`` around
some lines, ``@part("ffn")`` on a function that is one part whole), ONE
vocabulary for every architecture (:data:`DEVICE_PARTS`); the innermost one
on an operation's path is its part::

    tpuft.embed             the embedding lookup (its scatter-add gradient
                            follows by the path)
    tpuft.stream            a layer's norm of the stream, the residual add,
                            the stream's casts (float32 <-> bfloat16)
    tpuft.mixer_proj        the products into and out of a mixer: q/k/v/o,
                            MLA's low-rank pairs, KDA's and Mamba-2's w_in /
                            w_out, the index's projections
    tpuft.mixer_glue        everything of a mixer between its projections and
                            its kernel (rope, q/k norms, the short convolution,
                            softplus and decays, gates, the gated norm, splits,
                            reshapes and their layout copies), and the plain
                            path where no kernel runs; Gated DeltaNet's
                            convolution over q, k and v at once, its unit q and
                            k, ``softplus`` and the decay, the SiLU-gated head
                            norm and the full layers' sigmoid gate are here
                            (``models/gated_delta_moe.py``): no part is new
    tpuft.mixer_conv        a Mamba-2 mixer's short causal convolution over its
                            ``X | B | C`` channels with the bias and the SiLU
                            (``models/ssm_hybrid_dense.py``), forward and
                            backward: told from the glue where the state-space
                            mixer is the larger part of a step
    tpuft.mixer_gate        the same mixer's ``y * silu(z)``, the RMSNorm over
                            all its channels and the norm's weight, forward and
                            backward; ``softplus``, ``dt * x``, the running sum
                            of the log decay, the splits and the layout copies
                            around the launch stay ``mixer_glue``'s
    tpuft.mixer_diff        differential attention's combination of its two
                            softmaxes (``models/sambay.py``): ``lambda`` from
                            its four vectors, ``O1 - lambda O2``, the RMSNorm
                            over a pair's channels and ``1 - lambda_0``,
                            forward and backward; the projections stay
                            ``mixer_proj``'s and the head shuffles around the
                            launch ``mixer_glue``'s
    tpuft.mixer_pool        a mixer's pooling of keys and values into chunk
                            summaries (``models/eva.py``): the scores against
                            the learned vector, the softmax over a chunk, the
                            two weighted sums, the learned offset, and their
                            backward pass; told from the glue so that the
                            mechanism's XLA half has a time of its own
    tpuft.ffn               dense MLPs: SwiGLU, a dense layer, the shared expert
    tpuft.experts_route     router product, scores, groups, top-k, the load
                            count, the balance loss
    tpuft.experts_dispatch  RoutedExperts but for the grouped products: argsort,
                            gather, masks, activation, weights, scatter-add
    tpuft.head              final norm, logits, the losses, the step summary
    tpuft.mtp               the multi-token-prediction module's OWN work
                            (``models/latent.py``): the norms of the hidden
                            state and of the next token's embedding, the
                            projection of the pair, its final norm, its logits
                            and its cross-entropy; its layer names its parts
                            (mixer_proj, experts_dispatch, ...) as any layer does
    tpuft.loop_gate         a looped model's exit gate and what it weighs
                            (``models/looped.py``): every pass's gate logit,
                            ``softplus``, the exit distribution over the
                            passes, the expected loss under it, its entropy
                            and the step's summary of them, forward and
                            backward; the heads' logits and cross-entropies
                            stay ``head``'s
    tpuft.layers            the lax.scan over a run of layers, around the
                            body's own parts: what is left under it alone is
                            the loop's machinery, the slices of the stacked
                            weights and the writes of their stacked gradients
    tpuft.optimizer         the whole update step (optax, apply_updates,
                            advance_state)

The same paths group xprof's and TensorBoard's op profile by part, with no
tool of ours.  In a path ``jvp(`` without ``transpose(`` is the forward pass,
``transpose(`` the backward pass, and ``rematted_computation`` the forward
pass run again inside the backward pass (``jax.checkpoint``).  A scope is
metadata on the lowered operations: it costs nothing at run time, changes
nothing of the lowered program's text or the compile cache's key, and there
is no switch for it.  The other side of that: a persistent compile cache
hands out an executable with the paths of the process that COMPILED it, so a
program compiled before a scope was written shows the old paths until its
text changes or the cache is emptied.  A Mosaic kernel keeps the name above
(the TPU compiler names a custom call after the path component that encloses
its ``pallas_call``, which a scope AROUND the call does not change).
``ftbench/device_scopes.py`` shares out a trace's device time by them.

**Which replica, which step.**  Replica groups may be threads of one
process, and helper threads work for one of them.  A thread says whom it
works for with :func:`bind` (the replica's ``FlightRecorder``: it knows the
replica's id and the step the Manager last announced); every span then
carries ``r=`` and, unless the caller gave one, ``step=``.  A span on a
thread nobody bound says ``r=""``.  A child names its parent by nesting on
its own thread; across threads the spans of one round trip share ``r`` and
``step``.  A span that crosses threads (:meth:`_Span.detach` on the thread
that opened it, :meth:`_Span.attach` on the one that closes it) is two
annotations of one name, ``r`` and ``step`` whose union is the span.

**Boundaries also write the flight ring.**  ``span(..., flight=EVENT)``
records ONE event into the bound recorder at exit, with ``t0``,
``duration_s``, the attributes and whatever the body added with
:meth:`_Span.set`; ``begin=EVENT`` records a marker at entry (what a dump
shows of a stage that never returned).  Only per-step and per-heal
boundaries take them; a per-bucket span never does.  ``into=dict, key=``
stores the duration in seconds under ``key`` (``last_quorum_timings``).

``TORCHFT_FLIGHT_SPANS=1`` also keeps every span in a process-global
bounded buffer on ``time.monotonic``; :func:`export_chrome_trace` writes it
as Chrome trace-event JSON and ``scripts/flight_merge.py --spans`` merges
several replicas' files with their flight dumps into one fleet timeline.
"""

from __future__ import annotations

import collections
import contextlib
import json
import threading
import time
from typing import Any, Dict, List, Optional

from torchft_tpu import knobs

SPANS_ENV = "TORCHFT_FLIGHT_SPANS"

# the parts of the compiled step (the module docstring says what lies under each)
DEVICE_PARTS = (
    "embed", "stream", "mixer_proj", "mixer_glue", "mixer_diff", "mixer_pool", "ffn", "experts_route",
    "experts_dispatch", "head", "mtp", "loop_gate", "layers", "optimizer", "mixer_conv", "mixer_gate",
)
PART_PREFIX = "tpuft."

# None = resolve from env on first use; configure() pins it for the process
_enabled: Optional[bool] = None
_spans: "collections.deque" = collections.deque(maxlen=8192)
_lock = threading.Lock()
_bound = threading.local()
# jax.profiler.TraceAnnotation and jax's record of the session it started,
# imported at the first span: the lighthouse and the launcher import this
# package and never a backend
_annotation_cls: Any = None
_profile_state: Any = None


def _load_profiler() -> None:
    global _annotation_cls, _profile_state
    from jax.profiler import TraceAnnotation

    try:
        from jax._src.profiler import _profile_state as state
    except ImportError:  # a jax that keeps it elsewhere: ask the profiler
        state = None
    _profile_state = state
    _annotation_cls = TraceAnnotation


def _session_on() -> bool:
    """Whether this process has a profiler session on."""
    if _annotation_cls is None:
        _load_profiler()
    if _profile_state is None:
        return _annotation_cls.is_enabled()
    return _profile_state.profile_session is not None


def spans_enabled() -> bool:
    """Whether spans are also kept in the ``TORCHFT_FLIGHT_SPANS`` buffer."""
    global _enabled
    if _enabled is None:
        _enabled = knobs.get_bool(SPANS_ENV, False)
    return _enabled


def configure(enabled: Optional[bool], cap: Optional[int] = None) -> None:
    """Pin the span buffer on/off for the process (``None`` re-reads the
    env on next use).  ``cap`` resizes the buffer (drops collected spans)."""
    global _enabled, _spans
    _enabled = enabled
    if cap is not None:
        with _lock:
            _spans = collections.deque(maxlen=max(1, cap))


def clear() -> None:
    with _lock:
        _spans.clear()


def bind(recorder: Any) -> None:
    """The calling thread works for ``recorder``'s replica from here on (a
    ``FlightRecorder``, or None to unbind)."""
    _bound.recorder = recorder


def bound() -> Any:
    """The recorder the calling thread was bound to, or None: what a thread
    hands to a helper it starts."""
    return getattr(_bound, "recorder", None)


class _Span:
    __slots__ = (
        "name", "attrs", "t0", "duration_s", "_recorder", "_flight", "_into",
        "_key", "_begin", "_annotation",
    )

    def __init__(self, name, attrs, recorder, flight, begin, into, key) -> None:
        self.name = name
        self.attrs = attrs
        self.t0 = 0.0
        self.duration_s = 0.0
        self._recorder = recorder
        self._flight = flight
        self._into = into
        self._key = key
        self._begin = begin
        self._annotation = None

    def __enter__(self) -> "_Span":
        if self._begin is not None and self._recorder is not None:
            self._recorder.record(self._begin, step=self.attrs.get("step"))
        self.attach()
        self.t0 = time.monotonic()
        return self

    def set(self, **attrs: Any) -> None:
        """Facts the body learned (bytes, summed seconds of its stages): they
        ride the exit's flight event and the span buffer, not the profiler's
        annotation, which was written at entry."""
        self.attrs.update(attrs)

    def detach(self) -> None:
        """The opening thread leaves; the span stays open."""
        annotation, self._annotation = self._annotation, None
        if annotation is not None:
            annotation.__exit__(None, None, None)

    def attach(self) -> None:
        """The calling thread carries the span from here (and closes it)."""
        # only while a profiler session is on (see the module docstring)
        if _session_on():
            self._annotation = _annotation_cls(self.name, **self.attrs)
            self._annotation.__enter__()

    def __exit__(self, *exc: object) -> None:
        self.duration_s = duration = time.monotonic() - self.t0
        self.detach()
        attrs = self.attrs
        if _enabled:
            _spans.append(  # deque append: GIL-atomic, no lock on the hot path
                (self.name, self.t0, duration, threading.get_ident(), dict(attrs))
            )
        if self._into is not None:
            self._into[self._key] = duration
        if self._flight is not None and self._recorder is not None:
            detail = {k: v for k, v in attrs.items() if k not in ("r", "step")}
            self._recorder.record(
                self._flight,
                step=attrs.get("step"),
                t0=round(self.t0, 6),
                duration_s=round(duration, 6),
                **detail,
            )


class part(contextlib.ContextDecorator):
    """``jax.named_scope("tpuft.<name>")`` around the tracing of one part of
    the compiled step, as ``with part("ffn"):`` around some lines or as
    ``@part("ffn")`` on a function that is one part whole; ``name`` is one of
    :data:`DEVICE_PARTS`."""

    def __init__(self, name: str) -> None:
        if name not in DEVICE_PARTS:
            raise ValueError(f"{name!r} is no part of the compiled step: {DEVICE_PARTS}")
        self.name = name

    def _recreate_cm(self) -> "part":
        # a scope of its own for every call of a decorated function: replica
        # groups are threads, and two may trace the same function at once
        return part(self.name)

    def __enter__(self) -> Any:
        import jax

        self._scope = jax.named_scope(PART_PREFIX + self.name)
        return self._scope.__enter__()

    def __exit__(self, *exc: Any) -> Any:
        return self._scope.__exit__(*exc)


def span(
    name: str,
    flight: Any = None,
    begin: Any = None,
    into: Optional[Dict[str, float]] = None,
    key: Optional[str] = None,
    **attrs: Any,
) -> _Span:
    """Context manager around one named stage (see the module docstring)."""
    recorder = getattr(_bound, "recorder", None)
    if recorder is not None:
        attrs["r"] = recorder.replica_id
        if "step" not in attrs:
            attrs["step"] = recorder.step
    else:
        attrs["r"] = ""
    if _enabled is None:
        spans_enabled()
    return _Span(name, attrs, recorder, flight, begin, into, key)


def emit(name: str, t0: float, t1: float, **attrs: Any) -> None:
    """A span that has already ended, ``t0`` to ``t1`` on ``time.monotonic``:
    what a thread measured where it could open none (inside one native
    call).  Carries ``r`` and ``step`` as :func:`span` does and goes to the
    ``TORCHFT_FLIGHT_SPANS`` buffer alone: a profiler's trace takes only
    annotations that are open while it listens."""
    if not spans_enabled():
        return
    recorder = getattr(_bound, "recorder", None)
    attrs["r"] = recorder.replica_id if recorder is not None else ""
    if attrs.get("step") is None and recorder is not None:
        attrs["step"] = recorder.step
    _spans.append((name, t0, t1 - t0, threading.get_ident(), attrs))


def snapshot() -> List[Dict[str, Any]]:
    """Collected spans as dicts, oldest first (non-destructive)."""
    out = []
    for name, t0, dur, tid, attrs in list(_spans):
        out.append(
            {
                "name": name,
                "t": round(t0, 6),
                "dur": round(dur, 6),
                "tid": tid,
                "attrs": attrs,
            }
        )
    return out


def export_chrome_trace(path: str, replica_id: str = "") -> int:
    """Write the collected spans as Chrome trace-event JSON (``"X"``
    complete events, microsecond units) at ``path``.  Returns the span
    count.  The file is Perfetto-loadable standalone; the fleet view comes
    from ``scripts/flight_merge.py``."""
    events: List[Dict[str, Any]] = []
    pid = abs(hash(replica_id)) % 100000 if replica_id else 1
    if replica_id:
        events.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "args": {"name": replica_id},
            }
        )
    spans = snapshot()
    for rec in spans:
        events.append(
            {
                "name": rec["name"],
                "ph": "X",
                "ts": round(rec["t"] * 1e6, 1),
                "dur": round(rec["dur"] * 1e6, 1),
                "pid": pid,
                "tid": rec["tid"],
                "args": rec["attrs"],
            }
        )
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return len(spans)
