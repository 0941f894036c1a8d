"""The program's own spans (``torchft_tpu/obs/spans.py``), read from the
run's trace.

Every stage of the program is a ``tpuft/<layer>/<stage>`` annotation on the
profiler's clock, in the same ``.xplane.pb`` as the device's operations, and
carries as stats the replica it works for (``r``), the step that caused it
(``step``) and, on the communicator's op thread, which collective of the
step it is (``k``; a round trip's ONE ``tpuft/comm/session`` carries the
``k`` of its first piece and ``pieces``).  ``trace_reduce.from_profile`` keeps names and times
only, so this file reads the host planes again, with their stats, once a
process.  A program without such spans (a parent commit) gives an empty
list and every reader of it returns None.

A span that crosses threads (``tpuft/ddp/allreduce_pytree``: opened by the
train thread, closed by the gather thread) is two annotations of one name,
``r`` and ``step``; :func:`merged` makes one interval of them.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from ftbench import trace_reduce
from ftbench.accounting import union_seconds
from ftbench.sources import traced_stretch

PREFIX = "tpuft/"
SYNC = "tpuft/ddp/allreduce_pytree"
# the stages of one round trip, on whichever thread: what is taken out of
# the parent to leave ``sync_unnamed_ms``
SYNC_CHILDREN = ("tpuft/ddp/", "tpuft/comm/", "tpuft/manager/normalize")
# spans in which a thread waits for another: named last when idle time is
# shared out (``idle_by_span``)
WAITS = ("tpuft/ddp/ring_wait", "tpuft/manager/fence")
# pieces of one crossing span lie this close (a thread's start)
JOIN_S = 0.25

Span = Dict[str, Any]  # name, start, end (s, trace clock), line, r, step, k, ...

_LOADED: Dict[str, List[Span]] = {}


def from_profile(space: Any) -> List[Span]:
    """The ``tpuft/`` events of a ``jax.profiler.ProfileData`` (or anything
    with its ``planes`` / ``lines`` / ``events`` and an event's ``name``,
    ``start_ns``, ``duration_ns``, ``stats``), device planes left out."""
    out: List[Span] = []
    for plane in space.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for idx, line in enumerate(plane.lines):
            for ev in line.events:
                if not ev.name.startswith(PREFIX):
                    continue
                span = dict(ev.stats)
                span.update(
                    name=ev.name,
                    start=ev.start_ns * 1e-9,
                    end=(ev.start_ns + ev.duration_ns) * 1e-9,
                    line=(plane.name, idx),
                )
                span.setdefault("r", "")
                out.append(span)
    return sorted(out, key=lambda s: s["start"])


def load(bench_dir: Optional[str] = None) -> List[Span]:
    """The spans of the run's trace, found as the harness finds it."""
    bench_dir = bench_dir or os.path.dirname(os.path.abspath(__file__))
    path = trace_reduce.find_xplane(os.path.join(bench_dir, "out", "trace"))
    if path is None:
        return []
    if path not in _LOADED:
        from jax.profiler import ProfileData

        _LOADED.clear()
        _LOADED[path] = from_profile(ProfileData.from_file(path))
    return _LOADED[path]


def of_replica(spans: Iterable[Span], replica: int) -> List[Span]:
    """Spans of replica group ``replica``: the harness names its Managers
    ``ftbench_<idx>`` and the Manager adds a life's uuid and the rank."""
    want = f"ftbench_{replica}"
    return [
        s for s in spans
        if s["r"] == want or str(s["r"]).startswith((want + ":", want + "/"))
    ]


def all_in_stretch(
    sources: Dict[str, Any], spans: Optional[List[Span]] = None
) -> Optional[Tuple[List[Span], int]]:
    """(spans of every replica that begin inside ``sources['trace']``'s
    traced stretch, its steps), or None where there is no span.  A trace
    with no device plane (the CPU rehearsal) has no stretch: then every
    span of the file counts, and the steps are those replica 0 made a round
    trip in, or else had any span in (the profiler is started and stopped
    between two of replica 0's steps)."""
    spans = load() if spans is None else spans
    if not spans:
        return None
    stretch = traced_stretch(sources)
    if stretch is not None:
        a, b, steps = stretch
        return [s for s in spans if a <= s["start"] <= b], steps
    if sources.get("trace"):
        return None  # a device trace whose stretch holds no whole step
    mine = of_replica(spans, 0)
    steps = {s.get("step") for s in mine if s["name"] == SYNC} or {s.get("step") for s in mine}
    return spans, max(1, len(steps))


def in_stretch(
    sources: Dict[str, Any], replica: int = 0, spans: Optional[List[Span]] = None
) -> Optional[Tuple[List[Span], int]]:
    """:func:`all_in_stretch` for one replica group."""
    found = all_in_stretch(sources, spans)
    if found is None:
        return None
    mine = of_replica(found[0], replica)
    return (mine, found[1]) if mine else None


def merged(spans: Iterable[Span], name: str) -> List[Span]:
    """The spans called ``name`` with the pieces of a crossing span joined:
    pieces of one ``r`` and ``step`` that overlap or lie within ``JOIN_S``."""
    out: List[Span] = []
    for s in sorted((s for s in spans if s["name"] == name), key=lambda s: s["start"]):
        last = next(
            (o for o in reversed(out) if o["r"] == s["r"] and o.get("step") == s.get("step")),
            None,
        )
        if last is not None and s["start"] <= last["end"] + JOIN_S:
            last["end"] = max(last["end"], s["end"])
        else:
            out.append(dict(s))
    return out


def named_seconds(spans: Iterable[Span], name: str) -> float:
    return sum(s["end"] - s["start"] for s in spans if s["name"] == name)


def per_step_ms(sources: Dict[str, Any], name: str) -> Optional[float]:
    """Summed milliseconds a step of the spans called ``name`` on replica 0."""
    found = in_stretch(sources)
    if found is None:
        return None
    spans, steps = found
    if not any(s["name"] == name for s in spans):
        return None
    return 1000.0 * named_seconds(spans, name) / steps


def sync_round_trips(
    sources: Dict[str, Any], spans: Optional[List[Span]] = None
) -> Optional[List[Tuple[float, float]]]:
    """Per round trip of replica 0 inside the traced stretch: (seconds of
    the parent span, seconds of it that no child span on any thread of the
    replica covers)."""
    found = in_stretch(sources, spans=spans)
    if found is None:
        return None
    spans, _ = found
    parents = merged(spans, SYNC)
    if not parents:
        return None
    children = [
        s for s in spans if s["name"] != SYNC and s["name"].startswith(SYNC_CHILDREN)
    ]
    out = []
    for p in parents:
        covered = union_seconds(
            [
                (max(c["start"], p["start"]), min(c["end"], p["end"]))
                for c in children
                if c["end"] > p["start"] and c["start"] < p["end"]
            ]
        )
        out.append((p["end"] - p["start"], p["end"] - p["start"] - covered))
    return out


def peer_skew_s(spans: Sequence[Span], name: str = "tpuft/comm/session") -> List[Tuple[Any, float]]:
    """(step, summed over the step's spans called ``name`` the distance between
    the replicas' starts of the k-th one) for every step two replicas or more
    have spans of.  By default the ONE span a round trip's rings have since
    PR 60 (``tpuft/comm/session``: how far apart the replicas enter the ring;
    ``tpuft/comm/op``, a span a collective, is the per-call path's and in no
    trace of a session).  No per-layer metric reads it since PR 66 retired
    ``ring_peer_skew_ms``: it is a column of :func:`main`'s table."""
    starts: Dict[Tuple[Any, Any], Dict[str, float]] = {}
    for s in spans:
        if s["name"] == name and "k" in s and "step" in s:
            starts.setdefault((s["step"], s["k"]), {}).setdefault(s["r"], s["start"])
    per_step: Dict[Any, float] = {}
    for (step, _k), by_replica in starts.items():
        if len(by_replica) > 1:
            per_step[step] = per_step.get(step, 0.0) + max(by_replica.values()) - min(by_replica.values())
    return sorted(per_step.items())


def leaves(spans: Sequence[Span]) -> List[Span]:
    """Spans with no other span of their own thread inside them."""
    out = []
    by_line: Dict[Any, List[Span]] = {}
    for s in spans:
        by_line.setdefault(s["line"], []).append(s)
    for line_spans in by_line.values():
        for s in line_spans:
            if not any(
                o is not s and s["start"] <= o["start"] and o["end"] <= s["end"]
                and (o["start"], o["end"]) != (s["start"], s["end"])
                for o in line_spans
            ):
                out.append(s)
    return out


def _rank(name: str) -> int:
    """Who is given a moment two threads' spans share: the train thread's
    stage first (the serial path), then the op thread's, then the gather
    thread's work, and a span that only waits last."""
    if name in WAITS:
        return 3
    if name.startswith("tpuft/comm/") or name == "tpuft/manager/normalize":
        return 1
    if name == "tpuft/ddp/h2d":
        return 2
    return 0


def idle_by_span(
    sources: Dict[str, Any], spans: Optional[List[Span]] = None, otherwise: str = "no_span"
) -> Optional[List[Tuple[str, float]]]:
    """The first chip's idle seconds inside the traced stretch by the
    program's leaf spans of replica 0, most first: what
    ``trace_reduce.name_gaps`` does with the harness's six phases, with the
    program's own names.  Spans of several threads overlap, so every moment
    goes to ONE span (:func:`_rank`)."""
    found = in_stretch(sources, spans=spans)
    stretch = traced_stretch(sources)
    if found is None or stretch is None:
        return None
    mine, _ = found
    a, b, _ = stretch
    device = sources["trace"]["per_device"]
    ops = device[min(device)]["ops"]
    ranked = sorted(leaves(mine), key=lambda s: _rank(s["name"]))
    totals: Dict[str, float] = {}
    for g0, g1 in trace_reduce.idle_gaps(ops, a, b):
        over = [s for s in ranked if s["end"] > g0 and s["start"] < g1]
        cuts = sorted({g0, g1, *(min(max(t, g0), g1) for s in over for t in (s["start"], s["end"]))})
        for c0, c1 in zip(cuts, cuts[1:]):
            owner = next((s["name"] for s in over if s["start"] <= c0 and c1 <= s["end"]), otherwise)
            totals[owner] = totals.get(owner, 0.0) + c1 - c0
    return sorted(totals.items(), key=lambda x: -x[1])


def kernel_ms_per_step(sources: Dict[str, Any], kernel: str) -> Optional[float]:
    """Device milliseconds a step of one replica group's operations NAMED
    ``kernel`` (a ``pallas_call``'s ``name=``) on the first chip.  The name
    is matched at the start of the operation's text: other operations
    mention it as their operand."""
    stretch = traced_stretch(sources)
    if stretch is None:
        return None
    a, b, steps = stretch
    device = sources["trace"]["per_device"]
    ops = trace_reduce.clip(device[min(device)]["ops"], a, b)
    seconds = trace_reduce.matching_seconds(ops, rf"^%?{kernel}\b")
    if seconds <= 0.0:
        return None
    sharing = sources["replicas"] if sources["groups_share_chip"] else 1
    return 1000.0 * seconds / steps / sharing


def flight_events(events: Iterable[Dict[str, Any]], name: str, after: float = float("-inf")) -> List[Dict[str, Any]]:
    """The flight events called ``name`` recorded after host time ``after``."""
    return [e for e in events or [] if e.get("name") == name and e.get("t", 0.0) >= after]


def kill_mean(sources: Dict[str, Any], event: str, field: str, scale: float, survivor: bool = False) -> Optional[float]:
    """Mean over the run's kills of ``field`` (times ``scale``) summed over
    the flight events ``event`` of a kill: the new life's own ring, or the
    survivor's between this kill and the next.  None where no kill has it
    (a program that does not record it)."""
    kill = sources.get("kill")
    if not kill:
        return None
    kills = kill["kills"]
    values = []
    for i, k in enumerate(kills):
        if survivor:
            until = kills[i + 1]["t_kill"] if i + 1 < len(kills) else float("inf")
            events = [e for e in flight_events(kill.get("survivor_events"), event, k["t_kill"]) if e["t"] < until]
        else:
            events = flight_events(k.get("events"), event)
        got = [e[field] for e in events if e.get(field) is not None]
        if got:
            values.append(scale * sum(got))
    return sum(values) / len(values) if values else None


def sources_of_run(series_path: str, bench_dir: Optional[str] = None) -> Dict[str, Any]:
    """What a reader needs of ``sources['trace']`` and ``['window']``, made
    again from a traced run's series file (``ftbench/out/<cell>-...json``)
    and the trace beside it: for the builder's tables, after the run."""
    import json

    bench_dir = bench_dir or os.path.dirname(os.path.abspath(__file__))
    with open(series_path) as f:
        run = json.load(f)
    space = trace_reduce.load(trace_reduce.find_xplane(os.path.join(bench_dir, "out", "trace")))
    marks = trace_reduce.clock_marks(space)
    offset = marks[0][1] - run["marks"]["clock_host"]
    per_device = trace_reduce.summarize(space)
    t0 = max(d["t0"] for d in per_device.values())
    t1 = min(d["t1"] for d in per_device.values())
    replicas = 1 + max(r["replica"] for r in run["series"])
    steps = [
        [
            dict(r, t_exit=r["t_enter"] + r["wall_s"])
            for r in run["series"]
            if r["replica"] == i and r["in_window"]
            and t0 <= r["t_enter"] + offset and r["t_enter"] + r["wall_s"] + offset <= t1
        ]
        for i in range(replicas)
    ]
    return dict(
        trace=dict(per_device=trace_reduce.summarize(space, t0, t1), t0=t0, t1=t1, offset=offset, traced_steps=steps),
        replicas=replicas,
    )


def main(argv: List[str]) -> int:
    """``python -m ftbench.program_spans <series file>``: the idle seconds by
    program span and every span's seconds a step on replica 0."""
    import json

    sources = sources_of_run(argv[1])
    found = in_stretch(sources)
    if found is None:
        print(json.dumps({"spans": None}))
        return 1
    mine, steps = found
    per_step: Dict[str, List[float]] = {}
    for s in mine:
        entry = per_step.setdefault(s["name"], [0, 0.0])
        entry[0] += 1
        entry[1] += s["end"] - s["start"]
    trips = sync_round_trips(sources) or []
    print(json.dumps(dict(
        steps=steps,
        idle_by_span=idle_by_span(sources),
        span_ms_per_step={n: [c / steps, 1000.0 * t / steps] for n, (c, t) in sorted(per_step.items())},
        sync_round_trips_ms=[[1000.0 * w, 1000.0 * u] for w, u in trips],
        peer_skew_ms=[[step, 1000.0 * skew] for step, skew in peer_skew_s(all_in_stretch(sources)[0])],
    )))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv))
