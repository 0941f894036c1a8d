"""From the profiler's ``.xplane.pb`` to busy time, idle gaps and op times.

Only ``jax.profiler.ProfileData`` is needed to read the file.  Everything
after :func:`load` works on plain tuples, so the tests drive it on a small
recorded trace and on synthetic planes.  Times are seconds on the trace's
own clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from ftbench.accounting import union_seconds

Event = Tuple[str, float, float]  # name, start_s, duration_s

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CLOCK_MARK = "ftbench_clock"


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    return found[-1] if found else None


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def from_profile(space: Any) -> Dict[str, Dict[str, List[Event]]]:
    """``{plane name: {line name: [(event name, start_s, duration_s)]}}`` of
    a ``jax.profiler.ProfileData``.  Lines of one name within a plane (host
    threads) are merged."""
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in space.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return out


def device_planes(space: Dict[str, Dict[str, List[Event]]]) -> Dict[int, Dict[str, List[Event]]]:
    out = {}
    for name, lines in space.items():
        m = DEVICE_PLANE.match(name)
        if m:
            out[int(m.group(1))] = lines
    return out


def clip(events: Iterable[Event], t0: float, t1: float) -> List[Event]:
    """Events cut to ``[t0, t1]``; those wholly outside are dropped."""
    out = []
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_seconds(events: Iterable[Event]) -> float:
    """Union of the intervals in which an operation ran."""
    return union_seconds([(s, s + d) for _, s, d in events])


def idle_gaps(events: Iterable[Event], t0: float, t1: float) -> List[Tuple[float, float]]:
    """``(start, end)`` of every stretch of ``[t0, t1]`` no event covers."""
    gaps, reach = [], t0
    for _, start, dur in sorted(clip(events, t0, t1), key=lambda e: e[1]):
        if start > reach:
            gaps.append((reach, start))
        reach = max(reach, start + dur)
    if t1 > reach:
        gaps.append((reach, t1))
    return gaps


def op_totals(events: Iterable[Event]) -> Dict[str, float]:
    totals: Dict[str, float] = {}
    for name, _, dur in events:
        totals[name] = totals.get(name, 0.0) + dur
    return totals


def matching_seconds(events: Iterable[Event], pattern: str) -> float:
    """Summed device time of the events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(d for name, _, d in events if rx.search(name))


def clock_marks(space: Dict[str, Dict[str, List[Event]]]) -> List[Event]:
    """The runner's clock marks, wherever the host planes hold them."""
    marks = []
    for name, lines in space.items():
        if DEVICE_PLANE.match(name):
            continue
        for events in lines.values():
            marks.extend(e for e in events if e[0].startswith(CLOCK_MARK))
    return sorted(marks, key=lambda e: e[1])


def transitions(
    modules: Iterable[Event], first: str, then: str
) -> List[Tuple[float, float]]:
    """``(end of the last `first` program, start of the next `then`
    program)`` for every place where a program matching ``then`` follows
    one matching ``first`` on a device: the stretch the device waited
    between the two."""
    rx_a, rx_b = re.compile(first), re.compile(then)
    out, run_end = [], None  # run_end: where the current run of `first` ends
    for name, start, dur in sorted(modules, key=lambda e: e[1]):
        if rx_a.search(name):
            run_end = start + dur if run_end is None else max(run_end, start + dur)
        elif rx_b.search(name):
            if run_end is not None:
                out.append((run_end, start))
            run_end = None
    return out


def name_gaps(
    gaps: Sequence[Tuple[float, float]],
    phases: Sequence[Tuple[str, float, float]],
    otherwise: str = "host_between_steps",
) -> List[Tuple[str, float]]:
    """Cut each idle gap at the host phases ``(name, start, end)`` that lie
    over it and name the pieces; what no phase covers is ``otherwise``.
    Phases of ONE host thread, so that they do not overlap.  Returns
    ``(name, seconds)`` of every piece, longest first."""
    named = []
    for g0, g1 in gaps:
        covered = 0.0
        for name, p0, p1 in phases:
            overlap = min(g1, p1) - max(g0, p0)
            if overlap > 0.0:
                named.append((name, overlap))
                covered += overlap
        if g1 - g0 - covered > 1e-9:
            named.append((otherwise, g1 - g0 - covered))
    return sorted(named, key=lambda x: -x[1])


def gap_totals(named: Sequence[Tuple[str, float]]) -> List[Tuple[str, float]]:
    """Idle seconds by what the host was doing, most first."""
    totals: Dict[str, float] = {}
    for name, seconds in named:
        totals[name] = totals.get(name, 0.0) + seconds
    return sorted(totals.items(), key=lambda x: -x[1])


def summarize(
    space: Dict[str, Dict[str, List[Event]]],
    t0: Optional[float] = None,
    t1: Optional[float] = None,
) -> Dict:
    """Per device: op events and module events inside ``[t0, t1]`` (the
    whole trace where not given), busy seconds and idle gaps."""
    planes = device_planes(space)
    per_device = {}
    for idx, lines in sorted(planes.items()):
        ops = lines.get(OPS_LINE, [])
        if not ops:
            continue
        lo = min(s for _, s, _ in ops) if t0 is None else t0
        hi = max(s + d for _, s, d in ops) if t1 is None else t1
        cut = clip(ops, lo, hi)
        per_device[idx] = {
            "t0": lo,
            "t1": hi,
            "ops": cut,
            "modules": clip(lines.get(MODULES_LINE, []), lo, hi),
            "busy_s": busy_seconds(cut),
            "gaps": idle_gaps(cut, lo, hi),
        }
    return per_device
