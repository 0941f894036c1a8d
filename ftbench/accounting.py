"""Arithmetic from step stamps and event lists to end-to-end numbers.

Pure functions over plain lists, no JAX: the tests drive them on synthetic
series.  All times are seconds on one monotonic host clock.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence, Tuple


def window_indices(
    commits: Sequence[float], open_index: int, seconds: float
) -> Tuple[int, int]:
    """(open, close) indices into ``commits`` of the whole-step window.

    The window opens AT commit ``open_index`` and closes at the first later
    commit whose stamp is at or after ``seconds`` from the opening.  Steps
    are counted whole: the steps inside are ``open+1 .. close``, each of
    which began at or after the opening commit and ended at the closing one
    or before.  Raises if no commit lies that far out."""
    t_open = commits[open_index]
    for i in range(open_index + 1, len(commits)):
        if commits[i] - t_open >= seconds:
            return open_index, i
    raise ValueError(
        f"no commit at or after {seconds} s from commit {open_index}: "
        f"the series ends {commits[-1] - t_open:.3f} s after it"
    )


def tokens_per_s_per_chip(
    commits_by_replica: Sequence[Sequence[float]],
    open_index: int,
    close_index: int,
    tokens_per_step_per_replica: int,
    chips: int,
) -> float:
    """Committed tokens per second per chip over steps
    ``open_index+1 .. close_index``: every replica's whole steps between its
    own two commit stamps, summed over replicas, over the chips they hold."""
    steps = close_index - open_index
    if steps <= 0:
        raise ValueError("the window holds no whole step")
    rate = 0.0
    for commits in commits_by_replica:
        span = commits[close_index] - commits[open_index]
        rate += steps * tokens_per_step_per_replica / span
    return rate / chips


def step_times(commits: Sequence[float]) -> List[float]:
    return [b - a for a, b in zip(commits, commits[1:])]


def survivor_stall_s(commits: Sequence[float], t_kill: float, around: int = 3) -> float:
    """The survivor's longest gap between two commits around the kill, less
    its median step time away from it.  ``around`` gaps on each side of the
    one that holds the kill are the neighbourhood."""
    gaps = step_times(commits)
    if not gaps:
        raise ValueError("the survivor committed fewer than two steps")
    holding = next(
        (i for i in range(len(gaps)) if commits[i] <= t_kill < commits[i + 1]),
        None,
    )
    if holding is None:
        raise ValueError("the kill lies outside the survivor's commits")
    lo, hi = max(0, holding - around), min(len(gaps), holding + around + 1)
    rest = gaps[:lo] + gaps[hi:]
    if not rest:
        raise ValueError("no steps away from the kill to take a median from")
    return max(gaps[lo:hi]) - statistics.median(rest)


def resume_s(t_kill: float, victim_commits_after_heal: Sequence[float]) -> float:
    """From the kill to the victim's first committed step of its new life."""
    later = [t for t in victim_commits_after_heal if t >= t_kill]
    if not later:
        raise ValueError("the victim never committed after the kill")
    return later[0] - t_kill


def detect_s(t_kill: float, survivor_events: Sequence[Dict]) -> Optional[float]:
    """From the kill to the survivor's first QUORUM_ADOPT after it: that
    quorum is either without the victim (a smaller world) or carries its
    restarted life (a new quorum id at the same world)."""
    for ev in survivor_events:
        if ev.get("name") == "QUORUM_ADOPT" and ev.get("t", 0.0) >= t_kill:
            return ev["t"] - t_kill
    return None


def union_seconds(intervals: Sequence[Tuple[float, float]]) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``, as the driver takes it)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
