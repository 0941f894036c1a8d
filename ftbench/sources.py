"""What a per-layer reader is handed, and helpers to take numbers from it.

``read(sources)`` of a file under ``layer_metrics/`` gets one dict:

- ``window``: per replica, the steps of the window as dicts (``life``,
  ``step``, ``committed``, ``t_enter``, ``t_exit``, ``loss``,
  ``quorum_exit``, ``commit`` (enter, exit), ``ring`` [(submit, done)],
  ``quorum_rpc_s``), host clock (``time.monotonic``);
- ``trace``: None, or ``per_device`` {chip: ops, modules, busy_s, gaps},
  ``t0``/``t1``, ``offset`` (host clock to trace clock) and
  ``traced_steps`` (the window's steps that lie whole inside the trace);
- counters: ``peak_bytes``, ``grad_bytes_per_replica``, ``lane_tx`` (per
  replica, bytes sent at ``open`` and ``final``), ``open_step``,
  ``close_step``, ``final_step``;
- ``architecture``: the module ``architectures/<name>.py`` the cell's
  configuration names (``spec.load_architecture``).  Its ``flops`` is the
  class that counts operations and bytes from ``shapes`` (``is_mine``,
  ``train_flops_per_token`` and, where the architecture has them, ``gmm_step``
  and ``flash_step``): ``step_mfu_pct``, ``moe_gmm_roofline`` and
  ``flash_roofline`` are ONE reader each through it (:func:`arch_flops`);
- shapes: ``shapes`` (what the cell's architecture file gives:
  ``architectures/<name>.py``, ``shapes(config)``), ``seq``,
  ``rows_per_replica``, ``tokens_per_step_per_replica``, ``chips``,
  ``replicas``, ``groups_share_chip``, ``device_kind``;
- ``flight``: replica by replica, the flight events of the life the run
  ended with (``comm.flight.snapshot()``), in EVERY cell: whatever the
  program counts and records as a flight event (a later architecture's
  tokens an expert saw, say) reaches a new reader with no edit here;
- ``kill``: None, or ``kills`` (one dict a kill: ``t_kill``, and from the
  new life ``first_commit``, ``back_step``, ``timings`` (its
  ``last_quorum_timings``), ``heal`` (bytes, seconds; None unless striped)
  and ``events`` (its flight events)), ``survivor_events``,
  ``survivor_commits`` and ``state_bytes``.

A reader that finds nothing to read returns None and the metric is left
out of the line.
"""

from __future__ import annotations

import statistics
from typing import Any, Callable, Dict, List, Optional, Tuple

from ftbench import spec, trace_reduce


def split_for(name: str, moves: str) -> Tuple[Dict[str, str], Callable[[Dict[str, Any]], Optional[float]]]:
    """``(META, read)`` of metric ``name`` for a twin that is reported in
    cells with another end-to-end metric: the same reader, another
    ``moves``."""
    import os

    base = spec.load_metric(name, os.path.dirname(os.path.abspath(__file__)))
    return dict(base.META, moves=moves), base.read


def arch_flops(sources: Dict[str, Any], method: str) -> Optional[Callable[..., Any]]:
    """``method`` of the ``flops`` class of the cell's architecture, or None
    where the sources name no architecture or its class has no such method
    (an architecture with no experts has no ``gmm_step``)."""
    return getattr(getattr(sources.get("architecture"), "flops", None), method, None)


def chips_per_group(sources: Dict[str, Any]) -> int:
    """The chips ONE replica group's rows and tokens are shared out over."""
    return 1 if sources["groups_share_chip"] else sources["chips"] // sources["replicas"]


def all_steps(sources: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [r for w in sources["window"] for r in w if r["committed"]]


def mean_ms(values: List[float]) -> Optional[float]:
    return 1000.0 * statistics.fmean(values) if values else None


def traced_stretch(sources: Dict[str, Any]) -> Optional[Tuple[float, float, int]]:
    """(start, end, steps) on the trace's clock: from the first traced
    step's entry to the last one's exit on replica 0."""
    trace = sources.get("trace")
    if not trace or not trace["traced_steps"] or not trace["traced_steps"][0]:
        return None
    steps = trace["traced_steps"][0]
    return steps[0]["t_enter"] + trace["offset"], steps[-1]["t_exit"] + trace["offset"], len(steps)


def device_busy_s(sources: Dict[str, Any]) -> Optional[Tuple[float, float, int]]:
    """(busy seconds averaged over the chips, length, steps) of the traced
    stretch."""
    stretch = traced_stretch(sources)
    if stretch is None:
        return None
    a, b, steps = stretch
    per_device = sources["trace"]["per_device"].values()
    busy = statistics.fmean(
        trace_reduce.busy_seconds(trace_reduce.clip(d["ops"], a, b)) for d in per_device
    )
    return busy, b - a, steps


def step_device_s(sources: Dict[str, Any]) -> Optional[float]:
    """Device seconds of ONE replica group's step on one of its chips."""
    busy = device_busy_s(sources)
    if busy is None:
        return None
    sharing = sources["replicas"] if sources["groups_share_chip"] else 1
    return busy[0] / busy[2] / sharing
