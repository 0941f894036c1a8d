"""The device's operations with the scope each was traced under, read from
the run's trace: the device planes' twin of ``program_spans.py``.

The program names the parts of its compiled step with ``jax.named_scope``s
(``torchft_tpu/obs/spans.py``, ``DEVICE_PARTS``: ``tpuft.embed``,
``tpuft.mixer_proj``, ...).  XLA carries the scope path of every operation
into the compiled program (``op_name``) and the profiler writes it into the
``.xplane.pb`` as the stat ``tf_op`` of the operation's EVENT METADATA, beside
``hlo_category`` and ``source``.  ``jax.profiler.ProfileData`` hands out an
event's own stats only, not its metadata's, so this file reads the protobuf
itself: a reader of the wire format of ``XSpace`` / ``XPlane`` / ``XLine`` /
``XEvent`` / ``XEventMetadata`` / ``XStat`` (tsl's ``xplane.proto``; varints
and length-delimited fields are all it uses), with no import beyond the
standard library.  It walks the file plane by plane, reads the head of each
for its name and takes in only the device planes (``/device:TPU:<n>``): host
planes, which can be most of a file, are sought past by their length.  Once a
process, as ``program_spans.load``.

The "XLA Ops" line is NESTED: a ``while`` or ``conditional`` event lies over
the events of its body.  So every number here is of an operation's OWN time,
its duration less the events nested in it (one sweep with a stack), and own
times add up to the busy union.  From the path:

- ``part``: the innermost ``tpuft.<name>`` on it, or None.  The update
  program is one part whole, so an operation of ``jit(_update)`` is the
  optimizer's where its path has no scope: a persistent compile cache does not
  key on scopes, and hands out the executable a parent commit left there, the
  parent's paths with it, for a program whose lowered text did not change
  (seen on the chip: PERF.md section 6, PR 37).  ``scoped`` says whether the
  path itself names a part;
- ``pass``: ``fwd`` where it holds ``jvp(`` and no ``transpose(``, ``bwd``
  where it holds ``transpose(``, else ``other`` (the update step);
- ``remat``: it holds ``rematted_computation`` (``jax.checkpoint``'s forward
  pass run again inside the backward pass);
- ``kernel``: a Mosaic custom call (the path ends in ``pallas_call``, or the
  operation carries one of the names ``obs/spans.py`` lists).  A kernel has a
  name and a metric of its own; the part metrics leave it out.

A fusion that XLA made of two parts' operations carries ONE path, the
compiler's choice (on the v5e a weight-gradient product fused with the write
of its stacked gradient carries the product's), and is counted there.  A
trace of a program without scopes (a parent commit) holds no
``tpuft.`` anywhere and every reader of this file returns None.

``python -m ftbench.device_scopes ftbench/out/<series file>.json [N]`` after a
``--trace 1`` run prints the table by part, pass and remat (ms a step, share),
the kernels in a row of their own, and under each part its N (5) longest
operations.
"""

from __future__ import annotations

import io
import os
import re
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from ftbench import trace_reduce
from ftbench.sources import traced_stretch

PART = re.compile(r"tpuft\.(\w+)")
UPDATE_PROGRAM = "jit(_update)/"  # ``parallel/hsdp.py`` ``make_update_step``: ``tpuft.optimizer``, whole
# the names the program's kernels carry among a trace's operations
# (``obs/spans.py``, "Device operations with names of their own")
KERNEL_NAMES = re.compile(r"^%?(flash_|kda_|dsa_|ssd_|[\w.\-]*gmm)")
KEPT_STATS = ("tf_op", "hlo_category", "source")

Op = Dict[str, Any]  # name, start, dur_s, own_s, tf_op, category, source, scoped, part, pass, remat, kernel

_LOADED: Dict[str, Dict[int, List[Op]]] = {}
# the last stretch cut: (the plane's operations, start, end, what with_own_time made of them);
# eleven readers ask for the same one, and the sweep over a long trace is a third of a second
_CUT: List[Any] = []


# ----------------------------------------------------------------------
# the wire format
# ----------------------------------------------------------------------


def _fields(buf: Any) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of every field of one message: an int for a
    varint, a memoryview for a length-delimited field; fixed-width fields
    (a double's value) are passed over.  The varints are read in line: a
    long trace has a million fields, and a call apiece doubles the pass."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            c = buf[i]
            i += 1
            key |= (c & 0x7F) << shift
            if c < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0:
            value = shift = 0
            while True:
                c = buf[i]
                i += 1
                value |= (c & 0x7F) << shift
                if c < 0x80:
                    break
                shift += 7
            yield key >> 3, value
        elif wire == 2:
            size = shift = 0
            while True:
                c = buf[i]
                i += 1
                size |= (c & 0x7F) << shift
                if c < 0x80:
                    break
                shift += 7
            yield key >> 3, buf[i : i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")


def _text(value: Any) -> str:
    return bytes(value).decode("utf-8", "replace")


def _map_value(entry: Any) -> Any:
    """The value (field 2) of one entry of a protobuf map."""
    for number, value in _fields(entry):
        if number == 2:
            return value
    return b""


def _plane_name(head: Any) -> str:
    """A plane's name from its first bytes (``id`` and ``name`` lead it)."""
    try:
        for number, value in _fields(head):
            if number == 2:
                return _text(value)
    except IndexError:  # the head ends inside a later field: no name came first
        pass
    return ""


def _stream_varint(stream: Any) -> Optional[int]:
    value = shift = 0
    while True:
        c = stream.read(1)
        if not c:
            return None
        value |= (c[0] & 0x7F) << shift
        if c[0] < 0x80:
            return value
        shift += 7


def _device_plane(plane: Any) -> List[Op]:
    """The events of one plane's "XLA Ops" line, each with the kept stats of
    its metadata; times in seconds on the trace's clock."""
    lines, metadata_entries, stat_names = [], [], {}
    for number, value in _fields(plane):
        if number == 3:
            lines.append(value)
        elif number == 4:
            metadata_entries.append(value)
        elif number == 5:
            ident, name = 0, ""
            for n, v in _fields(_map_value(value)):
                if n == 1:
                    ident = v
                elif n == 2:
                    name = _text(v)
            stat_names[ident] = name
    metadata: Dict[int, Dict[str, str]] = {}
    for entry in metadata_entries:
        ident, kept = 0, {"name": ""}
        for n, v in _fields(_map_value(entry)):
            if n == 1:
                ident = v
            elif n == 2:
                kept["name"] = _text(v)
            elif n == 5:  # an XStat of the metadata
                stat, text = "", None
                for sn, sv in _fields(v):
                    if sn == 1:
                        stat = stat_names.get(sv, "")
                    elif sn == 5:  # str_value
                        text = _text(sv)
                    elif sn == 7:  # ref_value: a string kept as a stat's name
                        text = stat_names.get(sv, "")
                if stat in KEPT_STATS and text is not None:
                    kept[stat] = text
        metadata[ident] = kept
    ops: List[Op] = []
    for line in lines:
        name, timestamp_ns, events = "", 0, []
        for n, v in _fields(line):
            if n == 2:
                name = _text(v)
            elif n == 3:
                timestamp_ns = v
            elif n == 4:
                events.append(v)
        if name != trace_reduce.OPS_LINE:
            continue
        for event in events:
            ident = offset_ps = duration_ps = 0
            for n, v in _fields(event):
                if n == 1:
                    ident = v
                elif n == 2:
                    offset_ps = v
                elif n == 3:
                    duration_ps = v
            meta = metadata.get(ident, {"name": ""})
            ops.append(
                annotate(
                    dict(
                        name=meta["name"],
                        start_ps=timestamp_ns * 1000 + offset_ps,
                        dur_ps=duration_ps,
                        start=(timestamp_ns * 1000 + offset_ps) * 1e-12,
                        dur_s=duration_ps * 1e-12,
                        tf_op=meta.get("tf_op", ""),
                        category=meta.get("hlo_category", ""),
                        source=meta.get("source", ""),
                    )
                )
            )
    return sorted(ops, key=lambda o: (o["start_ps"], -o["dur_ps"]))


def annotate(op: Op) -> Op:
    """``part``, ``pass``, ``remat`` and ``kernel`` from the path and name."""
    path = op["tf_op"]
    found = PART.findall(path)
    op["scoped"] = bool(found)
    op["part"] = found[-1] if found else "optimizer" if path.startswith(UPDATE_PROGRAM) else None
    op["pass"] = "bwd" if "transpose(" in path else "fwd" if "jvp(" in path else "other"
    op["remat"] = "rematted_computation" in path
    op["kernel"] = path.rstrip(":").endswith("pallas_call") or bool(KERNEL_NAMES.match(op["name"]))
    return op


def parse(stream: Any, head_bytes: int = 512) -> Dict[int, List[Op]]:
    """``{chip: operations}`` of a serialized ``XSpace`` (a binary file, or
    bytes); a plane that is no device's is sought past, unread."""
    if isinstance(stream, (bytes, bytearray, memoryview)):
        stream = io.BytesIO(bytes(stream))
    out: Dict[int, List[Op]] = {}
    while True:
        key = _stream_varint(stream)
        if key is None:
            return out
        wire = key & 7
        if wire == 0:
            _stream_varint(stream)
        elif wire == 1 or wire == 5:
            stream.seek(8 if wire == 1 else 4, os.SEEK_CUR)
        elif wire == 2:
            size = _stream_varint(stream) or 0
            head = stream.read(min(size, head_bytes)) if key >> 3 == 1 else b""
            m = trace_reduce.DEVICE_PLANE.match(_plane_name(memoryview(head)))
            if m:
                ops = _device_plane(memoryview(head + stream.read(size - len(head))))
                if ops:
                    out[int(m.group(1))] = ops
            else:
                stream.seek(size - len(head), os.SEEK_CUR)
        else:
            raise ValueError(f"wire type {wire}: not an XSpace")


def load(bench_dir: Optional[str] = None) -> Dict[int, List[Op]]:
    """The device operations of the run's trace, found as the harness finds
    it; ``{}`` where there is no trace or no device plane in it."""
    bench_dir = bench_dir or os.path.dirname(os.path.abspath(__file__))
    path = trace_reduce.find_xplane(os.path.join(bench_dir, "out", "trace"))
    if path is None:
        return {}
    if path not in _LOADED:
        _LOADED.clear()
        with open(path, "rb") as f:
            _LOADED[path] = parse(f)
    return _LOADED[path]


# ----------------------------------------------------------------------
# own time
# ----------------------------------------------------------------------


def with_own_time(ops: Sequence[Op], t0: float, t1: float) -> List[Op]:
    """The operations cut to ``[t0, t1]`` (copies; those wholly outside are
    dropped), each with ``own_s``: its cut duration less that of the
    operations nested in it.  Own times add up to the busy union.  The sweep
    is in whole picoseconds, the file's unit: where one operation ends the
    next begins, and rounding must not nest the two."""
    lo, hi = round(t0 * 1e12), round(t1 * 1e12)
    cut = []
    for op in ops:
        a, b = max(op["start_ps"], lo), min(op["start_ps"] + op["dur_ps"], hi)
        if b > a:
            cut.append(dict(op, start_ps=a, dur_ps=b - a, own_ps=b - a))
    cut.sort(key=lambda o: (o["start_ps"], -o["dur_ps"]))
    stack: List[Op] = []
    for op in cut:
        end = op["start_ps"] + op["dur_ps"]
        while stack and stack[-1]["start_ps"] + stack[-1]["dur_ps"] <= op["start_ps"]:
            stack.pop()
        if stack:
            parent = stack[-1]
            # a child that outlasts its parent (never seen) counts once, in the child
            parent["own_ps"] -= min(end, parent["start_ps"] + parent["dur_ps"]) - op["start_ps"]
        stack.append(op)
    for op in cut:
        op.update(start=op["start_ps"] * 1e-12, dur_s=op["dur_ps"] * 1e-12, own_s=op["own_ps"] * 1e-12)
    return cut


def in_stretch(
    sources: Dict[str, Any], planes: Optional[Dict[int, List[Op]]] = None
) -> Optional[Tuple[List[Op], int]]:
    """(the first chip's operations inside ``sources['trace']``'s traced
    stretch with their own time, the stretch's steps), or None where there
    is no traced stretch or no device plane."""
    stretch = traced_stretch(sources)
    if stretch is None:
        return None
    planes = load() if planes is None else planes
    if not planes:
        return None
    a, b, steps = stretch
    plane = planes[min(planes)]
    if not (_CUT and _CUT[0] is plane and _CUT[1:3] == [a, b]):
        _CUT[:] = [plane, a, b, with_own_time(plane, a, b)]
    ops = _CUT[3]
    return (ops, steps) if ops else None


def _sharing(sources: Dict[str, Any]) -> int:
    return sources["replicas"] if sources.get("groups_share_chip") else 1


def scoped(found: Optional[Tuple[List[Op], int]]) -> bool:
    """Whether the program that made these operations names its parts."""
    return found is not None and any(op["scoped"] for op in found[0])


def own_ms_per_step(
    sources: Dict[str, Any], keep: Any, planes: Optional[Dict[int, List[Op]]] = None
) -> Optional[float]:
    """Own milliseconds a step, of one replica group, of the operations
    ``keep(op)`` holds for; None on a trace of a program without scopes."""
    found = in_stretch(sources, planes)
    if not scoped(found):
        return None
    ops, steps = found
    return 1000.0 * sum(op["own_s"] for op in ops if keep(op)) / steps / _sharing(sources)


def part_ms(sources: Dict[str, Any], *parts: str) -> Optional[float]:
    """What XLA made of ``parts``: own milliseconds a step of their
    operations, the Mosaic kernels left out (they have their metrics)."""
    return own_ms_per_step(sources, lambda op: op["part"] in parts and not op["kernel"])


# ----------------------------------------------------------------------
# the builder's table
# ----------------------------------------------------------------------


def table(ops: Sequence[Op], steps: int, longest: int = 5) -> Dict[str, Any]:
    """By part x pass x remat: ms a step and share of the busy time; the
    kernels in a row of their own; a part's time by ``hlo_category`` and by
    the primitive its path ends in, and its longest operations by summed own
    time, with the tail of their path and their source line."""
    busy = sum(op["own_s"] for op in ops) or 1.0
    rows: Dict[Tuple[str, str, bool], float] = {}
    by_name: Dict[str, Dict[str, List[Any]]] = {}
    by_kind: Dict[str, Dict[str, Dict[str, float]]] = {}
    for op in ops:
        row = "kernels" if op["kernel"] else op["part"] or "unscoped"
        key = (row, op["pass"], op["remat"])
        rows[key] = rows.get(key, 0.0) + op["own_s"]
        primitive = op["tf_op"].rstrip(":").rsplit("/", 1)[-1] or "(no path)"
        for kind, value in (("category", op["category"] or "(none)"), ("primitive", primitive)):
            kinds = by_kind.setdefault(row, {}).setdefault(kind, {})
            kinds[value] = kinds.get(value, 0.0) + 1000.0 * op["own_s"] / steps
        entry = by_name.setdefault(row, {}).setdefault(op["name"][:48], [0.0, 0, op])
        entry[0] += op["own_s"]
        entry[1] += 1
    totals: Dict[str, float] = {}
    for (row, _, _), seconds in rows.items():
        totals[row] = totals.get(row, 0.0) + seconds
    return dict(
        steps=steps,
        busy_ms_per_step=1000.0 * busy / steps,
        rows=[
            dict(part=row, **{"pass": p}, remat=remat, ms_per_step=1000.0 * s / steps, share_pct=100.0 * s / busy)
            for (row, p, remat), s in sorted(rows.items(), key=lambda kv: (-totals[kv[0][0]], kv[0][0], -kv[1]))
        ],
        parts=[dict(part=row, ms_per_step=1000.0 * s / steps, share_pct=100.0 * s / busy)
               for row, s in sorted(totals.items(), key=lambda kv: -kv[1])],
        by_kind=by_kind,
        longest={
            row: [
                dict(name=name, ms_per_step=1000.0 * seconds / steps, calls_per_step=count / steps,
                     category=op["category"], tf_op="/".join(op["tf_op"].split("/")[-4:]), source=op["source"])
                for name, (seconds, count, op) in sorted(names.items(), key=lambda kv: -kv[1][0])[:longest]
            ]
            for row, names in by_name.items()
        },
    )


def main(argv: List[str]) -> int:
    from ftbench import program_spans

    sources = program_spans.sources_of_run(argv[1])
    found = in_stretch(sources)
    if found is None:
        print("no traced stretch, or no device plane in the trace")
        return 1
    t = table(*found, longest=int(argv[2]) if len(argv) > 2 else 5)
    print(f"{t['steps']} steps, busy {t['busy_ms_per_step']:.3f} ms a step (own times summed)")
    print(f"{'part':<18}{'pass':<7}{'remat':<7}{'ms/step':>11}{'share %':>9}")
    for row in t["rows"]:
        print(f"{row['part']:<18}{row['pass']:<7}{'remat' if row['remat'] else '':<7}"
              f"{row['ms_per_step']:>11.3f}{row['share_pct']:>9.2f}")
    for entry in t["parts"]:
        print(f"\n{entry['part']}: {entry['ms_per_step']:.3f} ms a step, {entry['share_pct']:.2f} %")
        for kind, values in t["by_kind"][entry["part"]].items():
            top = sorted(values.items(), key=lambda kv: -kv[1])[:8]
            print(f"  by {kind}: " + ", ".join(f"{name} {ms:.2f}" for name, ms in top))
        for op in t["longest"][entry["part"]]:
            print(f"  {op['ms_per_step']:>9.3f} ms  x{op['calls_per_step']:<6.4g} {op['name']}  [{op['category']}]"
                  f"  {op['tf_op']}  {op['source']}")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv))
