"""Each bucket's share of the replica-dimension round trip, from a traced
ftbench run: ``python3 scripts/bucket_spans.py ftbench/out/<series>.json``
(run from the checkout whose ``ftbench/out/trace`` holds the run's trace)
prints, for replica 0 and a step of the traced stretch, the milliseconds of
``tpuft/ddp/{d2h,pack,submit,ring_wait,h2d}`` by ``bucket=`` and of
``tpuft/comm/op`` and ``tpuft/manager/normalize`` in the step's order, and
when each bucket's stages begin, from the step's ``tpuft/ddp/plan``."""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.getcwd())

from ftbench import program_spans  # noqa: E402


def main(series_path: str) -> int:
    bench_dir = os.path.join(os.getcwd(), "ftbench")
    try:
        sources = program_spans.sources_of_run(series_path, bench_dir)
    except ValueError:  # no device plane (a CPU rehearsal): every span counts
        sources = {}
    found = program_spans.in_stretch(sources, spans=program_spans.load(bench_dir))
    if found is None:
        print(json.dumps({"spans": None}))
        return 1
    mine, steps = found
    plan_start = {s["step"]: s["start"] for s in mine if s["name"] == "tpuft/ddp/plan"}
    by_bucket: dict = {}
    for s in mine:
        if "bucket" not in s or s.get("step") not in plan_start:
            continue
        entry = by_bucket.setdefault((s["name"].rsplit("/", 1)[1], int(s["bucket"])), [0.0, 0.0])
        entry[0] += s["end"] - s["start"]
        entry[1] += s["start"] - plan_start[s["step"]]
    n = max(1, len(plan_start))
    stages = sorted({k[0] for k in by_bucket})
    buckets = sorted({k[1] for k in by_bucket})
    out = dict(steps=steps, round_trips=len(plan_start))
    for stage in stages:
        out[stage + "_ms_by_bucket"] = [round(1e3 * by_bucket.get((stage, b), [0, 0])[0] / n, 1) for b in buckets]
        out[stage + "_begins_ms_by_bucket"] = [round(1e3 * by_bucket.get((stage, b), [0, 0])[1] / n, 1) for b in buckets]
    for name in ("tpuft/comm/op", "tpuft/manager/normalize"):
        by_k: dict = {}
        order: dict = {}
        for s in mine:
            if s["name"] != name or s.get("step") not in plan_start:
                continue
            k = order[s["step"]] = order.get(s["step"], -1) + 1
            entry = by_k.setdefault(k, [0.0, 0.0])
            entry[0] += s["end"] - s["start"]
            entry[1] += s["start"] - plan_start[s["step"]]
        out[name.rsplit("/", 1)[1] + "_ms_in_order"] = [round(1e3 * by_k[k][0] / n, 1) for k in sorted(by_k)]
        out[name.rsplit("/", 1)[1] + "_begins_ms_in_order"] = [round(1e3 * by_k[k][1] / n, 1) for k in sorted(by_k)]
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
