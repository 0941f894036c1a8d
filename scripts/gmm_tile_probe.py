"""On the chip: the experts' grouped products (``megablox``'s ``gmm`` and
``tgmm``, ``parallel/moe.py`` ``grouped_product``) at the five expert cells'
shapes, each of a layer's calls over a list of tiles.  A cell's shapes are read
from its file under ``ftbench/configs/``; its rows a layer and its router's
spread (the busiest held expert over the mean) are the ledger's
(``moe_rows_here_per_step`` over the expert layers, ``moe_load_max_over_mean``;
PR 50's lines).  Per call and tiling: the kernel's microseconds a call on the
DEVICE's clock (``_device_us``: the events ``moe_gmm_ms`` reads), grid steps,
the scoped memory ``moe.grouped_vmem`` reckons, and the share of the call's
roofline (the larger of the routed rows' product over 197 TFLOP/s and the
rows', the visited experts' matrices' and the result's bytes over 819 GB/s).

    chiprun -- python3 scripts/gmm_tile_probe.py --tiles rule,old          # 60 programs, 3 minutes
    chiprun --timeout 2400 -- python3 scripts/gmm_tile_probe.py --row-tiles 128,256 \
        --vmem-slack 3 --least-product 16 --out chiprun_out/gmm_tiles.jsonl   # 330 programs, 9 minutes

A layer's calls are named by kind and by the matrix they serve: ``fwd.in``
(rows [m, dim] through ``w_up`` / ``w_gate`` [dim, hidden]), ``fwd.out``
(through ``w_down``), ``bwd.in`` / ``bwd.out`` (the gradient to the rows:
``gmm`` with the matrix transposed), ``tgmm.in`` / ``tgmm.out`` (the gradient
to the matrices; the rows are the contracted dimension).  ``--tiles sweep``
(the default) times every candidate of ``_candidates``, ``--tiles rule,old``
only what ``moe.grouped_tiles`` answers beside PR 29's constant
``(128, 256, 256)``, and sums a step's calls into milliseconds a step
(``step_ms``: forward twice, where ``_held_part``'s backward walks its passes
again, and each backward call once; a layer that is rematerialised runs the
forward a third time, which the sum leaves out as the roofline readers do).
``--compile-only`` compiles the candidates for a described v5e without one
(``JAX_PLATFORMS=cpu``): what Mosaic refuses for its scoped memory costs no
chip time; a kernel ALONE compiles where the same kernel inside a step's
program is refused (PERF.md section 6, PR 51), so the step's own compile
(``tests/test_ftbench_compile_*.py``) is what holds the rule.  ``--toy`` walks
the script on the CPU in interpret mode at small shapes, without times.  The
last line is ``PROBE {...}``."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental.pallas.ops.tpu.megablox.ops import backend  # noqa: E402  (the kernels' module; the package's ``gmm`` is the function)

from torchft_tpu.parallel import moe  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# cell: its configuration's file, tokens a step, rows a layer on the held
# experts and the busiest held expert over the mean (ledger, PR 50), expert
# layers a step
CELLS = {
    "keye": ("keye-vl-2.0-30b-a3b-ep8-1x1", 16384, 16283, 1.19, 10),
    "nemotron": ("nemotron-3-nano-30b-a3b-ep8-1x1", 16384, 12503, 1.62, 4),
    "trinity": ("trinity-mini-ep8-1x1", 16384, 16463, 1.72, 7),
    "joyai": ("joyai-llm-flash-ep16-1x1", 16384, 8190, 1.05, 7),
    "ling": ("ling-3.0-flash-ep32-1x1", 8192, 1728, 1.87, 6),
}
OLD = (128, 256, 256)
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9  # one v5e chip, bfloat16 (Google Cloud, "TPU v5e")
CALLS = ("fwd.in", "fwd.out", "bwd.in", "bwd.out", "tgmm.in", "tgmm.out")


def cell_shapes(name):
    """``(m, dim, hidden, held, matrices an expert)`` of a cell: the buffer's
    static size as ``_held_part`` reckons it, and the configuration's widths."""
    file, tokens, _, _, _ = CELLS[name]
    with open(os.path.join(ROOT, "ftbench", "configs", file + ".json")) as f:
        cfg = json.load(f)
    held = cfg["experts_held"][1]
    m = moe.buffer_size(tokens, cfg["num_experts_per_tok"], held, cfg["router_experts"])
    matrices = 2 if cfg.get("mlp_hidden_act") == "relu2" else 3
    return m, cfg["hidden_size"], cfg["moe_intermediate_size"], held, matrices


def sizes_of(rows, held, spread, seed=0):
    """``held`` group sizes that add up to ``rows`` with the busiest at
    ``spread`` times the mean: a seeded normal draw, sharpened until it is."""
    z = np.random.default_rng(seed).standard_normal(held)
    lo, hi = 0.0, 8.0
    for _ in range(60):
        s = (lo + hi) / 2
        p = np.exp(s * z) / np.exp(s * z).sum()
        lo, hi = (s, hi) if p.max() * held < spread else (lo, s)
    sizes = np.floor(p * rows).astype(np.int32)
    sizes[np.argmax(sizes)] += rows - sizes.sum()
    return sizes


def call_shape(call, dim, hidden):
    """``(kind, k, n)`` as ``moe.grouped_tiles`` is asked: the contracted and
    the result's width of a ``gmm``, the result's two widths of a ``tgmm``."""
    kind, matrix = call.split(".")
    k, n = (dim, hidden) if matrix == "in" else (hidden, dim)
    return ("tgmm", k, n) if kind == "tgmm" else ("gmm", k, n) if kind == "fwd" else ("gmm_t", n, k)


def _candidates(kind, m, k, n, slack=1.0, row_tiles=(128, 256, 512), least=32):
    """The tiles to time for one call: row tiles of 128 to 512 that divide the
    buffer, times the rule's own pieces of k and n (``moe._pieces``: a width
    whole, in equal parts that are multiples of 128, and 1,024, 512 and 256
    with a last tile partly empty), where the reckoned scoped memory fits
    (``slack`` times it: what the compiler then refuses is a line too) and a
    grid step holds at least ``least`` (32) times the old constant's product."""
    out = [OLD]
    for tm in row_tiles:
        if m % tm:
            continue
        for tk in (t for t in moe._pieces(k) if t >= 256):
            for tn in (t for t in moe._pieces(n) if t >= 256):
                if moe.grouped_vmem(kind, tm, tk, tn) > slack * moe.SCOPED_VMEM or tm * tk * tn < least * 128 * 256 * 256:
                    continue
                out.append((tm, tk, tn))
    return out


def grid_steps(kind, sizes, m, k, n, tiles):
    """The grid steps of one call, as ``megablox`` lays its grid: the active
    row tiles (a tile is visited once for each group with rows in it; ``tgmm``
    visits an empty group once too) times the tiles of the other two."""
    tm, tk, tn = tiles
    ends = np.cumsum(sizes)
    starts = ends - sizes
    visits = np.where(sizes > 0, -(-ends // tm) - starts // tm, 1 if kind == "tgmm" else 0).sum()
    return int(visits) * -(-k // tk) * -(-n // tn)


def floor_s(sizes, k, n):
    """The least time of one call on the chip: the routed rows' product, or
    the bytes of the rows, of the matrices of the experts that have rows and of
    the result (the same count serves all three kinds)."""
    rows, live = int(sizes.sum()), int((sizes > 0).sum())
    return max(2 * rows * k * n / PEAK_FLOPS, 2 * (rows * k + live * k * n + rows * n) / PEAK_BYTES)


def program(kind, tiles, interpret):
    """One call as a jitted program of ``(a, b, sizes)``."""
    if kind == "tgmm":  # a [m, k] rows, b [m, n] gradients -> [held, k, n]
        return jax.jit(lambda a, b, s: backend.tgmm(a.swapaxes(0, 1), b, s, a.dtype, tiles, interpret=interpret))
    t = kind == "gmm_t"  # a [m, k], b [held, k, n], or [held, n, k] where it is transposed
    return jax.jit(lambda a, b, s: backend.gmm(a, b, s, a.dtype, tiles, transpose_rhs=t, interpret=interpret))


def operands(kind, m, k, n, held, seed, dtype, struct=None):
    make = struct or (lambda key, dims: jax.random.normal(key, dims, jnp.float32).astype(dtype))
    ka, kb = (None, None) if struct else jax.random.split(jax.random.PRNGKey(seed))
    second = (m, n) if kind == "tgmm" else (held, n, k) if kind == "gmm_t" else (held, k, n)
    return make(ka, (m, k)), make(kb, second)


def _device_us(fn, args, rounds):
    """Microseconds a call of ``fn`` on the DEVICE's clock: ``rounds`` calls
    inside a profiler session of their own, and the first chip's ``gmm`` /
    ``tgmm`` kernels' events summed (the pattern and the plane ``moe_gmm_ms``
    reads).  The host's clock around a call of a quarter of a millisecond read
    the dispatch, 15 % apart in two sweeps (PERF.md section 6, PR 51)."""
    import tempfile

    from ftbench import trace_reduce
    from ftbench.layer_metrics._ling import GMM

    with tempfile.TemporaryDirectory() as folder:
        jax.profiler.start_trace(folder)
        for _ in range(rounds):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        device = trace_reduce.device_planes(trace_reduce.load(trace_reduce.find_xplane(folder)))
    return 1e6 * trace_reduce.matching_seconds(device[min(device)][trace_reduce.OPS_LINE], GMM) / rounds


def _tilings(choice, kind, m, k, n, held, args):
    if choice == "sweep":
        return _candidates(kind, m, k, n, args.vmem_slack, [int(t) for t in args.row_tiles.split(",")], args.least_product)
    named = {"old": OLD, "rule": moe.grouped_tiles(kind, m, k, n, held)}
    return [named[c] for c in choice.split(",")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--calls", default=",".join(CALLS))
    ap.add_argument("--tiles", default="sweep", help="sweep, or a list of rule and old")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="", help="a file for the lines, one a timing, flushed")
    ap.add_argument("--vmem-slack", type=float, default=1.0, help="sweep up to this many times the reckoned limit")
    ap.add_argument("--row-tiles", default="128,256,512", help="the sweep's row tiles")
    ap.add_argument("--least-product", type=int, default=32, help="the sweep leaves out tiles under this many times the old constant's product")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--toy", action="store_true")
    args = ap.parse_args()
    interpret, dtype, chip = False, jnp.bfloat16, None
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    elif args.toy:
        interpret, dtype = True, jnp.float32
    elif jax.devices()[0].platform != "tpu":
        sys.exit("no TPU: --toy walks the script on the CPU, --compile-only compiles for a described chip")
    sink = open(args.out, "w") if args.out else None
    report = {}
    for name in args.cells.split(","):
        m, dim, hidden, held, matrices = cell_shapes(name)
        _, _, rows, spread, layers = CELLS[name]
        if args.toy:
            m, dim, hidden, rows = 512, 384, 200, 300
        sizes = sizes_of(rows, held, spread, args.seed)
        step_us = {}
        for call in args.calls.split(","):
            kind, k, n = call_shape(call, dim, hidden)
            floor = floor_s(sizes, k, n)
            # a step's calls of this kind: one a matrix; the forward runs twice
            times = (matrices - 1 if call.endswith(".in") else 1) * (2 if call.startswith("fwd") else 1)
            if args.compile_only:
                struct = lambda _, dims: jax.ShapeDtypeStruct(dims, dtype, sharding=chip)  # noqa: E731
                given = (*operands(kind, m, k, n, held, args.seed, dtype, struct), jax.ShapeDtypeStruct((held,), jnp.int32, sharding=chip))
            else:
                given = (*operands(kind, m, k, n, held, args.seed, dtype), jnp.asarray(sizes))
            for tiles in _tilings(args.tiles, kind, m, k, n, held, args):
                if args.toy:
                    tiles = tuple(min(t, 128) for t in tiles)
                line = dict(
                    cell=name, call=call, m=m, k=k, n=n, tiles=tiles, steps=grid_steps(kind, sizes, m, k, n, tiles),
                    vmem_mb=round(moe.grouped_vmem(kind, *tiles) / 2**20, 2),
                )
                fn = program(kind, tiles, interpret)
                try:  # what the compiler refuses is a finding, not a failure
                    if args.compile_only:
                        fn.lower(*given).compile()
                        line["compiles"] = True
                    else:
                        jax.block_until_ready(fn(*given))
                        if not args.toy:
                            us = _device_us(fn, given, args.rounds)
                            line.update(us=round(us, 1), roofline_pct=round(100 * floor * 1e6 / us, 1))
                            side = "old" if tuple(tiles) == OLD else "new"
                            step_us[side] = step_us.get(side, 0.0) + times * layers * us
                except Exception as e:
                    line["refused"] = (str(e).strip().splitlines() or [repr(e)])[-1][:200]
                print(json.dumps(line), flush=True)
                if sink:
                    sink.write(json.dumps(line) + "\n")
                    sink.flush()
        report[name] = dict(sizes=sizes.tolist(), vmem_limit=moe.SCOPED_VMEM)
        if args.tiles != "sweep" and not args.compile_only:
            report[name]["step_ms"] = {k: round(v / 1e3, 2) for k, v in step_us.items()}
    device = jax.devices()[0]
    print("PROBE", json.dumps(dict(device=dict(platform=device.platform, kind=device.device_kind), cells=report)))


if __name__ == "__main__":
    main()
