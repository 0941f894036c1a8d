"""On the chip: ``ops/flash_attention.py``'s three kernels at the cells' head
shapes and at one without causality (ring attention's off-diagonal step, which
no cell runs), on the same operands from the parent's programs (a checkout of
the commit before PR 50 under ``--parent``, where that directory is there) and
from the change's.  Each kernel's time a launch and a live grid step, whether
``o``, ``lse``, ``dq``, ``dk`` and ``dv`` are EQUAL across them, bit for bit,
and beside that flag the largest absolute and relative difference of each
(since PR 64 the forward sums a row's denominator in another order than its
parent, so ``o`` and ``lse`` agree to float32's rounding of that sum and are
no longer equal; a forward's ``lse`` is compared as ``[B, H, S]`` whichever
way it left the kernel).

    chiprun -- python3 scripts/flash_walk_probe.py --parent _parent

``--compile-only`` compiles the change's programs, and the ``--variants``',
for a described v5e chip without one (``JAX_PLATFORMS=cpu``): what Mosaic
refuses (the tables' SMEM at a group of 16) costs no chip time.  ``--toy``
walks the script on the CPU in interpret mode at 512 positions.  ``--variants name=path`` times further
copies of the module beside them (PR 50 timed a second, maskless body for the
blocks wholly under the diagonal so: it gained nothing, PERF.md section 6).
The last line is ``PROBE {...}``."""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from torchft_tpu.ops import flash_attention as change  # noqa: E402

# cell: positions, query heads, key heads, q/k head size, v head size, window, causal
SHAPES = {
    "joyai": (16384, 32, 32, 192, 128, None, True),
    "trinity_full": (16384, 32, 4, 128, 128, None, True),
    "trinity_win": (16384, 32, 4, 128, 128, 2048, True),
    "nemotron": (16384, 32, 2, 128, 128, None, True),
    "ling": (8192, 32, 32, 192, 128, None, True),
    "mistral": (2048, 32, 8, 128, 128, None, True),
    "ouro": (16384, 16, 16, 128, 128, None, True),
    "phi4miniflash": (16384, 40, 20, 64, 128, None, True),
    "qwen3next": (16384, 16, 2, 256, 256, None, True),
    "ring_off_diagonal": (8192, 32, 8, 128, 128, None, False),  # Mistral's heads on a ring's other chunk
}
BLOCK = 512


def _load(path):
    """Another copy of the module (the parent's, a variant's), or None."""
    if not os.path.exists(path):
        return None
    name = "flash_attention_at_" + "".join(c if c.isalnum() else "_" for c in path)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _programs(module, shape, block, interpret):
    """The three launches of ``module`` as jitted programs over heads-major
    operands: forward -> (o, lse); dq; (dk, dv)."""
    S, H, KV, D, Dv, window, causal = shape
    scale = 1.0 / float(np.sqrt(D))
    fwd = jax.jit(lambda q, k, v: module._fwd(q, k, v, scale, causal, block, block, interpret, window))

    def bwd(q, k, v, o, lse, do):
        if lse.ndim == 3:  # since PR 64 the forward hands out a row statistic as [B, H, S]
            lse = jnp.broadcast_to(lse[..., None], (*lse.shape, module._ROW_LANES))
        return module._bwd(scale, causal, block, block, interpret, (q, k, v, o, lse), do, window=window)

    dq = jax.jit(lambda *a: bwd(*a)[0])
    dkv = jax.jit(lambda *a: bwd(*a)[1:])
    return fwd, dq, dkv


def _operands(shape, seed, dtype):
    S, H, KV, D, Dv, _, _ = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    normal = lambda key, dims: jax.random.normal(key, dims, jnp.float32).astype(dtype)  # noqa: E731
    return (
        normal(ks[0], (1, H, S, D)), normal(ks[1], (1, KV, S, D)), normal(ks[2], (1, KV, S, Dv)),
        normal(ks[3], (1, H, S, Dv)),
    )


def _ms(fn, args, rounds):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(rounds):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / rounds


def _rows(lse):
    """A forward's row statistic as ``[B, H, S]``, whichever way it left."""
    return lse[..., 0] if lse.ndim == 4 else lse


def _differences(got, want):
    """The largest absolute difference of each of ``o``, ``lse``, ``dq``,
    ``dk``, ``dv`` and the largest relative one (over the entries of ``want``
    that are not tiny beside its largest)."""
    out = {}
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)  # exact for bfloat16 and float32 outputs
        gap = np.abs(a - b)
        large = np.abs(b) > 1e-3 * np.abs(b).max()
        out[name] = dict(abs=float(gap.max()), rel=float((gap[large] / np.abs(b[large])).max(initial=0.0)))
    return out


def _grid_steps(shape, block):
    """The grid steps a (head, launch) of forward and ``dq``, and a KV head's
    of ``dkv``."""
    S, H, KV, _, _, window, causal = shape
    blocks = (S // block, S // block, block, block, H // KV, window, causal)
    return dict(steps=int(np.prod(change._row_launch(*blocks)[0])), dkv_steps=int(np.prod(change._key_launch(*blocks)[0])))


def _compile_only(names, block, modules):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    report = {}
    for name in names:
        S, H, KV, D, Dv, _, _ = shape = SHAPES[name]
        struct = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=chip)  # noqa: E731
        q, k, v, do = struct(1, H, S, D), struct(1, KV, S, D), struct(1, KV, S, Dv), struct(1, H, S, Dv)
        lse = jax.ShapeDtypeStruct((1, H, S), jnp.float32, sharding=chip)
        t0 = time.perf_counter()
        for module in modules:
            fwd, dq, dkv = _programs(module, shape, block, False)
            fwd.lower(q, k, v).compile()
            dq.lower(q, k, v, do, lse, do).compile()
            dkv.lower(q, k, v, do, lse, do).compile()
        report[name] = dict(compile_s=round(time.perf_counter() - t0, 1), **_grid_steps(shape, block))
        print(name, report[name], flush=True)
    print("PROBE", json.dumps(dict(compiled_for="v5e, described", shapes=report)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default="_parent")
    ap.add_argument("--variants", default="", help="name=path,...: further copies of the module, timed beside the two")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--toy", action="store_true")
    args = ap.parse_args()
    names = args.shapes.split(",")
    variants = {n: _load(p) for n, p in (v.split("=") for v in args.variants.split(",") if v)}
    if args.compile_only:
        return _compile_only(names, BLOCK, [change, *variants.values()])
    interpret, block, dtype = False, BLOCK, jnp.bfloat16
    if args.toy:
        interpret, block, dtype = True, 128, jnp.float32
    elif jax.devices()[0].platform != "tpu":
        sys.exit("no TPU: --toy walks the script on the CPU, --compile-only compiles for a described chip")
    modules = {"parent": _load(os.path.join(args.parent, "torchft_tpu", "ops", "flash_attention.py"))}
    modules.update(variants)
    report = {}
    for name in names:
        shape = SHAPES[name]
        if args.toy:
            S, H, KV, D, Dv, window, causal = shape
            shape = (512, 4, max(1, 4 * KV // H), 64, 32, None if window is None else 200, causal)
        q, k, v, do = _operands(shape, args.seed, dtype)
        line, outputs = {}, {}
        grid = _grid_steps(shape, block)
        steps = dict(fwd=shape[1] * grid["steps"], dq=shape[1] * grid["steps"], dkv=shape[2] * grid["dkv_steps"])
        for stage in (*modules, "change"):
            module = modules.get(stage, change)
            if module is None:
                continue
            fwd, dq, dkv = _programs(module, shape, block, interpret)
            o, lse = fwd(q, k, v)
            back = (q, k, v, o, lse, do)
            outputs[stage] = [np.asarray(a) for a in (o, _rows(lse), dq(*back), *dkv(*back))]
            if not args.toy:  # ms a launch, and us a live grid step beside it
                ms = dict(fwd=_ms(fwd, (q, k, v), args.rounds), dq=_ms(dq, back, args.rounds), dkv=_ms(dkv, back, args.rounds))
                line[stage] = {f"{n}_ms": t for n, t in ms.items()} | {f"{n}_us_a_step": 1e3 * t / steps[n] for n, t in ms.items()}
        others = {stage: got for stage, got in outputs.items() if stage != "change"}
        line["equal_bit_for_bit"] = {
            stage: all(np.array_equal(a, b) for a, b in zip(got, outputs["change"])) for stage, got in others.items()
        }
        line["largest_difference"] = {stage: _differences(outputs["change"], got) for stage, got in others.items()}
        line["grid"] = grid
        report[name] = line
        print(name, json.dumps(line), flush=True)
    device = jax.devices()[0]
    print("PROBE", json.dumps(dict(device=dict(platform=device.platform, kind=device.device_kind), shapes=report)))


if __name__ == "__main__":
    main()
