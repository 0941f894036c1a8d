"""On the chip: the kernels of ``ops/indexed_attention.py`` against the plain
path at a length the dense arrays fit, and each kernel's time at the cell's
length.  ``chiprun -- python3 scripts/indexed_attention_probe.py``; the last
line is ``PROBE {...}``.

``--parent _parent`` (a checkout of another commit, ``git archive``) times
that copy's ``dsa_attn_fwd``, its backward launches and ``dsa_probs`` beside
the tree's on the same operands, in turn (parent, tree, tree, parent).  Since
PR 68 the tree's backward is ONE launch (``attn_bwd_ms``; the trace knows it as
``dsa_attn_dkv``) and a parent from before it has two (``parent_attn_dq_ms``,
``parent_attn_dkv_ms``): the report gives each and, as ``us_a_step``, each
over the launch's grid steps.  The backward launches and ``dsa_probs`` of both
are handed the TREE's ``o`` and ``lse``, and the report says whether ``dq``,
``dk``, ``dv`` and ``L_I``'s four are EQUAL bit for bit; of ``o`` and ``lse`` it
gives the LARGEST DIFFERENCE from the parent's (since PR 65 the forward's
tile lies keys-major and sums a row's denominator in another order: ``o`` in
steps of its own type, ``lse`` absolute and as ``[B, H, S]`` whichever way it
left the kernel).  ``--variants name=path,...`` times the forward and the
backward of further copies of the module beside them (PR 65 read the parent's
forward without its two lane reductions so; PR 68 its one backward launch
with a product or the resident accumulations taken out).
``--compile-only`` compiles the tree's launches, and the variants', for a
described v5e without one (``JAX_PLATFORMS=cpu``)."""

from __future__ import annotations

import argparse
import functools
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

from torchft_tpu.ops import indexed_attention as ia  # noqa: E402


def operands(seq, seed, dtype, heads=32, kv=4, dim=128, index_heads=16, index_dim=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda k, shape: jax.random.normal(k, shape, jnp.float32)  # noqa: E731
    return (
        normal(ks[0], (1, seq, heads, dim)).astype(dtype),
        normal(ks[1], (1, seq, kv, dim)).astype(dtype),
        normal(ks[2], (1, seq, kv, dim)).astype(dtype),
        normal(ks[3], (1, seq, index_heads, index_dim)).astype(dtype),
        normal(ks[4], (1, seq, index_dim)).astype(dtype),
        normal(ks[5], (1, seq, index_heads)) / 32.0,
    )


def kernels(topk, interpret=False):
    def f(q, k, v, qi, ki, w):
        mask, lse_i, n = ia.select_keys(qi, ki, w, topk=topk, interpret=interpret)
        o, kl = ia.indexed_attention(q, k, v, qi, ki, w, mask, lse_i, interpret=interpret)
        return jnp.sum(o.astype(jnp.float32) ** 2) + kl, (o, kl, jnp.mean(n))

    return f


def plain(topk):
    def f(q, k, v, qi, ki, w):
        o, kl, n = ia.indexed_attention_plain(q, k, v, qi, ki, w, topk=topk)
        return jnp.sum(o.astype(jnp.float32) ** 2) + kl, (o, kl, jnp.mean(n))

    return f


def _load(path):
    """Another copy of the module (a checkout's, a variant's file)."""
    if os.path.isdir(path):
        path = os.path.join(path, "torchft_tpu", "ops", "indexed_attention.py")
    spec = importlib.util.spec_from_file_location(
        "indexed_attention_at_" + "".join(c if c.isalnum() else "_" for c in path), path
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def _rows(lse):
    """A forward's row statistic as ``[B, H, S]``, whichever way it left."""
    return lse[..., 0] if lse.ndim == 4 else lse


def _forward_differences(got, want):
    """``o`` and ``lse`` of one forward against another's: the largest
    absolute difference of each, and ``o``'s in steps of its type at the
    other's value (over the entries that are not tiny beside the largest: a
    value near 0 is a cancellation, and float32's last bit is many of ITS
    steps)."""
    (o, lse), (o_want, lse_want) = got, want
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    a, b = f32(o), f32(o_want)
    gap, large = np.abs(a - b), np.abs(b) > 1e-3 * np.abs(b).max()
    step = 2.0 ** (np.floor(np.log2(np.abs(b[large]))) - jnp.finfo(o.dtype).nmant)
    return dict(
        o_abs=float(gap.max()), o_steps=float((gap[large] / step).max()),
        lse_abs=float(np.abs(f32(_rows(lse)) - f32(_rows(lse_want))).max()),
    )


@functools.cache
def launches(module, seq, dim, interpret=False):
    """The attention's launches and ``dsa_probs`` of ``module`` as jitted
    programs over heads-major operands; one set a module.  A module from
    before PR 68 has two backward launches (an output that is not returned
    takes its launch with it), the tree one."""
    blocks = module.Blocks().fit(seq)
    scale = 1.0 / float(np.sqrt(dim))
    bwd = lambda *a: module._attn_bwd(*a, scale, blocks, interpret)  # noqa: E731
    if hasattr(module, "_attn_dq_kernel"):
        backward = dict(attn_dq=jax.jit(lambda *a: bwd(*a)[:1]), attn_dkv=jax.jit(lambda *a: bwd(*a)[1:]))
    else:
        backward = dict(attn_bwd=jax.jit(bwd))
    return dict(
        attn_fwd=jax.jit(lambda *a: module._attn_fwd(*a, scale, blocks, interpret)),
        **backward,
        probs=jax.jit(lambda *a: module._index_loss(*a, scale, blocks, interpret)),
    )


def compile_only(seq, variants, heads=32, kv=4, dim=128, index_heads=16, index_dim=64):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    blocks = ia.Blocks().fit(seq)
    a = lambda dtype, *dims: jax.ShapeDtypeStruct(dims, dtype, sharding=chip)  # noqa: E731
    bf, f32 = jnp.bfloat16, jnp.float32
    q, kv_, lse = a(bf, 1, heads, seq, dim), a(bf, 1, kv, seq, dim), a(f32, 1, heads, seq, ia._ROW_LANES)
    mask = a(jnp.int32, 1, -(-(seq // blocks.k) // 32), seq, blocks.k)
    bwd = (q, kv_, kv_, mask, q, lse, q)
    args = dict(
        attn_fwd=(q, kv_, kv_, mask), attn_dq=bwd, attn_dkv=bwd, attn_bwd=bwd,
        probs=(
            q, kv_, lse, mask, a(bf, 1, index_heads, seq, index_dim), a(f32, 1, index_heads, seq, ia._ROW_LANES),
            a(bf, 1, seq, index_dim), a(f32, 1, seq, ia._ROW_LANES),
        ),
    )
    steps = ia._steps(seq, blocks).steps
    report = dict(steps=steps, table_bytes=4 * 3 * steps)
    for prefix, module in [("", ia)] + [(name + "_", module) for name, module in variants.items()]:
        for name, fn in launches(module, seq, dim).items():
            if prefix and name == "probs":
                continue
            t0 = time.perf_counter()
            fn.lower(*args[name]).compile()
            report[prefix + name + "_compile_s"] = round(time.perf_counter() - t0, 1)
    print("PROBE " + json.dumps(report))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check-seq", type=int, default=4096)
    ap.add_argument("--check-topk", type=int, default=512)
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--parent", default="", help="a checkout whose launches are timed beside the tree's")
    ap.add_argument("--variants", default="", help="name=path,...: further copies of the module, their forward timed")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--toy", action="store_true", help="on the CPU in interpret mode, 1,024 positions")
    args = ap.parse_args()
    variants = {n: _load(p) for n, p in (v.split("=") for v in args.variants.split(",") if v)}
    if args.compile_only:
        return compile_only(args.seq, variants)
    toy = args.toy
    if toy:
        args.check_seq, args.check_topk, args.seq, args.topk, args.rounds = 512, 64, 1024, 128, 1
    out = dict(device=jax.devices()[0].device_kind)

    # exactness at float32-scored bfloat16 operands: the same picks, and the
    # outputs and gradients to the plain path's rounding
    ops = operands(args.check_seq, 1, jnp.bfloat16)
    grad = lambda f: jax.jit(jax.value_and_grad(f, argnums=tuple(range(6)), has_aux=True))  # noqa: E731
    (_, (o1, kl1, n1)), g1 = grad(kernels(args.check_topk, toy))(*ops)
    (_, (o2, kl2, n2)), g2 = grad(plain(args.check_topk))(*ops)
    rel = lambda a, b: float(  # noqa: E731
        jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32)) / (jnp.linalg.norm(b.astype(jnp.float32)) + 1e-30)
    )
    out["check"] = dict(
        seq=args.check_seq, topk=args.check_topk, o=rel(o1, o2), kl=[float(kl1), float(kl2)],
        keys=[float(n1), float(n2)], grads=[rel(a, b) for a, b in zip(g1, g2)],
    )
    print("check", json.dumps(out["check"]), flush=True)

    ops = operands(args.seq, 2, jnp.bfloat16)
    q, k, v, qi, ki, w = ops
    timed = {}

    def clock(name, fn, *a):
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(args.rounds):
            r = fn(*a)
        jax.block_until_ready(r)
        timed[name] = 1000.0 * (time.perf_counter() - t0) / args.rounds
        print(name, timed[name], flush=True)
        return r

    mask, lse_i, n = clock("select_keys_ms", lambda qi, ki, w: ia.select_keys(qi, ki, w, topk=args.topk, interpret=toy), qi, ki, w)
    out["keys_per_query"] = float(jnp.mean(n))
    qh, kh, vh, qih, wh = ia._heads_major(q, k, v, qi, w)
    sides = [("", ia)]
    if args.parent:
        parent = ("parent_", _load(args.parent))
        sides = [parent, sides[0], ("again_", ia), ("parent_again_", parent[1])]
    # every side's backward launches and dsa_probs take the TREE's o and lse
    o, lse = launches(ia, args.seq, q.shape[-1], toy)["attn_fwd"](qh, kh, vh, mask)
    lanes = ia._row_lanes(lse)
    bwd = (qh, kh, vh, mask, o, lanes, o)
    forwards, got = {}, {}

    def attention(prefix, run):
        """A module's forward (kept in ``forwards``) and backward launches, timed: (dq, dk, dv)."""
        forwards[prefix] = clock(prefix + "attn_fwd_ms", run["attn_fwd"], qh, kh, vh, mask)
        backward = [name for name in ("attn_dq", "attn_dkv", "attn_bwd") if name in run]
        return tuple(g for name in backward for g in clock(prefix + name + "_ms", run[name], *bwd))

    for prefix, module in sides:
        run = launches(module, args.seq, q.shape[-1], toy)
        grads = attention(prefix, run)
        loss = clock(prefix + "probs_ms", run["probs"], qh, kh, lanes, mask, qih, wh, ki, ia._row_lanes(lse_i))
        got[prefix] = (*grads, *loss)
    for name, module in variants.items():
        got[name + "_"] = attention(name + "_", launches(module, args.seq, q.shape[-1], toy))
    if args.parent:
        names = "dq dk dv kl d_qi d_w d_ki".split()
        out["equal_to_parent"] = {
            n: bool(jnp.array_equal(a, b)) for n, a, b in zip(names, got[""], got["parent_"], strict=True)
        }
        print("equal_to_parent", out["equal_to_parent"], flush=True)
    if variants:
        out["variants_equal_to_tree"] = {
            name: {n: bool(jnp.array_equal(a, b)) for n, a, b in zip("dq dk dv".split(), got[name + "_"], got[""])}
            for name in variants
        }
        print("variants_equal_to_tree", out["variants_equal_to_tree"], flush=True)
    others = {n: f for n, f in forwards.items() if n and not n.startswith("again")}
    if others:
        out["forward_differs_by"] = {n.rstrip("_"): _forward_differences(forwards[""], f) for n, f in others.items()}
        print("forward_differs_by", out["forward_differs_by"], flush=True)
    clock("whole_grad_ms", jax.grad(lambda *a: kernels(args.topk, toy)(*a)[0], argnums=tuple(range(6))), *ops)
    from torchft_tpu.ops.flash_attention import flash_attention

    clock("dense_flash_fwd_ms", lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=toy), q, k, v)
    out["ms"] = timed
    # a launch's time over its grid steps (every attention launch walks the same live pairs of every KV head)
    grid_steps = kh.shape[1] * ia._steps(args.seq, ia.Blocks().fit(args.seq)).steps
    out["us_a_step"] = {n[:-3]: 1000.0 * ms / grid_steps for n, ms in timed.items() if "attn_" in n}
    print("PROBE " + json.dumps(out))


if __name__ == "__main__":
    main()
