"""On the chip: the kernels of ``ops/indexed_attention.py`` against the plain
path at a length the dense arrays fit, and each kernel's time at the cell's
length.  ``chiprun -- python3 scripts/indexed_attention_probe.py``; the last
line is ``PROBE {...}``."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

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


def kernels(topk):
    def f(q, k, v, qi, ki, w):
        mask, lse_i, n = ia.select_keys(qi, ki, w, topk=topk)
        o, kl = ia.indexed_attention(q, k, v, qi, ki, w, mask, lse_i)
        return jnp.sum(o.astype(jnp.float32) ** 2) + kl, (o, kl, jnp.mean(n))

    return f


def plain(topk):
    def f(q, k, v, qi, ki, w):
        o, kl, n = ia.indexed_attention_plain(q, k, v, qi, ki, w, topk=topk)
        return jnp.sum(o.astype(jnp.float32) ** 2) + kl, (o, kl, jnp.mean(n))

    return f


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check-seq", type=int, default=4096)
    ap.add_argument("--check-topk", type=int, default=512)
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()
    out = dict(device=jax.devices()[0].device_kind)

    # exactness at float32-scored bfloat16 operands: the same picks, and the
    # outputs and gradients to the plain path's rounding
    ops = operands(args.check_seq, 1, jnp.bfloat16)
    grad = lambda f: jax.jit(jax.value_and_grad(f, argnums=tuple(range(6)), has_aux=True))  # noqa: E731
    (_, (o1, kl1, n1)), g1 = grad(kernels(args.check_topk))(*ops)
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

    mask, lse_i, n = clock("select_keys_ms", lambda qi, ki, w: ia.select_keys(qi, ki, w, topk=args.topk), qi, ki, w)
    out["keys_per_query"] = float(jnp.mean(n))
    blocks = ia.Blocks().fit(args.seq)
    qh, kh, vh, qih, wh = ia._heads_major(q, k, v, qi, w)
    scale = 1.0 / float(np.sqrt(q.shape[-1]))
    o, lse = clock("attn_fwd_ms", lambda *a: ia._attn_fwd(*a, scale, blocks, False), qh, kh, vh, mask)
    clock("attn_bwd_ms", lambda *a: ia._attn_bwd(*a, scale, blocks, False), qh, kh, vh, mask, o, lse, o)
    clock(
        "probs_ms", lambda *a: ia._index_loss(*a, scale, blocks, False),
        qh, kh, lse, mask, qih, wh, ki, ia._row_lanes(lse_i),
    )
    clock("whole_grad_ms", jax.grad(lambda *a: kernels(args.topk)(*a)[0], argnums=tuple(range(6))), *ops)
    from torchft_tpu.ops.flash_attention import flash_attention

    clock("dense_flash_fwd_ms", lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
    out["ms"] = timed
    print("PROBE " + json.dumps(out))


if __name__ == "__main__":
    main()
