"""On the chip: the kernels of ``ops/ssd.py`` against the token-by-token
recurrence on the same operands at the cell's size (16,384 positions, 64
heads of 64, a state of 128, 8 groups: outputs and every gradient, in norm),
and each kernel's time.  ``chiprun -- python3 scripts/ssd_probe.py``; the
last line is ``PROBE {...}``."""

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

from torchft_tpu.ops import ssd  # noqa: E402

NAMES = ("x", "dt", "A_log", "B", "C", "D")


def operands(seq, seed, dtype, heads=64, head_dim=64, state=128, groups=8):
    """As a layer at ``init`` hands them over: unit ``x``, ``B`` and ``C``
    after their SiLU, steps log-uniform between 1e-3 and 0.1, ``A`` in [1, 16]."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda k, shape: jax.random.normal(k, shape, jnp.float32)  # noqa: E731
    dt = jnp.exp(jax.random.uniform(ks[1], (1, seq, heads), jnp.float32, np.log(1e-3), np.log(0.1)))
    return (
        normal(ks[0], (1, seq, heads, head_dim)).astype(dtype),
        dt,
        jnp.log(jax.random.uniform(ks[2], (heads,), jnp.float32, 1.0, 16.0)),
        jax.nn.silu(normal(ks[3], (1, seq, groups, state))).astype(dtype),
        jax.nn.silu(normal(ks[4], (1, seq, groups, state))).astype(dtype),
        1.0 + 0.1 * normal(ks[5], (heads,)),
    )


def recurrence(x, dt, A_log, Bm, Cm, D, block=128):
    """``S_t = a_t S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``, a
    token at a time in float32; blocks of tokens are rematerialised so that
    the gradient's tape (2 MB of state a token) fits."""
    x, Bm, Cm = (a.astype(jnp.float32) for a in (x, Bm, Cm))
    B, S, H, P = x.shape
    heads = H // Bm.shape[2]
    Bh, Ch = jnp.repeat(Bm, heads, axis=2), jnp.repeat(Cm, heads, axis=2)
    a = jnp.exp(-dt * jnp.exp(A_log))

    def token(state, now):
        x_t, dt_t, a_t, b_t, c_t = now
        state = a_t[..., None, None] * state + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return state, jnp.sum(state * c_t[..., None, :], axis=-1)

    blocks = lambda v: jnp.moveaxis(v, 1, 0).reshape(S // block, block, *v.shape[:1], *v.shape[2:])  # noqa: E731
    step = jax.checkpoint(lambda state, xs: jax.lax.scan(token, state, xs))
    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, Bm.shape[-1]), jnp.float32), tuple(map(blocks, (x, dt, a, Bh, Ch))))
    return jnp.moveaxis(y.reshape(S, B, H, P), 0, 1) + D[:, None] * x


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--seeds", type=int, default=2)
    args = ap.parse_args()
    out = dict(device=jax.devices()[0].device_kind, seq=args.seq, checks=[])
    rel = lambda a, b: float(  # noqa: E731
        jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32)) / (jnp.linalg.norm(b.astype(jnp.float32)) + 1e-30)
    )
    kernels = lambda *a: ssd.ssd_chunked(*a, chunk=128)  # noqa: E731
    for seed in range(1, args.seeds + 1):
        ops = operands(args.seq, seed, jnp.bfloat16)
        weight = jax.random.normal(jax.random.PRNGKey(100 + seed), ops[0].shape, jnp.float32)
        both = lambda f: jax.jit(  # noqa: E731
            jax.value_and_grad(lambda *a: (lambda y: (jnp.sum(y.astype(jnp.float32) * weight), y))(f(*a)),
                               argnums=tuple(range(6)), has_aux=True)
        )
        (_, y1), g1 = both(kernels)(*ops)
        with jax.default_matmul_precision("highest"):
            (_, y2), g2 = both(recurrence)(*ops)
        check = dict(seed=seed, y=rel(y1, y2), grads={n: rel(a, b) for n, a, b in zip(NAMES, g1, g2)})
        out["checks"].append(check)
        print("check", json.dumps(check), flush=True)

    ops = operands(args.seq, 7, jnp.bfloat16)
    prepared = jax.jit(lambda *a: ssd._prepare(*a, 128))(*ops[:5])
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

    y, h = clock("ssd_fwd_ms", lambda *a: ssd._fwd(*a, False), *prepared)
    clock("ssd_bwd_ms", lambda *a: ssd._bwd(*a, False), *prepared, h, y)
    clock("prepare_ms", lambda *a: ssd._prepare(*a, 128), *ops[:5])
    clock("whole_fwd_ms", kernels, *ops)
    clock("whole_grad_ms", jax.grad(lambda *a: jnp.sum(kernels(*a).astype(jnp.float32)), argnums=tuple(range(6))), *ops)
    out["ms"] = timed
    print("PROBE " + json.dumps(out))


if __name__ == "__main__":
    main()
