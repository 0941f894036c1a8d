"""On the chip: the kernels of ``ops/ssd.py`` against the token-by-token
recurrence on the same operands at the cell's size (16,384 positions, 64
heads of 64, a state of 128, 8 groups: outputs and every gradient, in norm),
and each kernel's time.  ``chiprun -- python3 scripts/ssd_probe.py``; the
last line is ``PROBE {...}``.  ``--groups 1 --variants 256:8,256:16,128:8``
(``chunk:head block``, PR 69) walks granite-4.0-h-micro's ONE group of 64
heads at each variant: every variant against the recurrence, and a launch's
time beside it; ``--compile-only`` compiles them for a described v5e here and
``--toy`` walks it on the CPU in interpret mode."""

from __future__ import annotations

import argparse
import functools
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


def device_clock(launches, calls):
    """``launches()`` under a profiler session: the device's own milliseconds a
    call of the operations NAMED ``ssd_fwd`` and ``ssd_bwd`` (what a cell's
    ``ssd_fwd_ms`` reads), where the host's clock around ``_fwd`` and ``_bwd``
    also holds the layout copy of the running sum and the cotangents' sum."""
    import tempfile

    from ftbench import trace_reduce

    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            launches()
        planes = trace_reduce.device_planes(trace_reduce.load(trace_reduce.find_xplane(trace_dir)))
    if not planes:
        return {}
    ops = planes[min(planes)].get(trace_reduce.OPS_LINE, [])
    return {f"device_{k}_ms": 1000.0 * trace_reduce.matching_seconds(ops, rf"^%?{k}\b") / calls for k in ("ssd_fwd", "ssd_bwd")}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--groups", type=int, default=8)
    ap.add_argument("--variants", default="128:", help="chunk:head block, comma separated; no block: ops/ssd.py's rule")
    ap.add_argument("--toy", action="store_true", help="64 positions, heads of 8 by 16, interpret mode")
    ap.add_argument("--compile-only", action="store_true", help="compile each variant's gradient for a described v5e")
    ap.add_argument("--device-clock", action="store_true", help="also trace each variant's launches and read ssd_fwd / ssd_bwd by name")
    args = ap.parse_args()
    variants = [(int(c), int(b) if b else None) for c, _, b in (v.partition(":") for v in args.variants.split(","))]
    sizes = dict(groups=args.groups, **(dict(head_dim=8, state=16) if args.toy else {}))
    seq, dtype = (64, jnp.float32) if args.toy else (args.seq, jnp.bfloat16)
    if args.toy:
        variants = [(min(c, 32), b) for c, b in variants]
    scan = lambda chunk, block: lambda *a: ssd.ssd_chunked(*a, chunk=chunk, block=block, interpret=args.toy)  # noqa: E731
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
        shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one) for a in jax.eval_shape(lambda: operands(seq, 1, dtype, **sizes))]
        for chunk, block in variants:
            grad = jax.grad(lambda *a: jnp.sum(scan(chunk, block)(*a).astype(jnp.float32)), argnums=tuple(range(6)))
            try:
                jax.jit(grad).lower(*shapes).compile()
                print("compiles", chunk, block, flush=True)
            except Exception as e:  # noqa: BLE001 — the compiler's refusal is the finding
                print("REFUSED", chunk, block, str(e)[:300], flush=True)
        return
    out = dict(device=jax.devices()[0].device_kind, seq=seq, groups=args.groups, checks=[], ms={})
    rel = lambda a, b: float(  # noqa: E731
        jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32)) / (jnp.linalg.norm(b.astype(jnp.float32)) + 1e-30)
    )
    for seed in range(1, args.seeds + 1):
        ops = operands(seq, seed, dtype, **sizes)
        weight = jax.random.normal(jax.random.PRNGKey(100 + seed), ops[0].shape, jnp.float32)
        both = lambda f: jax.jit(  # noqa: E731
            jax.value_and_grad(lambda *a: (lambda y: (jnp.sum(y.astype(jnp.float32) * weight), y))(f(*a)),
                               argnums=tuple(range(6)), has_aux=True)
        )
        with jax.default_matmul_precision("highest"):
            (_, y2), g2 = both(functools.partial(recurrence, block=min(128, seq)))(*ops)
        for chunk, block in variants:
            (_, y1), g1 = both(scan(chunk, block))(*ops)
            check = dict(seed=seed, chunk=chunk, block=block, y=rel(y1, y2), grads={n: rel(a, b) for n, a, b in zip(NAMES, g1, g2)})
            out["checks"].append(check)
            print("check", json.dumps(check), flush=True)

    ops = operands(seq, 7, dtype, **sizes)

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

    for chunk, block in variants:
        timed = out["ms"][f"{chunk}:{block or ''}"] = {}
        block = ssd.head_block(ops[0].shape[2] // args.groups, block)
        prepared = jax.jit(lambda *a: ssd._prepare(*a, min(chunk, seq), block))(*ops[:5])
        y, h = clock("ssd_fwd_ms", lambda *a: ssd._fwd(*a, args.toy), *prepared)
        clock("ssd_bwd_ms", lambda *a: ssd._bwd(*a, args.toy), *prepared, h, y)
        clock("prepare_ms", lambda *a: ssd._prepare(*a, min(chunk, seq), block), *ops[:5])
        clock("whole_fwd_ms", scan(chunk, block), *ops)
        clock("whole_grad_ms", jax.grad(lambda *a: jnp.sum(scan(chunk, block)(*a).astype(jnp.float32)), argnums=tuple(range(6))), *ops)
        if args.device_clock:
            both = lambda: (  # noqa: E731
                clock("traced_fwd_ms", lambda *a: ssd._fwd(*a, args.toy), *prepared),
                clock("traced_bwd_ms", lambda *a: ssd._bwd(*a, args.toy), *prepared, h, y),
            )
            timed.update(device_clock(both, args.rounds + 1))
    print("PROBE " + json.dumps(out))


if __name__ == "__main__":
    main()
