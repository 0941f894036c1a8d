"""Kimi Delta Attention (KDA): the gated delta rule with a decay for every
channel, in its chunked form, forward and backward (Pallas, TPU).

One head keeps a state ``S`` of ``[dk, dv]`` and, token by token,

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t,        a_t = exp(g_t),  g_t <= 0 for every channel

That recurrence is the plain reference's (``ftbench/architectures/
ling_hybrid_reference.py``); the program never runs it.  Here a sequence is
cut into chunks of ``C`` tokens.  With ``G`` the running sum of ``g`` inside
a chunk, ``S0`` the state the chunk starts from, ``kb = b*k``, ``vb = b*v``:

    A  = strict_tril(decay_dot(kb, k))        decay_dot(x, y)[t, s] =
    P  = tril(decay_dot(q, k))                    sum_d x[t,d] y[s,d] exp(G[t,d] - G[s,d])
    T  = (I + A)^-1                           (unit lower triangular: the WY/UT form)
    W  = T (kb * exp(G)),   Uv = T vb
    U  = Uv - W S0                            (every token's corrected value)
    O  = (q * exp(G)) S0 + P U
    S1 = exp(G_C) * S0 + (k * exp(G_C - G))^T U

(the code keeps ``S`` transposed, ``[dv, dk]``, so that a channel's decay
scales a lane and the kernels transpose nothing.)

``exp(G[t] - G[s])`` is at most one wherever ``t >= s``, but its two factors
are not: at the bound of -5 a token, ``exp(-G)`` leaves float32 after 18
tokens.  ``decay_dot`` therefore works in sub-blocks of 32 tokens: a block
against itself with its middle token as the reference (either factor within
``exp(80)``), a block against all earlier ones with the block's boundary as
the reference (both factors at most one).  The products of all references
are ONE matrix product over a contraction of ``(2 C/32 - 1) * dk``.  ``g``
below -5.5 a token would overflow it; the model's gate is bounded at -5.

``T`` comes from the nilpotent series ``(I - A)(I + A^2)(I + A^4)...`` in
float32 at full precision; the state, the decays and every accumulation are
float32; the other products take their operands in the inputs' type
(bfloat16 on the chip, float32 in the CPU tests).

Kernels: ``kda_fwd`` walks a head's chunks in order with the state in VMEM
and keeps each chunk's starting state for the backward; ``kda_bwd`` walks
them in reverse with the state's cotangent in VMEM, recomputes the chunk
from the kept state and applies the hand-written transpose of the algebra
above.  The running sum of ``g`` inside a chunk is taken outside (XLA's
``cumsum``, differentiated by jax), as are ``b*k`` and ``b*v``: the kernels
never see ``b``.  The short convolution, SiLU, the q/k normalisation, the
bounded gate, the per-head norm and the output gate are the model's
(``models/ling_hybrid.py``), outside the kernels.

``kda_chunked_plain`` is the same chunk algebra as plain ``jax.numpy`` under
a ``lax.scan``, differentiated by jax: what a model takes off the TPU, and
what the kernels are tested against besides the recurrence.
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# sub-block of the decay products; with |g| <= 5 a token the exponents of a
# block stay within 16 * 5 = 80 of its middle, inside float32's exp
_SUB = 32
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, dims, dtype):
    """``a`` and ``b`` contracted over ``dims`` with operands in ``dtype``,
    accumulated in float32."""
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), ((dims[0], dims[1]), ((), ())),
        preferred_element_type=_F32,
        precision=_HIGHEST if dtype == _F32 else None,
    )


_NN = ((1,), (0,))  # a @ b
_NT = ((1,), (1,))  # a @ b.T
_TN = ((0,), (0,))  # a.T @ b


def _scales(G):
    """The row and column factors of ``decay_dot``: ``(Ex, Ey)``, each
    ``[C, R * dk]`` with ``R = 2 C/32 - 1`` references, zero outside a
    reference's rows (columns)."""
    C, dk = G.shape
    sub = min(_SUB, C)
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, dk), 0)
    ex, ey = [], []

    def factor(mask, exponent):
        return jnp.where(mask, jnp.exp(jnp.where(mask, exponent, 0.0)), 0.0)

    for lo in range(0, C, sub):
        block = (rows >= lo) & (rows < lo + sub)
        mid = G[lo + sub // 2 - 1 : lo + sub // 2]
        ex.append(factor(block, G - mid))
        ey.append(factor(block, mid - G))
        if lo:
            edge = G[lo - 1 : lo]
            ex.append(factor(block, G - edge))
            ey.append(factor(rows < lo, edge - G))
    return jnp.concatenate(ex, axis=1), jnp.concatenate(ey, axis=1)


def _spread(x, scale):
    """``x`` beside itself once a reference, times the reference's factors."""
    return jnp.concatenate([x] * (scale.shape[1] // x.shape[1]), axis=1) * scale


def _fold(xs, scale, dk):
    """The transpose of :func:`_spread`."""
    xs = xs * scale
    out = xs[:, :dk]
    for r in range(1, scale.shape[1] // dk):
        out = out + xs[:, r * dk : (r + 1) * dk]
    return out


def _inverse_unit_lower(A):
    """``(I + A)^-1`` of a strictly lower triangular ``A`` ``[C, C]``."""
    C = A.shape[0]
    eye = (
        jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    ).astype(_F32)
    T = eye - A
    power = A
    span = 2
    while span < C:  # A^C = 0
        power = _dot(power, power, _NN, _F32)
        T = T + _dot(T, power, _NN, _F32)
        span *= 2
    return T


def _chunk_parts(q, k, kb, vb, G, St0):
    """Everything of one chunk that both directions need."""
    C, dk = k.shape
    mm = q.dtype
    q, k, kb, vb = (x.astype(_F32) for x in (q, k, kb, vb))
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    decay = jnp.exp(G)
    last = G[C - 1 : C]
    Ex, Ey = _scales(G)
    ks, kbs, qs = _spread(k, Ey), _spread(kb, Ex), _spread(q, Ex)
    A = jnp.where(row > col, _dot(kbs, ks, _NT, mm), 0.0)
    T = _inverse_unit_lower(A)
    kbg, qg = kb * decay, q * decay
    W = _dot(T, kbg, _NN, mm)
    Uv = _dot(T, vb, _NN, mm)
    U = Uv - _dot(W, St0, _NT, mm)
    P = jnp.where(row >= col, _dot(qs, ks, _NT, mm), 0.0)
    kd = k * jnp.exp(last - G)
    return dict(
        mm=mm, q=q, k=k, kb=kb, row=row, col=col, decay=decay, last=last, Ex=Ex, Ey=Ey,
        ks=ks, kbs=kbs, qs=qs, T=T, kbg=kbg, qg=qg, W=W, Uv=Uv, U=U, P=P, kd=kd,
    )


def _chunk_fwd(q, k, kb, vb, G, St0):
    """One chunk: ``(O [C, dv], St1 [dv, dk])``, both float32.  ``G`` is the
    running sum of the log decay inside the chunk; the state is kept
    TRANSPOSED (``St = S^T``, float32), so that a channel's decay scales a
    lane and nothing is ever transposed in the kernel."""
    p = _chunk_parts(q, k, kb, vb, G, St0)
    mm = p["mm"]
    O = _dot(p["qg"], St0, _NT, mm) + _dot(p["P"], p["U"], _NN, mm)
    St1 = jnp.exp(p["last"]) * St0 + _dot(p["U"], p["kd"], _TN, mm)
    return O, St1


def _chunk_bwd(q, k, kb, vb, G, St0, dO, dSt1):
    """The transpose of :func:`_chunk_fwd`: cotangents of ``q, k, kb, vb,
    G`` and ``St0``, all float32."""
    p = _chunk_parts(q, k, kb, vb, G, St0)
    mm, row, col = p["mm"], p["row"], p["col"]
    C, width = p["k"].shape
    dO = dO.astype(_F32)
    dU = _dot(p["P"], dO, _TN, mm) + _dot(p["kd"], dSt1, _NT, mm)
    dP = jnp.where(row >= col, _dot(dO, p["U"], _NT, mm), 0.0)
    dqg = _dot(dO, St0, _NN, mm)
    dSt0 = (
        _dot(dO, p["qg"], _TN, mm)
        + jnp.exp(p["last"]) * dSt1
        - _dot(dU, p["W"], _TN, mm)
    )
    dkd = _dot(p["U"], dSt1, _NN, mm)
    dW = -_dot(dU, St0, _NN, mm)
    dkbg = _dot(p["T"], dW, _TN, mm)
    dvb = _dot(p["T"], dU, _TN, mm)
    dA = -jnp.where(
        row > col, _dot(dkbg, p["W"], _NT, mm) + _dot(dvb, p["Uv"], _NT, mm), 0.0
    )
    # the two decay products, back to their rows and columns
    dkb_a = _fold(_dot(dA, p["ks"], _NN, mm), p["Ex"], width)
    dq_p = _fold(_dot(dP, p["ks"], _NN, mm), p["Ex"], width)
    dk_cols = _fold(
        _dot(dA, p["kbs"], _TN, mm) + _dot(dP, p["qs"], _TN, mm), p["Ey"], width
    )
    dq = dqg * p["decay"] + dq_p
    dkb = dkbg * p["decay"] + dkb_a
    dk = dk_cols + dkd * jnp.exp(p["last"] - G)
    dkd_kd = dkd * p["kd"]
    dG = (
        dqg * p["qg"] + dkbg * p["kbg"]
        + p["q"] * dq_p + p["kb"] * dkb_a - p["k"] * dk_cols
        - dkd_kd
    )
    # the chunk's last row is also the decay of the whole chunk
    d_last = (
        jnp.sum(dkd_kd, axis=0, keepdims=True)
        + jnp.sum(St0 * dSt1, axis=0, keepdims=True) * jnp.exp(p["last"])
    )
    last_row = jax.lax.broadcasted_iota(jnp.int32, (C, width), 0) == C - 1
    dG = dG + jnp.where(last_row, d_last, 0.0)
    return dq, dk, dkb, dvb, dG, dSt0


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, o_ref, h_ref, s_scr, *, heads):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_scr[...] = jnp.zeros_like(s_scr)

    for h in range(heads):
        St0 = s_scr[h]
        h_ref[0, h, 0] = St0
        O, St1 = _chunk_fwd(q_ref[0, h], k_ref[0, h], kb_ref[0, h], vb_ref[0, h], g_ref[0, h], St0)
        o_ref[0, h] = O.astype(o_ref.dtype)
        s_scr[h] = St1


def _bwd_kernel(
    q_ref, k_ref, kb_ref, vb_ref, g_ref, h_ref, do_ref,
    dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref, ds_scr, *, heads,
):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    for h in range(heads):
        dq, dk, dkb, dvb, dG, dSt0 = _chunk_bwd(
            q_ref[0, h], k_ref[0, h], kb_ref[0, h], vb_ref[0, h], g_ref[0, h],
            h_ref[0, h, 0], do_ref[0, h], ds_scr[h],
        )
        dq_ref[0, h] = dq.astype(dq_ref.dtype)
        dk_ref[0, h] = dk.astype(dk_ref.dtype)
        dkb_ref[0, h] = dkb.astype(dkb_ref.dtype)
        dvb_ref[0, h] = dvb.astype(dvb_ref.dtype)
        dg_ref[0, h] = dG
        ds_scr[h] = dSt0


# heads a grid step: on the v5e 1, 2, 4 and 8 read within 6 % of each other
# at 32 heads of 128 over 8,192 tokens, as did chunks of 64 and 128 (PERF.md
# section 6, PR 29)
_HEADS_PER_STEP = 4


def _heads_per_step(H: int) -> int:
    want = _HEADS_PER_STEP
    while H % want:
        want -= 1
    return want


def _fwd(q, k, kb, vb, G, chunk, heads, interpret):
    """Heads-major ``[B, H, S, D]`` in; ``(o [B,H,S,dv], h [B,H,S/C,dv,dk])``
    out, ``h`` the (transposed) state every chunk started from."""
    B, H, S, dk = q.shape
    dv = vb.shape[-1]
    nt = S // chunk
    seq = lambda d: pl.BlockSpec((1, heads, chunk, d), lambda b, h, c: (b, h, c, 0))  # noqa: E731
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads),
        grid=(B, H // heads, nt),
        in_specs=[seq(dk), seq(dk), seq(dk), seq(dv), seq(dk)],
        out_specs=[
            seq(dv),
            pl.BlockSpec((1, heads, 1, dv, dk), lambda b, h, c: (b, h, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, nt, dv, dk), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="kda_fwd",
    )(q, k, kb, vb, G)


def _bwd(q, k, kb, vb, G, h, do, chunk, heads, interpret):
    B, H, S, dk = q.shape
    dv = vb.shape[-1]
    nt = S // chunk
    # the chunks in reverse: the state's cotangent flows from the last one
    seq = lambda d: pl.BlockSpec(  # noqa: E731
        (1, heads, chunk, d), lambda b, h, c: (b, h, nt - 1 - c, 0)
    )
    like = lambda x, dtype=None: jax.ShapeDtypeStruct(x.shape, dtype or x.dtype)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads),
        grid=(B, H // heads, nt),
        in_specs=[
            seq(dk), seq(dk), seq(dk), seq(dv), seq(dk),
            pl.BlockSpec((1, heads, 1, dv, dk), lambda b, h, c: (b, h, nt - 1 - c, 0, 0)),
            seq(dv),
        ],
        out_specs=[seq(dk), seq(dk), seq(dk), seq(dv), seq(dk)],
        out_shape=[like(q), like(k), like(kb), like(vb), like(G, _F32)],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="kda_bwd",
    )(q, k, kb, vb, G, h, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda_hm(q, k, kb, vb, G, chunk, heads, interpret):
    return _fwd(q, k, kb, vb, G, chunk, heads, interpret)[0]


def _kda_hm_fwd(q, k, kb, vb, G, chunk, heads, interpret):
    o, h = _fwd(q, k, kb, vb, G, chunk, heads, interpret)
    return o, (q, k, kb, vb, G, h)


def _kda_hm_bwd(chunk, heads, interpret, res, do):
    return _bwd(*res, do, chunk, heads, interpret)


_kda_hm.defvjp(_kda_hm_fwd, _kda_hm_bwd)


# ---------------------------------------------------------------------------
# public entries ([B, S, H, D], the model's layout)
# ---------------------------------------------------------------------------


def _prepare(q, k, v, g, beta, scale, chunk):
    """Heads-major ``q * scale, k, b*k, b*v`` and the running sum of ``g``
    inside each chunk (float32)."""
    B, S, H, dk = q.shape
    if S % chunk or chunk % min(_SUB, chunk):
        raise ValueError(f"S={S} not divisible by the chunk {chunk}, or the chunk by {_SUB}")
    if scale is None:
        scale = dk ** -0.5
    hm = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    b = beta[..., None].astype(_F32)
    kb = (k.astype(_F32) * b).astype(k.dtype)
    vb = (v.astype(_F32) * b).astype(v.dtype)
    G = jnp.cumsum(
        hm(g).astype(_F32).reshape(B, H, S // chunk, chunk, dk), axis=3
    ).reshape(B, H, S, dk)
    return hm((q.astype(_F32) * scale).astype(q.dtype)), hm(k), hm(kb), hm(vb), G


def kda_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    *,
    scale: float | None = None,
    chunk: int = 64,
    interpret: bool = False,
) -> jax.Array:
    """The gated delta rule over whole sequences from a zero state, by the
    chunked kernels.  ``q, k, g`` ``[B, S, H, dk]``, ``v`` ``[B, S, H, dv]``,
    ``beta`` ``[B, S, H]``; ``g`` is the log of the decay, in ``[-5, 0]``.
    Returns ``[B, S, H, dv]`` in ``v``'s type.  ``S`` must be a multiple of
    ``chunk`` and ``chunk`` of 32 (or at most 32)."""
    chunk = min(chunk, q.shape[1])
    args = _prepare(q, k, v, g, beta, scale, chunk)
    heads = _heads_per_step(q.shape[2])
    return _kda_hm(*args, chunk, heads, interpret).transpose(0, 2, 1, 3)


def kda_chunked_plain(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    *,
    scale: float | None = None,
    chunk: int = 64,
) -> jax.Array:
    """:func:`kda_chunked`'s algebra with no kernel: a ``lax.scan`` over the
    chunks of :func:`_chunk_fwd`, differentiated by jax."""
    chunk = min(chunk, q.shape[1])
    qh, kh, kbh, vbh, G = _prepare(q, k, v, g, beta, scale, chunk)
    B, H, S, dk = qh.shape
    dv = vbh.shape[-1]

    def chunks(x):  # [B, H, S, d] -> [S/C, B, H, C, d]
        return x.reshape(B, H, S // chunk, chunk, x.shape[-1]).transpose(2, 0, 1, 3, 4)

    step = jax.vmap(jax.vmap(_chunk_fwd))

    def body(St0, xs):
        O, St1 = step(*xs, St0)
        return St1, O

    _, O = jax.lax.scan(
        body, jnp.zeros((B, H, dv, dk), _F32), tuple(chunks(x) for x in (qh, kh, kbh, vbh, G))
    )
    return O.transpose(1, 2, 0, 3, 4).reshape(B, H, S, dv).transpose(0, 2, 1, 3).astype(v.dtype)
