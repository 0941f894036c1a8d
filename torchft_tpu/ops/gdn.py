"""Gated DeltaNet (arXiv:2412.06464): the gated delta rule with ONE decay a
head and token, unbounded below, and several value heads to a key head, in
its chunked form, forward and backward (Pallas, TPU).

A value head keeps a state ``S`` of ``[dk, dv]`` and, token by token,

    S' = exp(g_t) S_{t-1}
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T,        o_t = S_t^T q_t

with ``g_t <= 0`` a NUMBER (``ops/kda.py``'s rule has one a channel, bounded
at -5).  Value head ``j`` reads the query and key of key head ``j // (Hv /
Hk)``; ``b`` and ``g`` are the value head's.  That recurrence is the plain
reference's (``ftbench/architectures/gated_delta_moe_reference.py``); the
program never runs it.  Here a sequence is cut into chunks of ``C`` tokens.
With ``G`` the running sum of ``g`` inside a chunk, ``S0`` the state the chunk
starts from and ``D[t, s] = exp(G[t] - G[s])`` for ``t >= s``, 0 above:

    A  = strict_tril(b * D * (k k^T))
    T  = (I + A)^-1                           (unit lower triangular: the WY/UT form)
    U  = T (b * (v - exp(G) * (k S0)))        (every token's corrected value)
    O  = exp(G) * (q S0) + (D * (q k^T)) U
    S1 = exp(G_C) S0 + (k * exp(G_C - G))^T U

The decay enters only as ``D``, ``exp(G)`` and ``exp(G_C - G)`` (``D``'s last
row): each is the exponential of a sum of ``g`` that is never positive, and a
sum between two tokens is made of its own terms alone, so none can overflow
whatever ``g`` is and none carries the rounding of a longer sum, where ``ops/kda.py`` must factorise a channel's ``exp(G[t] -
G[s])`` into two exponentials that leave float32 below -5.5 a token.  ``k
k^T`` and ``q k^T`` are a KEY head's: made once for the value heads that
share it, as are their cotangents' four products, and ``q`` and ``k`` are
read by the key head's index, never repeated in memory; ``dq`` and ``dk``
sum over the value heads inside the kernel.

``T`` is ``ops/kda.py``'s nilpotent series in float32 at full precision; the
state, the decays and every accumulation are float32; the other products
take their operands in the inputs' type (bfloat16 on the chip, float32 in
the CPU tests).

Kernels: ``gdn_fwd`` walks a head's chunks in order with the state in VMEM
and keeps each chunk's starting state for the backward; ``gdn_bwd`` walks
them in reverse with the state's cotangent in VMEM, recomputes the chunk
from the kept state and applies the hand-written transpose of the algebra
above.  ``g`` and ``b`` come in as ROWS ``[1, C]`` a head and chunk (2 MB a
layer at 16,384 positions); the running sum, the turn to a column and ``b``'s
products are the kernels'.  The convolution, SiLU, the q/k normalisation,
``softplus`` and the decay, the gated head norm are the model's
(``models/gated_delta_moe.py``), outside the kernels.

``gdn_chunked_plain`` is the same chunk algebra as plain ``jax.numpy`` under
a ``lax.scan``, differentiated by jax: what a model takes off the TPU, and
what the kernels are tested against besides the recurrence.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.ops.kda import _F32, _NN, _NT, _TN, _dot, _inverse_unit_lower

# value heads a grid step (``ops/kda.py``'s count, read on the v5e in PR 29)
_VALUE_HEADS_PER_STEP = 4


class _Decays(NamedTuple):
    """One value head's chunk: every form the decay takes, float32."""

    D: jax.Array  # [C, C]: exp(G[t] - G[s]) where t >= s, 0 above
    in_: jax.Array  # [C, 1]: exp(G), from the chunk's start to each token
    out: jax.Array  # [C, 1]: exp(G_C - G), from each token to the chunk's end
    whole: jax.Array  # [1, 1]: exp(G_C)
    lower: jax.Array  # [C, C] bool: t >= s
    eye: jax.Array  # [C, C] bool


def _column(row: jax.Array, eye: jax.Array) -> jax.Array:
    """``[1, C]`` turned to ``[C, 1]`` (exact: one term a sum)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(column: jax.Array, eye: jax.Array) -> jax.Array:
    return jnp.sum(jnp.where(eye, column, 0.0), axis=0, keepdims=True)


@jax.custom_vjp
def _exact_sums(x: jax.Array, ones: jax.Array) -> jax.Array:
    """``x @ ones`` for a float32 ``x`` and a matrix of zeros and ones, to
    float32's precision in three bfloat16 products where a float32 product at
    full precision takes six: ``x``'s 24 bits are three bfloat16 pieces, and a
    piece times one is exact.  Its transpose is the same product (jax's own
    would round the cotangent to ONE bfloat16 piece; only the plain path
    differentiates through here, the kernels' backward is written out)."""
    total = 0.0
    for _ in range(3):
        piece = x.astype(jnp.bfloat16)
        total = total + _dot(piece, ones, _NN, jnp.bfloat16)
        x = x - piece.astype(_F32)
    return total


_exact_sums.defvjp(lambda x, ones: (_exact_sums(x, ones), ones), lambda ones, ct: (_exact_sums(ct, ones.T), None))


def _decays(g_row: jax.Array) -> _Decays:
    C = g_row.shape[1]
    t = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    lower, eye = t >= s, t == s
    upto = jnp.where(lower, g_row, 0.0)  # [t, r]: g[r] where r <= t
    G = jnp.sum(upto, axis=1, keepdims=True)  # the running sum, a column
    # [t, s]: the sum of g over s < r <= t, each made of its own terms alone (a
    # difference of two running sums near -2,000 carries their rounding, 1e-4
    # of a decay that matters); never positive, whatever g is.  On the chip a
    # float32 product here cost the cell 2.1 % of its tokens (PERF.md section 6)
    between = _exact_sums(upto, (t > s).astype(jnp.bfloat16))
    D = jnp.where(lower, jnp.exp(jnp.where(lower, between, 0.0)), 0.0)
    in_ = jnp.exp(G)
    return _Decays(D, in_, _column(D[C - 1 : C], eye), in_[C - 1 : C], lower, eye)


def _key_products(q, k, mm):
    """``(k k^T, q k^T)``: a key head's, shared by its value heads."""
    return _dot(k, k, _NT, mm), _dot(q, k, _NT, mm)


def _head_parts(q, k, KK, v, g_row, beta_row, S0, mm):
    """Everything of one value head's chunk that both directions need."""
    d = _decays(g_row)
    beta = _column(beta_row, d.eye)
    DKK = jnp.where(d.lower & ~d.eye, d.D * KK, 0.0)
    T = _inverse_unit_lower(beta * DKK)
    KS = _dot(k, S0, _NN, mm)
    inner = v - d.in_ * KS
    U = _dot(T, beta * inner, _NN, mm)
    return d, beta, DKK, T, KS, inner, U


def _head_fwd(q, k, KK, QK, v, g_row, beta_row, S0, mm):
    """One value head's chunk: ``(O [C, dv], S1 [dk, dv])``, float32.  ``q``
    (scaled), ``k`` ``[C, dk]`` and ``v`` ``[C, dv]`` float32, ``g_row`` and
    ``beta_row`` ``[1, C]``, ``S0`` ``[dk, dv]`` float32, ``mm`` the
    products' operand type."""
    d, _, _, _, _, _, U = _head_parts(q, k, KK, v, g_row, beta_row, S0, mm)
    O = d.in_ * _dot(q, S0, _NN, mm) + _dot(d.D * QK, U, _NN, mm)
    S1 = d.whole * S0 + _dot(k * d.out, U, _TN, mm)
    return O, S1


def _head_bwd(q, k, KK, QK, v, g_row, beta_row, S0, dO, dS1, mm):
    """The transpose of :func:`_head_fwd`: this value head's part of ``dq``
    and ``dk`` (without what flows through ``KK`` and ``QK``), the cotangents
    of ``KK``, ``QK``, ``v``, ``g_row``, ``beta_row`` and ``S0``."""
    d, beta, DKK, T, KS, inner, U = _head_parts(q, k, KK, v, g_row, beta_row, S0, mm)
    C = q.shape[0]
    rows = lambda x: jnp.sum(x, axis=1, keepdims=True)  # noqa: E731
    P = d.D * QK
    QS = _dot(q, S0, _NN, mm)
    dU = _dot(P, dO, _TN, mm) + _dot(k * d.out, dS1, _NN, mm)
    dP = jnp.where(d.lower, _dot(dO, U, _NT, mm), 0.0)
    dQS = d.in_ * dO
    d_in = rows(dO * QS)
    dq = _dot(dQS, S0, _NT, mm)
    dS0 = _dot(q, dQS, _TN, mm) + d.whole * dS1
    last = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1
    # exp(G_C) is the last row's exp(G)
    d_in = d_in + jnp.where(last, jnp.sum(rows(S0 * dS1), axis=0, keepdims=True), 0.0)
    dkd = _dot(U, dS1, _NT, mm)
    dk = dkd * d.out
    d_out = rows(dkd * k)
    # U = T (beta * inner), T = (I + A)^-1:  dA = -T^T dU U^T
    dR = _dot(T, dU, _TN, mm)
    dA = -jnp.where(d.lower & ~d.eye, _dot(dR, U, _NT, mm), 0.0)
    dbeta = rows(dR * inner) + rows(dA * DKK)
    dv = beta * dR
    dKS = -d.in_ * dv
    d_in = d_in - rows(dv * KS)
    dk = dk + _dot(dKS, S0, _NT, mm)
    dS0 = dS0 + _dot(k, dKS, _TN, mm)
    dDKK = beta * dA
    # D[t, s] = exp(G[t] - G[s]): a row's sum goes to G[t], a column's comes off
    # G[s]; exp(G_C - G) is D's last row
    E = (dDKK * KK + dP * QK) * d.D + jnp.where(last, _row(d_out * d.out, d.eye), 0.0)
    dG = rows(E) - _column(jnp.sum(E, axis=0, keepdims=True), d.eye) + d_in * d.in_
    # G is the running sum of g: g[r] takes from every G[t] with t >= r
    dg_row = jnp.sum(jnp.where(d.lower, dG, 0.0), axis=0, keepdims=True)
    return dq, dk, dDKK * d.D, dP * d.D, dv, dg_row, _row(dbeta, d.eye), dS0


def _key_head_fwd(q, k, v, g_rows, beta_rows, S0):
    """A key head's chunk and its ``r`` value heads: ``q, k [C, dk]``, ``v [r,
    C, dv]``, ``g_rows, beta_rows [r, C]``, ``S0 [r, dk, dv]`` → ``(O [r, C,
    dv], S1 [r, dk, dv])``."""
    mm = q.dtype
    q, k = q.astype(_F32), k.astype(_F32)
    KK, QK = _key_products(q, k, mm)
    outs = [
        _head_fwd(q, k, KK, QK, v[j].astype(_F32), g_rows[j : j + 1], beta_rows[j : j + 1], S0[j], mm)
        for j in range(v.shape[0])
    ]
    return jnp.stack([o for o, _ in outs]), jnp.stack([s for _, s in outs])


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, h_ref, s_scr, *, kheads, r):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_scr[...] = jnp.zeros_like(s_scr)

    mm = q_ref.dtype
    for kh in range(kheads):
        q, k = q_ref[0, kh].astype(_F32), k_ref[0, kh].astype(_F32)
        KK, QK = _key_products(q, k, mm)
        for j in range(kh * r, (kh + 1) * r):
            S0 = s_scr[j]
            h_ref[0, j, 0] = S0
            O, S1 = _head_fwd(
                q, k, KK, QK, v_ref[0, j].astype(_F32), g_ref[0, 0, 0, j : j + 1], b_ref[0, 0, 0, j : j + 1], S0, mm
            )
            o_ref[0, j] = O.astype(o_ref.dtype)
            s_scr[j] = S1


def _bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, b_ref, h_ref, do_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_scr, *, kheads, r,
):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    mm = q_ref.dtype
    for kh in range(kheads):
        q, k = q_ref[0, kh].astype(_F32), k_ref[0, kh].astype(_F32)
        KK, QK = _key_products(q, k, mm)
        dq = dk = dKK = dQK = 0.0
        for j in range(kh * r, (kh + 1) * r):
            dq_j, dk_j, dKK_j, dQK_j, dv, dg_row, db_row, dS0 = _head_bwd(
                q, k, KK, QK, v_ref[0, j].astype(_F32), g_ref[0, 0, 0, j : j + 1], b_ref[0, 0, 0, j : j + 1],
                h_ref[0, j, 0], do_ref[0, j].astype(_F32), ds_scr[j], mm,
            )
            dq, dk, dKK, dQK = dq + dq_j, dk + dk_j, dKK + dKK_j, dQK + dQK_j
            dv_ref[0, j] = dv.astype(dv_ref.dtype)
            dg_ref[0, 0, 0, j : j + 1] = dg_row
            db_ref[0, 0, 0, j : j + 1] = db_row
            ds_scr[j] = dS0
        # through the key head's two products, once for its value heads
        dq = dq + _dot(dQK, k, _NN, mm)
        dk = dk + _dot(dQK, q, _TN, mm) + _dot(dKK, k, _NN, mm) + _dot(dKK, k, _TN, mm)
        dq_ref[0, kh] = dq.astype(dq_ref.dtype)
        dk_ref[0, kh] = dk.astype(dk_ref.dtype)


def _key_heads_per_step(Hk: int, r: int) -> int:
    want = max(1, _VALUE_HEADS_PER_STEP // r)
    while Hk % want:
        want -= 1
    return want


def _specs(chunk, kheads, r, dk, dv, nt, reverse):
    """The block specs of a launch over ``(B, Hk / kheads, nt)``: a key
    head's block, its value heads' block, the rows of ``g`` and ``b``, the
    kept states; the chunks in reverse for the backward (the state's
    cotangent flows from the last one)."""
    at = (lambda c: nt - 1 - c) if reverse else (lambda c: c)
    key = pl.BlockSpec((1, kheads, chunk, dk), lambda b, h, c: (b, h, at(c), 0))
    value = pl.BlockSpec((1, kheads * r, chunk, dv), lambda b, h, c: (b, h, at(c), 0))
    rows = pl.BlockSpec((1, 1, 1, kheads * r, chunk), lambda b, h, c: (b, h, at(c), 0, 0))
    states = pl.BlockSpec((1, kheads * r, 1, dk, dv), lambda b, h, c: (b, h, at(c), 0, 0))
    return key, value, rows, states


def _fwd(q, k, v, g, beta, chunk, kheads, interpret):
    """Heads-major in: ``q, k [B, Hk, S, dk]``, ``v [B, Hv, S, dv]``, ``g,
    beta [B, Hk / kheads, S / C, kheads * r, C]`` float32; ``(o [B, Hv, S,
    dv], h [B, Hv, S / C, dk, dv])`` out, ``h`` the state every chunk started
    from."""
    B, Hk, S, dk = q.shape
    Hv, dv = v.shape[1], v.shape[3]
    r, nt = Hv // Hk, S // chunk
    key, value, rows, states = _specs(chunk, kheads, r, dk, dv, nt, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, kheads=kheads, r=r),
        grid=(B, Hk // kheads, nt),
        in_specs=[key, key, value, rows, rows],
        out_specs=[value, states],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((B, Hv, nt, dk, dv), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((kheads * r, dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_fwd",
    )(q, k, v, g, beta)


def _bwd(q, k, v, g, beta, h, do, chunk, kheads, interpret):
    B, Hk, S, dk = q.shape
    Hv, dv = v.shape[1], v.shape[3]
    r, nt = Hv // Hk, S // chunk
    key, value, rows, states = _specs(chunk, kheads, r, dk, dv, nt, reverse=True)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_kernel, kheads=kheads, r=r),
        grid=(B, Hk // kheads, nt),
        in_specs=[key, key, value, rows, rows, states, value],
        out_specs=[key, key, value, rows, rows],
        out_shape=[like(q), like(k), like(v), like(g), like(beta)],
        scratch_shapes=[pltpu.VMEM((kheads * r, dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_bwd",
    )(q, k, v, g, beta, h, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _gdn_hm(q, k, v, g, beta, chunk, kheads, interpret):
    return _fwd(q, k, v, g, beta, chunk, kheads, interpret)[0]


def _gdn_hm_fwd(q, k, v, g, beta, chunk, kheads, interpret):
    o, h = _fwd(q, k, v, g, beta, chunk, kheads, interpret)
    return o, (q, k, v, g, beta, h)


def _gdn_hm_bwd(chunk, kheads, interpret, res, do):
    return _bwd(*res, do, chunk, kheads, interpret)


_gdn_hm.defvjp(_gdn_hm_fwd, _gdn_hm_bwd)


# ---------------------------------------------------------------------------
# public entries ([B, S, H, D], the model's layout)
# ---------------------------------------------------------------------------


def _prepare(q, k, v, g, beta, scale, chunk, kheads):
    """Heads-major ``q * scale, k, v`` and the rows of ``g`` and ``beta``
    (float32), ``[B, Hk / kheads, S / C, kheads * r, C]``: value head ``j``
    beside the others of its key head's group."""
    B, S, Hk, dk = q.shape
    Hv = v.shape[2]
    if S % chunk or Hv % Hk or g.shape != (B, S, Hv) or beta.shape != (B, S, Hv):
        raise ValueError(
            f"S={S} not divisible by the chunk {chunk}, {Hv} value heads not by {Hk} key heads, "
            f"or g {g.shape} and beta {beta.shape} not a value head's"
        )
    if scale is None:
        scale = dk ** -0.5
    hm = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    per_step = kheads * (Hv // Hk)

    def rows(x):  # [B, S, Hv] -> [B, Hk / kheads, S / C, kheads * r, C]
        return x.astype(_F32).reshape(B, S // chunk, chunk, Hv // per_step, per_step).transpose(0, 3, 1, 4, 2)

    return hm((q.astype(_F32) * scale).astype(q.dtype)), hm(k), hm(v), rows(g), rows(beta)


def gdn_chunked(
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
    chunked kernels.  ``q, k`` ``[B, S, Hk, dk]``, ``v`` ``[B, S, Hv, dv]``,
    ``g, beta`` ``[B, S, Hv]`` with ``Hv % Hk == 0``; ``g`` is the log of the
    decay, any number that is not positive.  Returns ``[B, S, Hv, dv]`` in
    ``v``'s type.  ``S`` must be a multiple of ``chunk``."""
    chunk = min(chunk, q.shape[1])
    kheads = _key_heads_per_step(q.shape[2], v.shape[2] // q.shape[2])
    args = _prepare(q, k, v, g, beta, scale, chunk, kheads)
    return _gdn_hm(*args, chunk, kheads, interpret).transpose(0, 2, 1, 3)


def gdn_chunked_plain(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    *,
    scale: float | None = None,
    chunk: int = 64,
) -> jax.Array:
    """:func:`gdn_chunked`'s algebra with no kernel: a ``lax.scan`` over the
    chunks of :func:`_key_head_fwd`, differentiated by jax."""
    chunk = min(chunk, q.shape[1])
    qh, kh, vh, g_rows, b_rows = _prepare(q, k, v, g, beta, scale, chunk, 1)
    B, Hk, S, dk = qh.shape
    Hv, dv = vh.shape[1], vh.shape[3]
    r, nt = Hv // Hk, S // chunk

    def chunks(x):  # [B, Hk, S, d] -> [S/C, B, Hk, C, d]
        return x.reshape(B, Hk, nt, chunk, x.shape[-1]).transpose(2, 0, 1, 3, 4)

    vs = vh.reshape(B, Hk, r, nt, chunk, dv).transpose(3, 0, 1, 2, 4, 5)  # [S/C, B, Hk, r, C, dv]
    step = jax.vmap(jax.vmap(_key_head_fwd))

    def body(S0, xs):
        O, S1 = step(*xs, S0)
        return S1, O

    xs = (chunks(qh), chunks(kh), vs, g_rows.transpose(2, 0, 1, 3, 4), b_rows.transpose(2, 0, 1, 3, 4))
    _, O = jax.lax.scan(body, jnp.zeros((B, Hk, r, dk, dv), _F32), xs)  # [S/C, B, Hk, r, C, dv]
    return O.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, Hv, dv).astype(v.dtype)
