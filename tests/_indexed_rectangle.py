"""The launches of ``ops/indexed_attention.py`` as they stood before PR 53:
``dsa_attn_fwd``, ``dsa_attn_dq``, ``dsa_attn_dkv`` and ``dsa_probs`` over the
whole RECTANGLE of (row block, key block) pairs, a dead pair a grid step that
does nothing (``pl.when``) and fetches nothing (the index maps stay on the
last live block).  Kept word for word (the bodies' shared pieces come from the
package, which did not change them) as the oracle of
``tests/test_indexed_attention.py``.  What it still holds BIT FOR BIT: ``dq``,
``dk``, ``dv`` and ``L_I``'s four (``kl`` and its three gradients), both
modules handed the tree's ``o`` and ``lse``.  Its forward is the rows-major
body the package had until PR 65 (the maximum and the sum along the lanes,
``lse`` out as ``[B, H, S, 8]``): the tree's keys-major forward sums a row's
denominator in another order, so ``o`` and ``lse`` are held to it at
float32's rounding, not bit for bit.  Nothing of the package reads this
file."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.ops.indexed_attention import (
    _LANES, _NEG_INF, _ROW_LANES, _dot_0, _dot_t, _index_scores, _masked_scores, _p_and_ds, _params, _tile_bits,
)


def _attn_fwd_kernel(
    q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, sm_scale, group, block_q, block_k, num_k_blocks,
):
    qi, ki = pl.program_id(2), pl.program_id(3)
    D = q_ref.shape[-1]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki * block_k <= qi * block_q + block_q - 1)
    def _accumulate():
        q = q_ref[0].reshape(group * block_q, D)
        v = v_ref[0, 0]
        s = _masked_scores(q, k_ref[0, 0], _tile_bits(mask_ref, ki), sm_scale, group)
        # a row that picked nothing in the blocks so far keeps m at _NEG_INF
        # and adds exp(0) here; the first picked key's correction,
        # exp(_NEG_INF - m), wipes that to exactly 0
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * correction + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[...] / l).reshape(group, block_q, D).astype(o_ref.dtype)
        lse = m_scr[:, :1] + jnp.log(l)
        lse_ref[0] = jnp.broadcast_to(lse, (group * block_q, _ROW_LANES)).reshape(
            group, block_q, _ROW_LANES
        )


def _attn_dq_kernel(
    q_ref, k_ref, v_ref, mask_ref, lse_ref, do_ref, delta_ref, dq_ref, dq_scr,
    *, sm_scale, group, block_q, block_k, num_k_blocks,
):
    qi, ki = pl.program_id(2), pl.program_id(3)
    D = q_ref.shape[-1]
    rows = group * block_q

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(ki * block_k <= qi * block_q + block_q - 1)
    def _accumulate():
        k = k_ref[0, 0]
        _, ds = _p_and_ds(
            q_ref[0].reshape(rows, D), k, v_ref[0, 0], do_ref[0].reshape(rows, D),
            lse_ref[0].reshape(rows, _ROW_LANES)[:, :1], delta_ref[0].reshape(rows, _ROW_LANES)[:, :1],
            _tile_bits(mask_ref, ki), sm_scale, group,
        )
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].reshape(group, block_q, D).astype(dq_ref.dtype)


def _attn_dkv_kernel(
    q_ref, k_ref, v_ref, mask_ref, lse_ref, do_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
    *, sm_scale, group, block_q, block_k, num_q_blocks,
):
    ki, qi = pl.program_id(2), pl.program_id(3)
    D = q_ref.shape[-1]
    rows = group * block_q

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
    def _accumulate():
        q, do = q_ref[0].reshape(rows, D), do_ref[0].reshape(rows, D)
        p, ds = _p_and_ds(
            q, k_ref[0, 0], v_ref[0, 0], do,
            lse_ref[0].reshape(rows, _ROW_LANES)[:, :1], delta_ref[0].reshape(rows, _ROW_LANES)[:, :1],
            _tile_bits(mask_ref, ki), sm_scale, group,
        )
        # contracting the stacked rows sums the whole group of query heads
        dv_scr[...] += _dot_0(p.astype(do.dtype), do)
        dk_scr[...] += _dot_0(ds.astype(q.dtype), q)

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _attn_specs(group, bq, bk, D):
    """Block specs of the q-major kernels' grid ``(B, KV, nq, nk)``.  A key
    block wholly after the query block is never read: the index maps stay
    on the last live one, so nothing is fetched for it."""
    last = lambda qi: (qi * bq + bq - 1) // bk  # noqa: E731
    q_spec = pl.BlockSpec((1, group, bq, D), lambda b, h, qi, ki: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki: (b, h, jnp.minimum(ki, last(qi)), 0))
    mask_spec = pl.BlockSpec(
        (1, 1, bq, bk), lambda b, h, qi, ki: (b, jnp.minimum(ki, last(qi)) // 32, qi, 0)
    )
    row_spec = pl.BlockSpec((1, group, bq, _ROW_LANES), lambda b, h, qi, ki: (b, h, qi, 0))
    return q_spec, kv_spec, mask_spec, row_spec


def _attn_fwd(q, k, v, mask, sm_scale, blocks, interpret):
    """q [B, H, S, D], k and v [B, KV, S, D] → (o [B, H, S, D], lse
    [B, H, S, 8])."""
    B, H, S, D = q.shape
    KV = k.shape[1]
    group = H // KV
    bq, bk = blocks.q, blocks.k
    nq, nk = S // bq, S // bk
    q_spec, kv_spec, mask_spec, row_spec = _attn_specs(group, bq, bk, D)
    return pl.pallas_call(
        functools.partial(
            _attn_fwd_kernel, sm_scale=sm_scale, group=group, block_q=bq, block_k=bk, num_k_blocks=nk
        ),
        grid=(B, KV, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, mask_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, S, _ROW_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((group * bq, _LANES), jnp.float32),
            pltpu.VMEM((group * bq, _LANES), jnp.float32),
            pltpu.VMEM((group * bq, D), jnp.float32),
        ],
        compiler_params=_params("parallel", "parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="dsa_attn_fwd",
    )(q, k, v, mask)


def _attn_bwd(q, k, v, mask, o, lse, do, sm_scale, blocks, interpret):
    B, H, S, D = q.shape
    KV = k.shape[1]
    group = H // KV
    bq, bk = blocks.q, blocks.k
    nq, nk = S // bq, S // bk
    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True),
        (B, H, S, _ROW_LANES),
    )
    q_spec, kv_spec, mask_spec, row_spec = _attn_specs(group, bq, bk, D)
    dq = pl.pallas_call(
        functools.partial(
            _attn_dq_kernel, sm_scale=sm_scale, group=group, block_q=bq, block_k=bk, num_k_blocks=nk
        ),
        grid=(B, KV, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, mask_spec, row_spec, q_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((group * bq, D), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="dsa_attn_dq",
    )(q, k, v, mask, lse, do, delta)

    # k-major: a query block wholly before the key block is never read
    first = lambda ki: (ki * bk) // bq  # noqa: E731
    at = lambda ki, qi: jnp.maximum(qi, first(ki))  # noqa: E731
    gq_spec = pl.BlockSpec((1, group, bq, D), lambda b, h, ki, qi: (b, h, at(ki, qi), 0))
    gkv_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, ki, qi: (b, h, ki, 0))
    gmask_spec = pl.BlockSpec((1, 1, bq, bk), lambda b, h, ki, qi: (b, ki // 32, at(ki, qi), 0))
    grow_spec = pl.BlockSpec((1, group, bq, _ROW_LANES), lambda b, h, ki, qi: (b, h, at(ki, qi), 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _attn_dkv_kernel, sm_scale=sm_scale, group=group, block_q=bq, block_k=bk, num_q_blocks=nq
        ),
        grid=(B, KV, nk, nq),
        in_specs=[gq_spec, gkv_spec, gkv_spec, gmask_spec, grow_spec, gq_spec, grow_spec],
        out_specs=[gkv_spec, gkv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32), pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="dsa_attn_dkv",
    )(q, k, v, mask, lse, do, delta)
    return dq, dk, dv


def _probs_kernel(
    q_ref, k_ref, lse_ref, mask_ref, qi_ref, w_ref, ki_ref, stat_ref, kl_ref, dq_ref, dw_ref, dk_ref,
    kl_scr, dq_scr, dw_scr, *, sm_scale, kv_heads, group, heads, block_q, block_k, num_k_blocks,
):
    qi, ki = pl.program_id(1), pl.program_id(2)
    D = q_ref.shape[-1]
    rows = group * block_q

    @pl.when(ki == 0)
    def _init():
        kl_scr[...] = jnp.zeros_like(kl_scr)
        dq_scr[...] = jnp.zeros_like(dq_scr)
        dw_scr[...] = jnp.zeros_like(dw_scr)

    @pl.when((qi == 0) & (ki == 0))
    def _init_keys():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    @pl.when(ki * block_k <= qi * block_q + block_q - 1)
    def _accumulate():
        picked = _tile_bits(mask_ref, ki)
        # the head-mean of the attention's softmax over the picked keys
        p = jnp.zeros((block_q, block_k), jnp.float32)
        for g in range(kv_heads):
            heads_of = slice(g * group, (g + 1) * group)
            s = _dot_t(q_ref[0, heads_of].reshape(rows, D), k_ref[0, g]) * sm_scale
            lse = lse_ref[0, heads_of].reshape(rows, _ROW_LANES)[:, :1]
            p = p + jnp.sum(jnp.exp(s - lse).reshape(group, block_q, block_k), axis=0)
        p = jnp.where(picked, p * (1.0 / (kv_heads * group)), 0.0)
        k_index = ki_ref[0]
        log_q = _index_scores(qi_ref, w_ref, k_index, heads) - stat_ref[0][:, :1]
        kl = jnp.where(picked, p * (jnp.log(jnp.maximum(p, 1e-37)) - log_q), 0.0)
        kl_scr[...] += jnp.broadcast_to(jnp.sum(kl, axis=1, keepdims=True), kl_scr.shape)
        # d L_I / d score = softmax_S(I) - p; through the relu to each head
        d_score = jnp.where(picked, jnp.exp(log_q) - p, 0.0)
        for j in range(heads):
            q_j = qi_ref[0, j]
            dots = _dot_t(q_j, k_index)
            dw_scr[j] += jnp.broadcast_to(
                jnp.sum(d_score * jnp.maximum(dots, 0.0), axis=1, keepdims=True), dw_scr.shape[1:]
            )
            d_dots = jnp.where(dots > 0.0, d_score * w_ref[0, j][:, :1], 0.0).astype(q_j.dtype)
            dq_scr[j] += jax.lax.dot_general(
                d_dots, k_index, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            dk_ref[0, ki] += _dot_0(d_dots, q_j)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        kl_ref[0] = kl_scr[:, :_ROW_LANES]
        dq_ref[0] = dq_scr[...]
        dw_ref[0] = dw_scr[:, :, :_ROW_LANES]


def _index_loss(q, k, lse, mask, q_index, w, k_index, lse_index, sm_scale, blocks, interpret):
    """``sum_s p log(p / softmax_S(I))`` of every row, [B, S] float32, and
    its gradient to ``q_index`` [B, J, S, DI], ``w`` [B, J, S] and
    ``k_index`` [B, S, DI], all float32, in the one pass (the gradient
    needs no cotangent but a scalar's, and the tile's probabilities are
    the expensive part of both).  q [B, H, S, D], k [B, KV, S, D], lse
    [B, H, S, 8], q_index [B, J, S, DI], w [B, J, S, 8], k_index
    [B, S, DI], lse_index [B, S, 8]."""
    B, H, S, D = q.shape
    KV = k.shape[1]
    J, DI = q_index.shape[1], q_index.shape[3]
    bq, bk = blocks.q, blocks.k
    nq, nk = S // bq, S // bk
    last = lambda qi: (qi * bq + bq - 1) // bk  # noqa: E731
    key_at = lambda qi, ki: jnp.minimum(ki, last(qi))  # noqa: E731
    rows = lambda *lead: pl.BlockSpec(  # noqa: E731
        (1, *lead, bq, _ROW_LANES), lambda b, qi, ki: (b,) + (0,) * len(lead) + (qi, 0)
    )
    index_q_spec = pl.BlockSpec((1, J, bq, DI), lambda b, qi, ki: (b, 0, qi, 0))
    kl, dq, dw, dk = pl.pallas_call(
        functools.partial(
            _probs_kernel, sm_scale=sm_scale, kv_heads=KV, group=H // KV, heads=J, block_q=bq,
            block_k=bk, num_k_blocks=nk,
        ),
        grid=(B, nq, nk),
        in_specs=[
            pl.BlockSpec((1, H, bq, D), lambda b, qi, ki: (b, 0, qi, 0)),
            pl.BlockSpec((1, KV, bk, D), lambda b, qi, ki: (b, 0, key_at(qi, ki), 0)),
            rows(H),
            pl.BlockSpec((1, 1, bq, bk), lambda b, qi, ki: (b, key_at(qi, ki) // 32, qi, 0)),
            index_q_spec,
            rows(J),
            pl.BlockSpec((1, bk, DI), lambda b, qi, ki: (b, key_at(qi, ki), 0)),
            rows(),
        ],
        out_specs=[
            rows(),
            index_q_spec,
            rows(J),
            # every query block adds to every earlier key block: the whole
            # array stays in fast memory for a batch row
            pl.BlockSpec((1, nk, bk, DI), lambda b, qi, ki: (b, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, _ROW_LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, J, S, DI), jnp.float32),
            jax.ShapeDtypeStruct((B, J, S, _ROW_LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, nk, bk, DI), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((J, bq, DI), jnp.float32),
            pltpu.VMEM((J, bq, _LANES), jnp.float32),
        ],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
        name="dsa_probs",
    )(q, k, lse, mask, q_index, w, k_index, lse_index)
    return kl[..., 0], dq, dw[..., 0], dk.reshape(B, S, DI)
