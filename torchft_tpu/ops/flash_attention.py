"""Fused causal GQA flash attention (Pallas, TPU) — forward and backward.

The naive attention path materializes the [B, H, S, S] score matrix in HBM
(~400 MB per layer at S=1024 in the bench config) — pure HBM-bandwidth tax.
This is the standard flash construction tiled for the TPU: the grid's k
dimension is innermost (the TPU grid is a sequential loop, so VMEM scratch
carries the online-softmax accumulators across k-blocks), fp32
accumulation, bf16 MXU matmuls.  The reference's GPU analog is
torch SDPA/flash; here it is a first-party kernel because the framework is
standalone (SURVEY.md §2.2 Triton-kernels row).

GQA is handled in the BlockSpec index maps: k/v blocks for q-head ``h``
are fetched from kv-head ``h // groups`` directly, so grouped K/V are
never repeated to full head count in HBM (the naive path's ``jnp.repeat``
costs ``groups``× K/V bandwidth).

Backward is the standard two-kernel flash scheme over the saved
logsumexp: ``dq`` accumulates over k-blocks; ``dk``/``dv`` accumulate over
(q-head-in-group × q-block) so each kv-head's gradient sums its whole GQA
group without materializing per-q-head copies.  Without a window the grid
walks every (row block, key block) pair and the causally-dead ones are
skipped with ``pl.when`` in both directions: their blocks are still fetched.

With a ``window`` (a sliding window: query ``i`` sees keys ``j`` with ``i -
window < j <= i``, its own position counted) the grid does not hold the dead
pairs at all.  Forward and ``dq`` walk, for a row block, only the key blocks
its rows' windows touch (``_key_steps`` of them at most, ending at the
diagonal block: the index map is offset by the row block), ``dkv`` walks for
a key block only the row blocks that can see it (the mirror, starting at the
diagonal), and the two edge blocks are masked inside.  At 16,384 positions,
a window of 2,048 and blocks of 512 that is 5 key blocks a row block against
16.5 on average.  These programs are named ``flash_win_fwd``,
``flash_win_dq`` and ``flash_win_dkv``, so that a trace tells a windowed
layer's kernels from a full layer's; a window that covers the sequence IS
causal attention and takes the full layers' programs.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128  # accumulator minor dim (TPU lane width)
# rowwise stats (lse, delta) carry a trailing 8-lane dim: Mosaic requires
# the last block dim be 128-divisible OR equal to the full array dim, and a
# [B,H,S]-shaped output tiled (1,1,bq) satisfies neither
_ROW_LANES = 8


# ---------------------------------------------------------------------------
# which pairs are alive
# ---------------------------------------------------------------------------


def _masked(s, qi, ki, block_q, block_k, window):
    """Scores [bq, bk] of row block ``qi`` against key block ``ki`` with the
    dead pairs (a later key; with a window, one ``window`` or more back) at
    ``_NEG_INF``."""
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    keep = rows >= cols
    if window is not None:
        keep = keep & (cols > rows - window)
    return jnp.where(keep, s, _NEG_INF)


def _key_steps(nq, nk, block_q, block_k, window):
    """The most key blocks one row block's live pairs touch under a window:
    from its first row's oldest key to the diagonal block."""
    most = max(
        (i * block_q + block_q - 1) // block_k - (i * block_q - window + 1) // block_k + 1
        for i in range(nq)
    )
    return min(most, nk)


def _row_steps(nq, nk, block_q, block_k, window):
    """The mirror: the most row blocks that see one key block, from the
    diagonal block to its last key's last reader."""
    most = max(
        (i * block_k + block_k + window - 2) // block_q - (i * block_k) // block_q + 1
        for i in range(nk)
    )
    return min(most, nq)


def _walked_k(qi, step, block_q, block_k, steps):
    """The key block of row block ``qi``'s ``step``-th visit: the walk ENDS
    at the diagonal block, so an early row block's first visits fall before
    the sequence (negative: dead, and the index map holds them at 0)."""
    return jax.lax.div(qi * block_q + block_q - 1, block_k) - (steps - 1) + step


def _walked_q(ki, step, block_q, block_k):
    """The row block of key block ``ki``'s ``step``-th visit: the walk STARTS
    at the diagonal block, so a late key block's last visits fall past the
    sequence (dead, and the index map holds them at the last block)."""
    return jax.lax.div(ki * block_k, block_q) + step


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref,  # [1, 1, bq, D]
    k_ref,  # [1, 1, bk, D]
    v_ref,  # [1, 1, bk, D]
    o_ref,  # [1, 1, bq, D]
    lse_ref,  # [1, 1, bq, _ROW_LANES]
    m_scr,  # VMEM [bq, _LANES] f32: running row max
    l_scr,  # VMEM [bq, _LANES] f32: running denominator
    acc_scr,  # VMEM [bq, D] f32: running (unnormalized) output
    *,
    sm_scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    num_k_blocks: int,  # the grid's steps a row block: with a window, its walk
    window: Optional[int] = None,
):
    qi = pl.program_id(2)
    step = pl.program_id(3)

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if window is None:
        ki = step
        # with causality, k-blocks wholly above the diagonal are dead
        live = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)
    else:
        ki = _walked_k(qi, step, block_q, block_k, num_k_blocks)
        # the walk ends at the diagonal; a block is dead before the sequence
        # or where its last key is older than the first row's window
        live = (ki >= 0) & ((ki + 1) * block_k + window - 2 >= qi * block_q)

    @pl.when(live)
    def _accumulate():
        q = q_ref[0, 0]  # [bq, D]
        k = k_ref[0, 0]  # [bk, D]
        v = v_ref[0, 0]

        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * sm_scale
        )  # [bq, bk] f32
        if causal:
            s = _masked(s, qi, ki, block_q, block_k, window)

        m_prev = m_scr[:, :1]  # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # [bq, bk]
        correction = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_new = l_scr[:, :1] * correction + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(step == num_k_blocks - 1)
    def _finalize():
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        denom = jnp.where(l > 0.0, l, 1.0)  # fully-masked rows guard
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(
            m + jnp.log(denom), (m.shape[0], _ROW_LANES)
        )


def _fwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    sm_scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    interpret: bool,
    window: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """q [B,H,Sq,D], k [B,KV,Sk,D], v [B,KV,Sk,Dv] → (o [B,H,Sq,Dv],
    lse [B,H,Sq]).  Rectangular (Sq != Sk) is allowed when not causal; v's
    head size may differ from q's and k's (latent attention: 192 and 128)."""
    B, H, S, D = q.shape
    Dv = v.shape[3]
    KV = k.shape[1]
    Sk = k.shape[2]
    groups = H // KV
    nq, nk = S // block_q, Sk // block_k
    kv_map, steps, names = _walk_of_keys(groups, nq, nk, block_q, block_k, window)
    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=steps,
        window=window,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, steps),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, Dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec(
                (1, 1, block_q, _ROW_LANES),
                lambda b, h, qi, ki: (b, h, qi, 0),
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, _ROW_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        interpret=interpret,
        name=names[0],
    )(q, k, v)


def _walk_of_keys(groups, nq, nk, block_q, block_k, window):
    """For forward and ``dq``: (the key blocks' index map over the grid ``(b,
    h, row block, step)``, the steps a row block, the programs' names)."""
    if window is None:
        return (
            lambda b, h, qi, ki: (b, h // groups, ki, 0),
            nk,
            ("flash_fwd", "flash_dq"),
        )
    steps = _key_steps(nq, nk, block_q, block_k, window)

    def kv_map(b, h, qi, step):
        ki = _walked_k(qi, step, block_q, block_k, steps)
        return (b, h // groups, jnp.maximum(ki, 0), 0)

    return kv_map, steps, ("flash_win_fwd", "flash_win_dq")


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _recompute_p_ds(
    q, k, lse, do, v, delta, sm_scale, causal, qi, ki, block_q, block_k,
    window=None,
):
    """Shared backward math for one (q-block, k-block) pair: the normalized
    probabilities ``p`` and score-gradient ``ds`` (both [bq, bk], f32).
    ``lse``/``delta`` are [bq, 1] column vectors."""
    s = (
        jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * sm_scale
    )
    if causal:
        s = _masked(s, qi, ki, block_q, block_k, window)
    p = jnp.exp(s - lse)  # normalized probabilities
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bq, bk]
    ds = p * (dp - delta) * sm_scale
    return p, ds


def _dq_kernel(
    q_ref, k_ref, v_ref, lse_ref, do_ref, delta_ref, dq_ref, dq_scr,
    *, sm_scale, causal, block_q, block_k, num_k_blocks, window=None,
):
    qi = pl.program_id(2)
    step = pl.program_id(3)

    @pl.when(step == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    if window is None:
        ki = step
        live = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)
    else:  # the forward's walk
        ki = _walked_k(qi, step, block_q, block_k, num_k_blocks)
        live = (ki >= 0) & ((ki + 1) * block_k + window - 2 >= qi * block_q)

    @pl.when(live)
    def _accumulate():
        _, ds = _recompute_p_ds(
            q_ref[0, 0], k_ref[0, 0], lse_ref[0, 0][:, :1], do_ref[0, 0],
            v_ref[0, 0], delta_ref[0, 0][:, :1], sm_scale, causal, qi, ki,
            block_q, block_k, window,
        )
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(step == num_k_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, lse_ref, do_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, sm_scale, causal, block_q, block_k, num_q_blocks, inner_steps,
    window=None, walk=None,
):
    # ``walk``: with a window, the row blocks a group member visits
    ki = pl.program_id(2)
    inner = pl.program_id(3)  # flattened (g, qi): sums the whole GQA group
    if window is None:
        qi = inner % num_q_blocks
    else:
        qi = _walked_q(ki, inner % walk, block_q, block_k)

    @pl.when(inner == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if window is None:
        live = (not causal) or (qi * block_q + block_q - 1 >= ki * block_k)
    else:
        # the walk starts at the diagonal; a block is dead past the sequence
        # or where its first row is past the last key's window
        live = (qi < num_q_blocks) & (qi * block_q <= (ki + 1) * block_k + window - 2)

    @pl.when(live)
    def _accumulate():
        p, ds = _recompute_p_ds(
            q_ref[0, 0], k_ref[0, 0], lse_ref[0, 0][:, :1], do_ref[0, 0],
            v_ref[0, 0], delta_ref[0, 0][:, :1], sm_scale, causal, qi, ki,
            block_q, block_k, window,
        )
        do = do_ref[0, 0]
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # p^T @ do: [bk, D]
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0, 0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # ds^T @ q: [bk, D]

    @pl.when(inner == inner_steps - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd(
    sm_scale, causal, block_q, block_k, interpret, residuals, do, dlse=None,
    window=None,
):
    """``dlse`` (optional, [B, H, S]): cotangent of the logsumexp output.
    Since ∂lse_i/∂s_ij = p_ij, it folds into the existing delta term:
    ds = p·(dp − (delta − dlse)) — the kernels are unchanged."""
    q, k, v, o, lse = residuals
    B, H, S, D = q.shape
    Dv = v.shape[3]
    KV = k.shape[1]
    Sk = k.shape[2]
    groups = H // KV
    nq, nk = S // block_q, Sk // block_k

    delta_rows = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32),
        axis=-1,
        keepdims=True,
    )
    if dlse is not None:
        delta_rows = delta_rows - dlse[..., None].astype(jnp.float32)
    delta = jnp.broadcast_to(delta_rows, (B, H, S, _ROW_LANES))

    q_map = lambda b, h, qi, ki: (b, h, qi, 0)
    kv_map, steps, names = _walk_of_keys(groups, nq, nk, block_q, block_k, window)
    row_map = lambda b, h, qi, ki: (b, h, qi, 0)
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_k_blocks=steps, window=window,
        ),
        grid=(B, H, nq, steps),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, Dv), kv_map),
            pl.BlockSpec((1, 1, block_q, _ROW_LANES), row_map),
            pl.BlockSpec((1, 1, block_q, Dv), q_map),
            pl.BlockSpec((1, 1, block_q, _ROW_LANES), row_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name=names[1],
    )(q, k, v, lse, do, delta)

    # dk/dv: grid inner dim flattens (group member, q block) so the scratch
    # accumulator sums the whole GQA group for this kv head
    if window is None:
        walk = nq
        g_q_map = lambda b, kv, ki, i: (b, kv * groups + i // nq, i % nq, 0)
        g_row_map = lambda b, kv, ki, i: (b, kv * groups + i // nq, i % nq, 0)
    else:
        # the mirror of the forward's walk: the row blocks that see this key block
        walk = _row_steps(nq, nk, block_q, block_k, window)

        def g_q_map(b, kv, ki, i):
            qi = _walked_q(ki, i % walk, block_q, block_k)
            return (b, kv * groups + i // walk, jnp.minimum(qi, nq - 1), 0)

        g_row_map = g_q_map
    inner = groups * walk
    g_kv_map = lambda b, kv, ki, i: (b, kv, ki, 0)
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_q_blocks=nq,
            inner_steps=inner, window=window, walk=walk,
        ),
        grid=(B, KV, nk, inner),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), g_q_map),
            pl.BlockSpec((1, 1, block_k, D), g_kv_map),
            pl.BlockSpec((1, 1, block_k, Dv), g_kv_map),
            pl.BlockSpec((1, 1, block_q, _ROW_LANES), g_row_map),
            pl.BlockSpec((1, 1, block_q, Dv), g_q_map),
            pl.BlockSpec((1, 1, block_q, _ROW_LANES), g_row_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), g_kv_map),
            pl.BlockSpec((1, 1, block_k, Dv), g_kv_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_dkv" if window is None else "flash_win_dkv",
    )(q, k, v, lse, do, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry (custom_vjp over heads-major layout)
# ---------------------------------------------------------------------------


def _validate(q, k, v, causal, sm_scale, block_q, block_k, window=None):
    """Shared shape/divisibility validation for the public wrappers
    ([B, S, H, D] layout).  Returns the resolved (sm_scale, bq, bk).  q and
    k share a head size; v may have its own (the output has v's).  A
    ``window`` is a whole number of positions, at least 1 (the query's own),
    and means something under causality only."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    Sk = k.shape[1]
    if H % KV:
        raise ValueError(f"GQA needs H % KV == 0, got H={H} KV={KV}")
    if k.shape[3] != D or v.shape[:3] != k.shape[:3]:
        raise ValueError(
            f"q and k need one head size and v k's other dims, got "
            f"q={q.shape} k={k.shape} v={v.shape}"
        )
    if causal and Sk != S:
        raise ValueError(
            f"causal attention (with or without a window) needs Sq == Sk, "
            f"got Sq={S} Sk={Sk}"
        )
    if window is not None and (
        not causal or isinstance(window, bool)
        or not isinstance(window, (int, np.integer)) or window < 1
    ):
        raise ValueError(
            f"a window is a static whole number of positions >= 1 (the "
            f"query's own counts) over causal attention, got "
            f"window={window!r} causal={causal}"
        )
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    if S % block_q or Sk % block_k:
        raise ValueError(
            f"Sq={S}/Sk={Sk} not divisible by blocks ({block_q},{block_k}); "
            f"a window need not be (its edge blocks are masked inside)"
        )
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(D))
    return float(sm_scale), block_q, block_k


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_hm(q, k, v, sm_scale, causal, block_q, block_k, interpret, window):
    o, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret, window)
    return o


# the forward rule's residuals that a rematerialising caller's policy may
# keep, so that ``flash_fwd`` runs once a step (a policy that lists neither,
# as ``nothing_saveable``, is served as before)
KEPT_NAMES = ("flash_o", "flash_lse")


def _flash_hm_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret, window):
    o, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret, window)
    o, lse = (checkpoint_name(a, n) for a, n in zip((o, lse), KEPT_NAMES))
    return o, (q, k, v, o, lse)


def _flash_hm_bwd(sm_scale, causal, block_q, block_k, interpret, window, res, do):
    return _bwd(sm_scale, causal, block_q, block_k, interpret, res, do, window=window)


_flash_hm.defvjp(_flash_hm_fwd, _flash_hm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_hm_lse(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    """Heads-major flash returning (o, lse [B,H,S] f32) — for callers that
    merge partial attention results across blocks (ring attention)."""
    o, lse4 = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return o, lse4[..., 0]


def _flash_hm_lse_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    o, lse4 = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return (o, lse4[..., 0]), (q, k, v, o, lse4)


def _flash_hm_lse_bwd(sm_scale, causal, block_q, block_k, interpret, res, cts):
    do, dlse = cts
    return _bwd(
        sm_scale, causal, block_q, block_k, interpret, res, do, dlse=dlse
    )


_flash_hm_lse.defvjp(_flash_hm_lse_fwd, _flash_hm_lse_bwd)


def flash_attention_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Like :func:`flash_attention` but also returns the rowwise logsumexp
    (``[B, S, H]``, f32), so partial results over different K/V blocks can
    be merged exactly: ``lse = logaddexp(lse1, lse2)``,
    ``o = o1·exp(lse1−lse) + o2·exp(lse2−lse)``.  Differentiable in both
    outputs (the lse cotangent folds into the backward delta term).

    K/V may carry a different sequence length than q (partial-block
    attention) when ``causal=False``."""
    sm_scale, block_q, block_k = _validate(
        q, k, v, causal, sm_scale, block_q, block_k
    )
    o, lse = _flash_hm_lse(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        float(sm_scale),
        causal,
        block_q,
        block_k,
        interpret,
    )
    return o.transpose(0, 2, 1, 3), lse.transpose(0, 2, 1)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Fused differentiable attention in the model's native layout.

    q: [B, S, H, D]; k/v: [B, S, KV, D] with H % KV == 0 (GQA, un-repeated).
    Returns [B, S, H, D].  S must be divisible by the block sizes (the
    Llama dispatch falls back to the naive path otherwise).

    ``window`` (static): query ``i`` sees keys ``j`` with ``i - window < j
    <= i``.  ``None``, or a window that covers the sequence, is causal
    attention over every earlier key (``flash_fwd``, ``flash_dq``,
    ``flash_dkv``); a shorter one walks only the blocks the window touches
    (``flash_win_fwd``, ``flash_win_dq``, ``flash_win_dkv``) and need be no
    multiple of a block.
    """
    sm_scale, block_q, block_k = _validate(
        q, k, v, causal, sm_scale, block_q, block_k, window
    )
    if window is not None and window >= q.shape[1]:
        window = None

    # kernel layout: heads-major so a (bq, D) block is contiguous in S,D
    out = _flash_hm(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        float(sm_scale),
        causal,
        block_q,
        block_k,
        interpret,
        None if window is None else int(window),
    )
    return out.transpose(0, 2, 1, 3)


def flash_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh,
    dp_axis: str = "dp",
    tp_axis: str = "tp",
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention under an SPMD mesh: batch over ``(dp, fsdp)``,
    heads over ``tp``.

    A bare ``pallas_call`` is not SPMD-partitionable, so inside a sharded
    jit it would force operand replication; attention is embarrassingly
    parallel over (batch, head), so a shard_map manual over the whole mesh
    with specs ``P((dp, fsdp), None, tp, None)`` runs the kernel on local
    blocks with zero communication.  The batch dim carries the ``fsdp``
    axis because activations shard over it (``Llama.batch_specs`` — FSDP
    is data parallelism); a dp-only spec would make XLA all-gather q/k/v
    over ``fsdp`` at every layer.  ``sp``/``pp``/``ep`` paths have their
    own attention plumbing and must not route here.

    Requires B % (dp*fsdp) == 0, H % tp == 0, KV % tp == 0 (so each shard
    keeps the full GQA group ratio).
    """
    from jax.sharding import PartitionSpec as P

    B, S, H, D = q.shape
    KV = k.shape[2]
    dp = mesh.shape[dp_axis]
    fsdp_axis = "fsdp" if "fsdp" in mesh.shape else None
    bp = dp * (mesh.shape[fsdp_axis] if fsdp_axis else 1)
    tp = mesh.shape[tp_axis]
    if B % bp or H % tp or KV % tp:
        raise ValueError(
            f"flash_attention_sharded needs B%(dp*fsdp)==0, H%tp==0, "
            f"KV%tp==0; got B={B} H={H} KV={KV} over dp*fsdp={bp} tp={tp}"
        )

    batch_entry = (dp_axis, fsdp_axis) if fsdp_axis else dp_axis
    spec = P(batch_entry, None, tp_axis, None)
    body = functools.partial(
        flash_attention,
        causal=causal,
        sm_scale=sm_scale,  # None → flash_attention derives 1/sqrt(D)
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
