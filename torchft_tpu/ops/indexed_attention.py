"""Attention over keys that a learned index picks (Pallas, TPU), forward and
backward, with the index's own loss.

The mechanism is DeepSeek-V3.2's sparse attention: a light index scores every
earlier position for every query, ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] .
kI[s])`` over ``J`` small heads and ONE key head, the ``topk`` best positions
``s <= t`` of a row are its key set ``S_t`` (all of them while ``t < topk``;
ties go to the lower ``s``), attention runs over ``S_t`` alone, and the index
learns from ``L_I = sum_t KL(p[t, .] || softmax_{S_t} I[t, .])`` where ``p``
is the head-mean of the attention's own softmax over ``S_t`` and carries no
gradient.  Nothing here differentiates the selection.

**The form.**  A key set is held as bits: ``mask[b, w, t, j]`` has bit ``i``
set where row ``t`` picked position ``(32 w + i) * block_k + j``, so the tile
of key block ``ki`` is ``(mask[b, ki // 32, rows, :] >> (ki % 32)) & 1``, an
elementwise read with no shuffle, and 32 MB hold 16,384 rows of 16,384
positions.  The attention's two launches and ``L_I``'s walk the LIVE
(row block, key block) pairs alone, those with a key at or before a row, and
apply that tile: their grids are ``(B, KV, steps)`` (``L_I``'s ``(B, steps)``)
over the steps that ``ops/flash_attention.py``'s ``_live_blocks`` and
``_walk`` list from the static shapes, a step's row block, key block and
first-visit and last-visit flags come from int32 tables by scalar prefetch,
and a dead pair is no grid step (``_steps``; at 16,384 positions and blocks
of 128 x 512 a launch's 4 KV heads take 8,448 steps where the rectangle held
16,384, from 25 KB of tables).  Every launch takes a row block's key blocks
ascending.  Eight query heads of a KV head are stacked into one
``[8 * block_q, D]`` operand, so a key block and a mask tile
are read once for the group.  No ``[S, S]`` array is made: the index's
float32 scores exist for one chunk of ``chunk`` query rows at a time.

**The forward's tile lies keys-major** (PR 65, as ``ops/flash_attention.py``'s
since PR 64).  ``s^T = k q^T`` is ``[block_k, 8 * block_q]``, the keys down the
sublanes and the group's heads side by side along the lanes, so the running
maximum and the denominator are elementwise maxima and sums ACROSS the tile's
vector registers and one fold of eight sublanes a column group: no reduction
along the lanes stands in the loop.  ``m`` and ``l`` are rows of
``[1, 8 * block_q]`` float32 and reach the tile by a sublane broadcast; the
accumulator lies the same way, ``[D, 8 * block_q]`` (``v^T p^T``, the
transposed-left product ``dkv`` makes), and is turned once a ROW BLOCK on its
last visit.  The bits arrive as every launch reads them, an int32 tile
``[block_q, block_k]`` of the rows-major mask; the kernel shifts and masks it
as the others do, TURNS it (64 registers through the transpose unit, beside
the products) and applies the ``[block_k, block_q]`` tile to each head's
columns.  (A keys-major copy of the mask made by XLA once a launch, read as
``[block_k, block_q]`` tiles, was 1.2 ms a launch SLOWER on the chip: PERF.md
section 6, PR 65.)  Same scores, same ``exp``, same products and operand
types as the rows-major body it replaced: the maximum is exact in any order,
``l`` is the same addends summed in another order, so ``o`` and ``lse`` are
the rectangle's to float32's rounding of that sum
(``scripts/indexed_attention_probe.py --parent`` prints the largest
difference).  The backward launch and ``dsa_probs`` keep their rows-major
tiles.

**The backward pass is ONE launch** (PR 68), named ``dsa_attn_dkv`` (the name
the benchmark's readers already count as the backward; it makes ``dq`` too).
A live pair's ``s``, ``p`` and ``ds`` are made once and feed all three
gradients: five products a pair (``q k^T``, ``do v^T``, ``ds k``, ``p^T do``,
``ds^T q``) where a ``dq`` launch and a ``dkv`` launch made seven, one
``exp``, one read of the bits.  ``dq`` sums over a row block's key blocks,
which the walk visits in a run: a scratch accumulator, flushed on the row
block's last visit.  ``dk`` and ``dv`` sum over a key block's row blocks,
which the walk does NOT visit in a run, so a KV head's WHOLE ``dk`` and
``dv`` stay in fast memory for its steps as float32 output blocks
``(1, 1, nk, block_k, D)``, zeroed at the head's first step, added to at the
key block the table names (``dk_ref[0, 0, ki] += ...``, as ``dsa_probs``
keeps a batch row's ``dk``) and written to HBM once, when the head changes;
XLA rounds them to the operands' type.  At 16,384 positions and ``D`` 128
that is 2 x 8 MB (32 MB double-buffered) of the 100 MiB the kernels may use;
``Blocks.refusal`` refuses a length whose two would not fit.  The order of
every sum is still the rectangle's: ``dq`` adds a row block's key blocks
ascending, and a key block meets its row blocks ascending because the walk
takes the row blocks ascending, which is the order the ``dkv`` launch took
them in.  So ``dq``, ``dk``, ``dv`` and ``L_I``'s four are bit for bit the
rectangle's.

Five ``pallas_call`` names, which the benchmark's readers find in a trace:

- ``dsa_index``: the scores of one chunk of rows against every causal key
  block, float32, ``[nk, chunk, block_k]``;
- ``dsa_select``: the exact ``min(t + 1, topk)`` best of every row by
  bisection on the scores' bit patterns (32 passes for the value, then
  ``log2 S`` for the cut among equal scores), the bits, the row's
  ``logsumexp`` of its picked scores and its number of keys;
- ``dsa_attn_fwd``, ``dsa_attn_dkv``: the attention, forward and backward;
- ``dsa_probs``: ``L_I``, a row at a time, from the attention's ``lse``,
  and in the same pass its gradient to ``qI``, ``kI`` and ``w`` (the
  backward pass scales it by the loss's cotangent).

**What the backward pass is handed.**  The forward rule's residuals are the
attention's operands, the bits, and five values that carry a
``checkpoint_name`` (``KEPT_NAMES``): ``o``, its ``lse`` a row as the forward
wrote it (``[B, H, S]`` float32, block ``(1, group, 1, block_q)`` of
``[B, H, 1, S]``: ONE number a row; ``dsa_probs`` and the backward launch
take theirs as ``[..., 8]`` columns, ``_row_lanes``), and ``L_I``'s gradient
to ``qI``, ``w`` and ``kI`` in float32, as ``dsa_probs`` left it.  A caller
that rematerialises its layers lists those names in its policy and
``dsa_attn_fwd`` and ``dsa_probs`` run once; one that lists none computes
both again in its backward pass, to the same bits.

:func:`indexed_attention_plain` is the same mathematics in ``jax.numpy`` with
dense ``[S, S]`` arrays: the path off the TPU and the tests' oracle.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.ops.flash_attention import Walk, _live_blocks, _walk, _where

_NEG_INF = -1e30
_LANES = 128
_ROW_LANES = 8  # rowwise outputs carry a trailing 8-lane dim (ops/flash_attention.py)
_INT_MIN = np.int32(-(2**31))
_VMEM_LIMIT = 100 * 1024 * 1024
# of which the backward launch's resident dk and dv may take three quarters (its tiles and
# its other operands' buffers take the rest: 64 MiB of accumulators compile for a v5e)
_ACCUMULATORS_LIMIT = _VMEM_LIMIT * 3 // 4
# the forward rule's residuals that a rematerialising caller's policy may keep
KEPT_NAMES = ("dsa_o", "dsa_attn_lse", "dsa_d_qi", "dsa_d_w", "dsa_d_ki")


class Blocks(NamedTuple):
    """Tile sizes: ``q`` query rows a head in the attention and ``L_I``
    kernels, ``k`` keys a block (and the width of a mask tile), ``chunk``
    query rows whose index scores exist at a time, ``s`` of them in one
    step of the selection."""

    q: int = 128
    k: int = 512
    chunk: int = 512
    s: int = 64

    def fit(self, seq: int) -> "Blocks":
        k = min(self.k, seq)
        chunk = min(self.chunk, seq)
        return Blocks(min(self.q, seq), k, chunk, min(self.s, chunk))

    def refusal(self, seq: int, head_dim: int = 128) -> str:
        """Why the kernels do not take ``seq`` positions at heads of
        ``head_dim``, or ''."""
        b = self.fit(seq)
        if seq % b.q or seq % b.k or seq % b.chunk or b.chunk % b.s or b.q % 8 or b.s % 8:
            return f"seq={seq} does not divide into blocks {tuple(b)}"
        # the backward launch's dk and dv of a KV head: two float32 [seq, head_dim]
        # output blocks, each held twice (Pallas double-buffers an output block)
        held = 2 * 2 * seq * head_dim * 4
        if held > _ACCUMULATORS_LIMIT:
            return (
                f"a KV head's dk and dv over seq={seq} at head_dim={head_dim} take {held >> 20} MiB of fast "
                f"memory in the backward launch, over {_ACCUMULATORS_LIMIT >> 20} of the {_VMEM_LIMIT >> 20} MiB "
                "it may use"
            )
        return ""


def _params(*semantics: str) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)


def _dot_t(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b.T`` with float32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _dot_0(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a.T @ b`` with float32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _row_lanes(x: jax.Array) -> jax.Array:
    """A value a row as the kernels take it: ``[..., 8]``, the lanes alike."""
    return jnp.broadcast_to(x[..., None], x.shape + (_ROW_LANES,))


def _tile_bits(mask_ref, ki: jax.Array, keys_major: bool = False) -> jax.Array:
    """The picked positions of key block ``ki`` as a boolean ``[bq, bk]``;
    ``keys_major``, the int32 tile is turned first and they are ``[bk, bq]``."""
    bits = (mask_ref[0, 0] >> (ki % 32)) & 1
    return (bits.T if keys_major else bits) != 0


def _steps(seq: int, blocks: "Blocks") -> Walk:
    """The grid steps of a launch over ``seq`` positions, the live (row block,
    key block) pairs alone (``ops/flash_attention.py``, the walk): a row
    block's key blocks ascending, in every launch."""
    return _walk(_live_blocks(seq // blocks.q, seq // blocks.k, blocks.q, blocks.k, None))


# ---------------------------------------------------------------------------
# the index: scores of one chunk of rows, and the selection
# ---------------------------------------------------------------------------


def _index_scores(q_ref, w_ref, k, heads: int) -> jax.Array:
    """``sum_j w[:, j] * relu(qI[:, j] @ k.T)``, float32 ``[rows, bk]``."""
    acc = None
    for j in range(heads):
        term = w_ref[0, j][:, :1] * jnp.maximum(_dot_t(q_ref[0, j], k), 0.0)
        acc = term if acc is None else acc + term
    return acc


def _index_kernel(c_ref, q_ref, w_ref, k_ref, out_ref, *, heads, chunk, block_k):
    ki = pl.program_id(1)
    live = ki * block_k <= c_ref[0] * chunk + chunk - 1

    @pl.when(live)
    def _():
        out_ref[0, 0] = _index_scores(q_ref, w_ref, k_ref[0], heads)


def _select_kernel(
    c_ref, s_ref, mask_ref, stat_ref, key_scr, *, topk, chunk, block_s, block_k, words, pos_bits
):
    row0 = c_ref[0] * chunk + pl.program_id(1) * block_s
    n_live = (row0 + block_s - 1) // block_k + 1
    shape = (block_s, block_k)
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col0 = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    want = jnp.minimum(rows[:, :1] + 1, topk)  # keys this row picks

    def total(per_block) -> jax.Array:
        """``sum`` over the live blocks and their columns of an int32 tile."""
        acc = jax.lax.fori_loop(
            0, n_live, lambda j, a: a + per_block(j), jnp.zeros(shape, jnp.int32)
        )
        return jnp.sum(acc, axis=1, keepdims=True)

    # float32 order as signed-integer order; positions after the row's own
    # sort below every score.  -0.0 counts as 0.0.
    def to_keys(j, best):
        s = s_ref[0, j]
        s = jnp.where(s == 0.0, 0.0, s)
        bits = jax.lax.bitcast_convert_type(s, jnp.int32)
        key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
        seen = j * block_k + col0 <= rows
        key_scr[j] = jnp.where(seen, key, _INT_MIN)
        return jnp.maximum(best, jnp.where(seen, s, _NEG_INF))

    best = jax.lax.fori_loop(0, n_live, to_keys, jnp.full(shape, _NEG_INF, jnp.float32))
    best = jnp.max(best, axis=1, keepdims=True)

    # the want-th largest key, bit by bit from the top, in unsigned order
    def value_bit(i, prefix):
        cand = prefix | (jnp.int32(1) << (31 - i))
        signed = cand ^ _INT_MIN
        n = total(lambda j: (key_scr[j] >= signed).astype(jnp.int32))
        return jnp.where(n >= want, cand, prefix)

    tau = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros((block_s, 1), jnp.int32)) ^ _INT_MIN
    need = want - total(lambda j: (key_scr[j] > tau).astype(jnp.int32))

    # among the keys equal to tau the lowest positions: the largest p with
    # fewer than ``need`` equal keys before it is the last one taken
    def position_bit(i, p):
        cand = p | (jnp.int32(1) << (pos_bits - 1 - i))
        n = total(lambda j: ((key_scr[j] == tau) & (j * block_k + col0 < cand)).astype(jnp.int32))
        return jnp.where(n < need, cand, p)

    cut = jax.lax.fori_loop(0, pos_bits, position_bit, jnp.zeros((block_s, 1), jnp.int32))

    for word in range(words):
        mask_ref[0, word] = jnp.zeros(shape, jnp.int32)

    def emit(j, carry):
        denom, count = carry
        key = key_scr[j]
        picked = (key > tau) | ((key == tau) & (j * block_k + col0 <= cut))
        mask_ref[0, j // 32] = mask_ref[0, j // 32] | (picked.astype(jnp.int32) << (j % 32))
        denom = denom + jnp.where(picked, jnp.exp(s_ref[0, j] - best), 0.0)
        return denom, count + picked.astype(jnp.int32)

    denom, count = jax.lax.fori_loop(
        0, n_live, emit, (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.int32))
    )
    lse = best + jnp.log(jnp.sum(denom, axis=1, keepdims=True))
    count = jnp.sum(count, axis=1, keepdims=True).astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_s, _ROW_LANES), 1)
    stat_ref[0] = jnp.where(lane == 0, lse, count)


def select_keys(
    q_index: jax.Array, k_index: jax.Array, w: jax.Array, *, topk: int, blocks: Blocks = Blocks(),
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The key sets.  ``q_index`` [B, S, J, DI], ``k_index`` [B, S, DI],
    ``w`` [B, S, J] float32 → (``mask`` [B, words, S, block_k] int32, the
    ``logsumexp`` of every row's picked scores [B, S] float32, its number of
    keys [B, S] float32).  Not differentiable: the operands' gradients stop
    here."""
    q_index, k_index, w = jax.lax.stop_gradient((q_index, k_index, w))
    B, S, J, DI = q_index.shape
    blocks = blocks.fit(S)
    bk, C, bs = blocks.k, blocks.chunk, blocks.s
    nk = S // bk
    words = -(-nk // 32)
    qh = q_index.transpose(0, 2, 1, 3)  # [B, J, S, DI]
    wh = _row_lanes(w.astype(jnp.float32).transpose(0, 2, 1))  # [B, J, S, 8]

    def last_live(c):
        return (c[0] * C + C - 1) // bk

    index = pl.pallas_call(
        functools.partial(_index_kernel, heads=J, chunk=C, block_k=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nk),
            in_specs=[
                pl.BlockSpec((1, J, C, DI), lambda b, ki, c: (b, 0, 0, 0)),
                pl.BlockSpec((1, J, C, _ROW_LANES), lambda b, ki, c: (b, 0, 0, 0)),
                # a block past the chunk's last row is never read: stay on the last live one
                pl.BlockSpec((1, bk, DI), lambda b, ki, c: (b, jnp.minimum(ki, last_live(c)), 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, C, bk), lambda b, ki, c: (b, ki, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, nk, C, bk), jnp.float32),
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
        name="dsa_index",
    )
    select = pl.pallas_call(
        functools.partial(
            _select_kernel, topk=topk, chunk=C, block_s=bs, block_k=bk, words=words,
            pos_bits=max(1, int(S - 1).bit_length()),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, C // bs),
            in_specs=[pl.BlockSpec((1, nk, bs, bk), lambda b, i, c: (b, 0, i, 0))],
            out_specs=[
                pl.BlockSpec((1, words, bs, bk), lambda b, i, c: (b, 0, i, 0)),
                pl.BlockSpec((1, bs, _ROW_LANES), lambda b, i, c: (b, i, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((nk, bs, bk), jnp.int32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, words, C, bk), jnp.int32),
            jax.ShapeDtypeStruct((B, C, _ROW_LANES), jnp.float32),
        ],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
        name="dsa_select",
    )

    def one_chunk(c):
        at = jnp.reshape(c, (1,)).astype(jnp.int32)
        rows = lambda a: jax.lax.dynamic_slice_in_dim(a, c * C, C, axis=2)  # noqa: E731
        scores = index(at, rows(qh), rows(wh), k_index)
        return select(at, scores)

    mask, stat = jax.lax.map(one_chunk, jnp.arange(S // C, dtype=jnp.int32))
    # [S // C, B, words, C, bk] → [B, words, S, bk]
    mask = mask.transpose(1, 2, 0, 3, 4).reshape(B, words, S, bk)
    stat = stat.transpose(1, 0, 2, 3).reshape(B, S, _ROW_LANES)
    return mask, stat[..., 0], stat[..., 1]


# ---------------------------------------------------------------------------
# attention over the picked keys
# ---------------------------------------------------------------------------


def _masked_scores(q, k, picked, sm_scale, group):
    """``[group * bq, bk]`` scores of a stacked group of query heads, with
    the positions a row did not pick at ``_NEG_INF``."""
    s = _dot_t(q, k) * sm_scale
    bq, bk = picked.shape
    s = jnp.where(picked[None], s.reshape(group, bq, bk), _NEG_INF)
    return s.reshape(group * bq, bk)


def _attn_fwd_kernel(*refs, sm_scale, group, block_q):
    """One live (row block, key block) pair of the online softmax over the
    picked keys, the tile keys-major (the module docstring): the group's
    heads lie side by side along the lanes, and every reduction over the keys
    runs down the sublanes and across vector registers."""
    *tables, q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    _, ki, first, last = _where(tables)
    D = q_ref.shape[-1]
    heads = [slice(g * block_q, (g + 1) * block_q) for g in range(group)]

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].reshape(group * block_q, D)
    v = v_ref[0, 0]
    s = _dot_t(k_ref[0, 0], q) * sm_scale  # [bk, group * bq]
    picked = _tile_bits(mask_ref, ki, keys_major=True)  # [bk, bq]
    s = jnp.concatenate([jnp.where(picked, s[:, h], _NEG_INF) for h in heads], axis=1)
    # a row that picked nothing in the blocks so far keeps m at _NEG_INF
    # and adds exp(0) here; the first picked key's correction,
    # exp(_NEG_INF - m), wipes that to exactly 0
    m_prev = m_scr[...]  # [1, group * bq]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    p = jnp.exp(s - m_new)
    correction = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * correction + jnp.sum(p, axis=0, keepdims=True)
    acc_scr[...] = acc_scr[...] * correction + _dot_0(v, p.astype(v.dtype))  # v^T p^T: [D, group * bq]
    m_scr[...] = m_new

    @pl.when(last)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / l).T.reshape(group, block_q, D).astype(o_ref.dtype)
        lse = m_scr[...] + jnp.log(l)
        for g, h in enumerate(heads):  # a head a row of [group, 1, bq]
            lse_ref[0, g] = lse[:, h]


def _p_and_ds(q, k, v, do, lse, delta, picked, sm_scale, group):
    s = _masked_scores(q, k, picked, sm_scale, group)
    p = jnp.exp(s - lse)
    dp = _dot_t(do, v)
    return p, p * (dp - delta) * sm_scale


def _attn_bwd_kernel(*refs, sm_scale, group, block_q):
    """One live (row block, key block) pair of the whole backward pass: ``p``
    and ``ds`` once, ``dq`` summed in scratch over the row block's key blocks,
    ``dk`` and ``dv`` into the KV head's whole float32 arrays, resident for a
    ``(b, h)``, at the key block the table names."""
    *tables, q_ref, k_ref, v_ref, mask_ref, lse_ref, do_ref, delta_ref, dq_ref, dk_ref, dv_ref, dq_scr = refs
    _, ki, first, last = _where(tables)
    D = q_ref.shape[-1]
    rows = group * block_q

    @pl.when(first)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(pl.program_id(2) == 0)
    def _init_keys():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    q, do, k = q_ref[0].reshape(rows, D), do_ref[0].reshape(rows, D), k_ref[0, 0]
    p, ds = _p_and_ds(
        q, k, v_ref[0, 0], do,
        lse_ref[0].reshape(rows, _ROW_LANES)[:, :1], delta_ref[0].reshape(rows, _ROW_LANES)[:, :1],
        _tile_bits(mask_ref, ki), sm_scale, group,
    )
    dq_scr[...] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # contracting the stacked rows sums the whole group of query heads
    dv_ref[0, 0, ki] += _dot_0(p.astype(do.dtype), do)
    dk_ref[0, 0, ki] += _dot_0(ds.astype(q.dtype), q)

    @pl.when(last)
    def _finalize():
        dq_ref[0] = dq_scr[...].reshape(group, block_q, D).astype(dq_ref.dtype)


def _attn_specs(group, bq, bk, D):
    """Block specs of the attention launches' grid ``(B, KV, steps)``: a
    step's row block and key block are what the walk's tables say
    (``_steps``; scalar prefetch), whichever of the two it walks by, so a
    dead pair has no step and nothing of it is fetched."""
    q_spec = pl.BlockSpec((1, group, bq, D), lambda b, h, t, qt, kt, ft: (b, h, qt[t], 0))
    kv_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, t, qt, kt, ft: (b, h, kt[t], 0))
    mask_spec = pl.BlockSpec((1, 1, bq, bk), lambda b, h, t, qt, kt, ft: (b, kt[t] // 32, qt[t], 0))
    row_spec = pl.BlockSpec((1, group, bq, _ROW_LANES), lambda b, h, t, qt, kt, ft: (b, h, qt[t], 0))
    return q_spec, kv_spec, mask_spec, row_spec


def _attn_fwd(q, k, v, mask, sm_scale, blocks, interpret):
    """q [B, H, S, D], k and v [B, KV, S, D] → (o [B, H, S, D], lse
    [B, H, S])."""
    B, H, S, D = q.shape
    KV = k.shape[1]
    group = H // KV
    bq, bk = blocks.q, blocks.k
    walk = _steps(S, blocks)
    q_spec, kv_spec, mask_spec, _ = _attn_specs(group, bq, bk, D)
    o, lse = pl.pallas_call(
        functools.partial(_attn_fwd_kernel, sm_scale=sm_scale, group=group, block_q=bq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk.tables),
            grid=(B, KV, walk.steps),
            in_specs=[q_spec, kv_spec, kv_spec, mask_spec],
            out_specs=[
                q_spec,
                # a row block's statistics lie along the lanes of [B, H, 1, S]
                pl.BlockSpec((1, group, 1, bq), lambda b, h, t, qt, kt, ft: (b, h, 0, qt[t])),
            ],
            scratch_shapes=[
                pltpu.VMEM((1, group * bq), jnp.float32),
                pltpu.VMEM((1, group * bq), jnp.float32),
                pltpu.VMEM((D, group * bq), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32),
        ],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="dsa_attn_fwd",
    )(*walk.tables, q, k, v, mask)
    return o, lse.reshape(B, H, S)


def _attn_bwd(q, k, v, mask, o, lse, do, sm_scale, blocks, interpret):
    """(dq, dk, dv) from ONE launch over the forward's walk (the module
    docstring).  lse [B, H, S, 8]."""
    B, H, S, D = q.shape
    KV = k.shape[1]
    group = H // KV
    bq, bk = blocks.q, blocks.k
    nk = S // bk
    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True),
        (B, H, S, _ROW_LANES),
    )
    walk = _steps(S, blocks)
    q_spec, kv_spec, mask_spec, row_spec = _attn_specs(group, bq, bk, D)
    # every row block adds to every earlier key block: a KV head's whole dk and
    # dv stay in fast memory for its steps, as dsa_probs' dk does for a batch row
    keys_spec = pl.BlockSpec((1, 1, nk, bk, D), lambda b, h, t, qt, kt, ft: (b, h, 0, 0, 0))
    keys_shape = jax.ShapeDtypeStruct((B, KV, nk, bk, D), jnp.float32)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_attn_bwd_kernel, sm_scale=sm_scale, group=group, block_q=bq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk.tables),
            grid=(B, KV, walk.steps),
            in_specs=[q_spec, kv_spec, kv_spec, mask_spec, row_spec, q_spec, row_spec],
            out_specs=[q_spec, keys_spec, keys_spec],
            scratch_shapes=[pltpu.VMEM((group * bq, D), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), keys_shape, keys_shape],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="dsa_attn_dkv",  # the name the benchmark's readers count as the backward (it makes dq too)
    )(*walk.tables, q, k, v, mask, lse, do, delta)
    return dq, dk.reshape(k.shape).astype(k.dtype), dv.reshape(v.shape).astype(v.dtype)


# ---------------------------------------------------------------------------
# the index's loss
# ---------------------------------------------------------------------------


def _probs_kernel(*refs, sm_scale, kv_heads, group, heads, block_q, block_k):
    (
        *tables, q_ref, k_ref, lse_ref, mask_ref, qi_ref, w_ref, ki_ref, stat_ref, kl_ref, dq_ref, dw_ref, dk_ref,
        kl_scr, dq_scr, dw_scr,
    ) = refs
    _, ki, first, last = _where(tables, axis=1)
    D = q_ref.shape[-1]
    rows = group * block_q

    @pl.when(first)
    def _init():
        kl_scr[...] = jnp.zeros_like(kl_scr)
        dq_scr[...] = jnp.zeros_like(dq_scr)
        dw_scr[...] = jnp.zeros_like(dw_scr)

    @pl.when(pl.program_id(1) == 0)
    def _init_keys():
        dk_ref[...] = jnp.zeros_like(dk_ref)

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

    @pl.when(last)
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
    nk = S // bk
    walk = _steps(S, blocks)  # the forward's
    rows = lambda *lead: pl.BlockSpec(  # noqa: E731
        (1, *lead, bq, _ROW_LANES), lambda b, t, qt, kt, ft: (b,) + (0,) * len(lead) + (qt[t], 0)
    )
    index_q_spec = pl.BlockSpec((1, J, bq, DI), lambda b, t, qt, kt, ft: (b, 0, qt[t], 0))
    kl, dq, dw, dk = pl.pallas_call(
        functools.partial(
            _probs_kernel, sm_scale=sm_scale, kv_heads=KV, group=H // KV, heads=J, block_q=bq, block_k=bk
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk.tables),
            grid=(B, walk.steps),
            in_specs=[
                pl.BlockSpec((1, H, bq, D), lambda b, t, qt, kt, ft: (b, 0, qt[t], 0)),
                pl.BlockSpec((1, KV, bk, D), lambda b, t, qt, kt, ft: (b, 0, kt[t], 0)),
                rows(H),
                pl.BlockSpec((1, 1, bq, bk), lambda b, t, qt, kt, ft: (b, kt[t] // 32, qt[t], 0)),
                index_q_spec,
                rows(J),
                pl.BlockSpec((1, bk, DI), lambda b, t, qt, kt, ft: (b, kt[t], 0)),
                rows(),
            ],
            out_specs=[
                rows(),
                index_q_spec,
                rows(J),
                # every query block adds to every earlier key block: the whole
                # array stays in fast memory for a batch row
                pl.BlockSpec((1, nk, bk, DI), lambda b, t, qt, kt, ft: (b, 0, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, _LANES), jnp.float32),
                pltpu.VMEM((J, bq, DI), jnp.float32),
                pltpu.VMEM((J, bq, _LANES), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, S, _ROW_LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, J, S, DI), jnp.float32),
            jax.ShapeDtypeStruct((B, J, S, _ROW_LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, nk, bk, DI), jnp.float32),
        ],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
        name="dsa_probs",
    )(*walk.tables, q, k, lse, mask, q_index, w, k_index, lse_index)
    return kl[..., 0], dq, dw[..., 0], dk.reshape(B, S, DI)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def _heads_major(q, k, v, q_index, w):
    t = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
    return t(q), t(k), t(v), t(q_index), _row_lanes(w.astype(jnp.float32).transpose(0, 2, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _attend(q, k, v, q_index, k_index, w, mask, lse_index, sm_scale, blocks, interpret):
    return _attend_fwd(q, k, v, q_index, k_index, w, mask, lse_index, sm_scale, blocks, interpret)[0]


def _attend_fwd(q, k, v, q_index, k_index, w, mask, lse_index, sm_scale, blocks, interpret):
    qh, kh, vh, qih, wh = _heads_major(q, k, v, q_index, w)
    o, lse = _attn_fwd(qh, kh, vh, mask, sm_scale, blocks, interpret)
    kl, d_qi, d_w, d_ki = _index_loss(
        qh, kh, _row_lanes(lse), mask, qih, wh, k_index, _row_lanes(lse_index), sm_scale, blocks, interpret
    )
    # the named values ARE the residuals: a policy that keeps the names leaves
    # the backward pass no use for either kernel above
    o, lse, d_qi, d_w, d_ki = (
        checkpoint_name(a, n) for a, n in zip((o, lse, d_qi, d_w, d_ki), KEPT_NAMES)
    )
    like = tuple(jnp.zeros((0,), a.dtype) for a in (q_index, k_index, w))  # the cotangents' dtypes
    kept = (qh, kh, vh, mask, o, lse, d_qi, d_w, d_ki, like)
    return (o.transpose(0, 2, 1, 3), jnp.sum(kl)), kept


def _attend_bwd(sm_scale, blocks, interpret, kept, cotangents):
    qh, kh, vh, mask, o, lse, d_qi, d_w, d_ki, like = kept
    qi_dtype, ki_dtype, w_dtype = (a.dtype for a in like)
    do, g = cotangents
    dq, dk, dv = _attn_bwd(
        qh, kh, vh, mask, o, _row_lanes(lse), do.transpose(0, 2, 1, 3).astype(o.dtype), sm_scale, blocks,
        interpret,
    )
    t = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
    g = g.astype(jnp.float32)
    return (
        t(dq), t(dk), t(dv),
        (g * t(d_qi)).astype(qi_dtype), (g * d_ki).astype(ki_dtype),
        (g * d_w.transpose(0, 2, 1)).astype(w_dtype),
        None, jnp.zeros_like(lse[:, 0]),
    )


_attend.defvjp(_attend_fwd, _attend_bwd)


def indexed_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, q_index: jax.Array, k_index: jax.Array, w: jax.Array,
    mask: jax.Array, lse_index: jax.Array, *, blocks: Blocks = Blocks(), interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Attention over the key sets of :func:`select_keys` and the index's
    loss.  q [B, S, H, D], k and v [B, S, KV, D], ``q_index`` [B, S, J, DI],
    ``k_index`` [B, S, DI], ``w`` [B, S, J] → (o [B, S, H, D], ``L_I``
    summed over the rows, float32).

    ``o``'s gradient reaches q, k and v alone; ``L_I``'s reaches the
    index's three operands alone (the attention's probabilities are its
    target and carry none)."""
    blocks = blocks.fit(q.shape[1])
    sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    return _attend(
        q, k, v, q_index, k_index, w, mask, jax.lax.stop_gradient(lse_index), sm_scale, blocks, interpret
    )


# ---------------------------------------------------------------------------
# the same in plain jax.numpy (dense [S, S] arrays)
# ---------------------------------------------------------------------------


def picked_plain(q_index: jax.Array, k_index: jax.Array, w: jax.Array, topk: int) -> Tuple[jax.Array, jax.Array]:
    """(the index's scores [B, S, S] float32, the key sets as booleans)."""
    S = q_index.shape[1]
    dots = jnp.einsum("btjd,bsd->btjs", q_index, k_index, preferred_element_type=jnp.float32)
    scores = jnp.einsum("btj,btjs->bts", w.astype(jnp.float32), jax.nn.relu(dots))
    causal = jnp.tril(jnp.ones((S, S), bool))
    k = min(topk, S)
    best, at = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), k)  # ties: the lower position first
    rows = jnp.arange(S)[None, :, None]
    picked = jnp.zeros(scores.shape, bool).at[jnp.arange(scores.shape[0])[:, None, None], rows, at].max(
        best > -jnp.inf
    )
    return scores, picked


def indexed_attention_plain(
    q: jax.Array, k: jax.Array, v: jax.Array, q_index: jax.Array, k_index: jax.Array, w: jax.Array,
    *, topk: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(o, ``L_I`` summed over the rows, keys a row [B, S]) as
    :func:`select_keys` and :func:`indexed_attention` give them."""
    B, S, H, D = q.shape
    group = H // k.shape[2]
    # the picking itself carries no gradient (positions and booleans); the
    # scores do, to the index's loss below
    scores, picked = picked_plain(q_index, k_index, w, topk)
    logits = jnp.einsum(
        "bthd,bshd->bhts", q, jnp.repeat(k, group, axis=2), preferred_element_type=jnp.float32
    ) / np.sqrt(D)
    probs = jax.nn.softmax(jnp.where(picked[:, None], logits, _NEG_INF), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", probs.astype(v.dtype), jnp.repeat(v, group, axis=2))
    log_q = jax.nn.log_softmax(jnp.where(picked, scores, _NEG_INF), axis=-1)
    target = jax.lax.stop_gradient(jnp.mean(probs, axis=1))
    kl = jnp.where(picked, target * (jnp.log(jnp.maximum(target, 1e-37)) - log_q), 0.0)
    return o, jnp.sum(kl), jnp.sum(picked, axis=-1).astype(jnp.float32)
