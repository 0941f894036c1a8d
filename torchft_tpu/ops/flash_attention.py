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

**The forward's tile lies keys-major** (PR 64).  ``s^T = k q^T`` is
``[bk, bq]``, the keys down the sublanes and the queries along the lanes, so
the two reductions over the keys an online-softmax step makes (the running
maximum, the denominator) are elementwise maxima and sums ACROSS the tile's
vector registers and one fold of eight sublanes a column group, and no
reduction along the lanes stands in the loop; ``m`` and ``l`` are rows of
``[1, bq]`` float32 (four registers, where ``[bq, 128]`` broadcasts were
written whole every step) and reach the tile by a sublane broadcast.  The
accumulator lies the same way, ``[Dv, bq]`` (``v^T p^T``, the transposed-left
product ``dkv`` makes), and is turned once a ROW BLOCK on its last visit;
``lse`` leaves as it lies, a row: the forward's second output is ``[B, H, S]``
float32 (block ``(1, 1, 1, bq)`` of ``[B, H, 1, S]``).  Same scores, same
``exp``, same products and operand types as the rows-major body it replaced:
the maximum is exact in any order, ``l`` is the same addends summed in another
order (``scripts/flash_walk_probe.py`` prints the largest difference).  The
backward kernels keep their rows-major tiles and read ``lse`` and ``delta`` as
``[B, H, S, 8]`` columns (``_spread``).

Backward is the standard two-kernel flash scheme over the saved
logsumexp: ``dq`` accumulates over k-blocks; ``dk``/``dv`` accumulate over
(q-head-in-group × q-block) so each kv-head's gradient sums its whole GQA
group without materializing per-q-head copies.

**The walk.**  From static shapes alone (the sequence, the blocks, the
window) every (row block, key block) pair of a causal launch is classified
once (``_live_blocks``) as live (some pair of positions alive) or dead (a key
block wholly above the diagonal, or wholly behind the window), and a dead
block is NOT IN THE GRID.  The grid's last axis is the flattened list of the
live pairs (``_walk``); which row block and key block a step works on comes
from small int32 tables handed in by scalar prefetch
(``pltpu.PrefetchScalarGridSpec``: the index maps read them), and a step's
flags say whether it is the first or the last visit of the block whose output
it accumulates (init, write).  Forward and ``dq`` walk, for a row block, the
key blocks with a live pair in ascending order; ``dkv`` walks, for a key block
and each member of its GQA group in turn, the row blocks that see it: the
order of accumulation is the rectangle's, so every output is bit for bit what
a walk of the whole rectangle gives (``scripts/flash_walk_probe.py`` holds the
programs to their predecessors' on the chip).  No dead block is fetched and
none costs a grid step.  Every live block runs ONE body, mask and all: a full
layer is a window of the whole sequence.  At 16,384 positions and blocks of
512 a full layer's launch walks 528 pairs a head of 1,024; under a window of
2,048 it walks 150.  ``dkv``'s tables hold a step for every member of the
group (``_TABLE_BYTES`` bounds them, with an error that says so).

Without causality (ring attention's off-diagonal steps through
``flash_attention_lse``) no block is dead and nothing needs a mask: the grid
is the rectangle ``(rows, keys)`` with affine index maps and no table
(``_where`` tells the two apart inside a kernel).

A ``window`` is a sliding window: query ``i`` sees keys ``j`` with ``i - window
< j <= i``, its own position counted.  A windowed layer's programs are named
``flash_win_fwd``, ``flash_win_dq`` and ``flash_win_dkv``, so that a trace
tells its kernels from a full layer's (``flash_fwd``, ``flash_dq``,
``flash_dkv``); a window that covers the sequence IS causal attention and
takes the full layers' programs.

**Two key sources under one softmax** (``eva_attention``; EvaByte's chunked
attention, arXiv:2302.04542).  The key axis holds a sequence's chunk SUMMARIES
first (a pooled key and value a chunk of ``chunk`` positions) and its TOKENS
after them; query ``i`` of window ``w(i) = i // window`` sees the summaries of
every EARLIER window and the tokens of its own window up to itself, and ONE
running maximum and ONE denominator run over both.  That is a second rule of
liveness (``Pooled``) beside the sliding window, taken by the same
``_live_blocks``, ``_walk``, ``_masked`` and the same three bodies: a row block
walks its live summary blocks and then its live token blocks, ``dkv`` walks the
key blocks of both kinds, and the gradient of the key axis is cut back into
``dk~, dv~`` and ``dk, dv`` where the two were joined.  The blocks divide a
window, so a block lies in ONE window and is of ONE kind; a token block's mask
is the diagonal's and a summary block's a bound a row block.  The programs are
named ``eva_fwd``, ``eva_dq`` and ``eva_dkv``.  Key blocks are 512 for both
kinds: a window's 128 summaries alone would make a block that is always whole
or dead, but a step of 512 x 128 holds 0.17 us of product under the 0.4-0.5 us
a grid step costs (PERF.md section 6, PR 51), and the mask costs nothing.

What the chip said (v5e, PR 50, PERF.md section 6): the dead steps were
15-28 % of a full layer's launches at 32 x 32 blocks (each fetched its
blocks).  The mask costs NOTHING that shows: a second, maskless body for the
blocks wholly under the diagonal ran as fast as this one (the vector unit's
spare slots take the mask), so there is none.  A step's table reads cost
0.06-0.09 us, which a windowed layer, with few dead steps to lose, pays for
with 2-4 % of its kernels' time.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# the backward kernels' rowwise stats (lse, delta) carry a trailing 8-lane dim
# and are read as [bq, 1] columns beside their rows-major tiles; the forward
# writes its own as a row, block (1, 1, 1, bq) of [B, H, 1, S]
_ROW_LANES = 8


# ---------------------------------------------------------------------------
# the walk: which blocks a causal launch visits
# ---------------------------------------------------------------------------

_FIRST, _LAST = 1, 2  # a grid step's flags
# the most a launch's tables may hold: they are SMEM operands, and ``dkv``'s
# grow with the live pairs times the GQA group.  A v5e's SMEM is 1 MiB by the
# compiler's count and a program keeps some 9 KB of its own there (a group of
# 16 compiles at 44,032 positions and blocks of 512, 958 KB, and not at
# 49,152); the cells' largest is 135 KB and runs at full pace (PERF.md
# section 6, PR 50)
_TABLE_BYTES = 960 << 10


class Pooled(NamedTuple):
    """The rule of liveness of two key sources (given where a ``window`` is):
    the key axis holds ``summaries`` pooled keys first, ``per_window`` of them
    a window of ``window`` positions, and the tokens after them.  Query ``i``
    sees summary ``c`` with ``c // per_window < i // window`` and token ``j``
    with ``j // window == i // window`` and ``j <= i``.  Both kinds of block
    divide ``window`` and ``summaries``, so a row block lies in one window and
    a key block is of one kind and, if of tokens, in one window."""

    window: int
    per_window: int
    summaries: int


def _live_blocks(nq, nk, block_q, block_k, window) -> np.ndarray:
    """``[nq, nk]``: whether a (row block, key block) pair holds a live pair
    of positions.  Query ``i`` sees key ``j`` with ``i - window < j <= i``; a
    full layer is a window of the whole sequence.  Under ``Pooled`` a summary
    block is live to the row blocks of a later window than its first
    summary's, a token block to those of its own window from the diagonal on."""
    first_row = np.arange(nq)[:, None] * block_q
    last_row = first_row + block_q - 1
    first_key = np.arange(nk)[None, :] * block_k
    last_key = first_key + block_k - 1
    if isinstance(window, Pooled):
        rows_window = first_row // window.window
        first_token = first_key - window.summaries
        return np.where(
            first_token < 0,
            first_key < window.per_window * rows_window,
            (first_token // window.window == rows_window) & (first_token <= last_row),
        )
    reach = nq * block_q if window is None else window
    return (last_row >= first_key) & (last_key > first_row - reach)


class Walk(NamedTuple):
    """One launch's grid steps in their order, a table entry a step: the row
    block and the key block it works on, its flags (the first and the last
    visit of the block whose output it accumulates) and, for ``dkv``, the
    member of the GQA group whose rows it reads.  A dead block has no step."""

    q: np.ndarray
    k: np.ndarray
    flags: np.ndarray
    member: Optional[np.ndarray] = None

    @property
    def steps(self) -> int:
        return len(self.flags)

    @property
    def tables(self) -> Tuple[jax.Array, ...]:
        """The scalar-prefetch operands, in the kernels' order."""
        held = (self.q, self.k, self.flags) + (() if self.member is None else (self.member,))
        if 4 * self.steps * len(held) > _TABLE_BYTES:
            raise ValueError(
                f"a flash launch of {self.steps} grid steps a head needs {len(held)} int32 tables of "
                f"that length in SMEM, over {_TABLE_BYTES} bytes: take larger blocks"
                + ("" if self.member is None else " (dkv holds a step for every member of the GQA group)")
            )
        return tuple(jnp.asarray(a, jnp.int32) for a in held)


def _walk(live: np.ndarray, groups: Optional[int] = None) -> Walk:
    """The steps over ``live`` (``_live_blocks``).  Forward and ``dq``
    (``groups`` None): for a row block, the key blocks with a live pair,
    ascending.  ``dkv``: for a key block and each member of its GQA group in
    turn, the row blocks that see it, ascending, so that one accumulator sums
    the whole group."""
    by_key = groups is not None
    members = groups or 1
    outer, inner, flags, member = [], [], [], []
    for o, row in enumerate(live.T if by_key else live):
        (seen,) = np.nonzero(row)
        assert seen.size, f"block {o} has no live pair to write its output from"
        run = np.tile(seen, members)
        f = np.zeros_like(run)
        f[0] |= _FIRST
        f[-1] |= _LAST
        outer.append(np.full_like(run, o))
        inner.append(run)
        flags.append(f)
        member.append(np.repeat(np.arange(members), seen.size))
    outer, inner, flags, member = (np.concatenate(a) for a in (outer, inner, flags, member))
    if by_key:
        return Walk(q=inner, k=outer, flags=flags, member=member)
    return Walk(q=outer, k=inner, flags=flags)


def _name(window, kernel: str) -> str:
    """What a launch is called in a trace: by its rule of liveness."""
    if isinstance(window, Pooled):
        return "eva_" + kernel
    return ("flash_" if window is None else "flash_win_") + kernel


def _row_launch(nq, nk, block_q, block_k, groups, window, causal):
    """Forward's and ``dq``'s launch over ``(b, h)``: (the grid's further
    axes, the tables, the index map of a row block's operands, that of the key
    blocks of its KV head).  Under causality the walk; without it no block is
    dead and the grid is the rectangle."""
    if not causal:
        return (
            (nq, nk), (),
            lambda b, h, i, j: (b, h, i, 0),
            lambda b, h, i, j: (b, h // groups, j, 0),
        )
    walk = _walk(_live_blocks(nq, nk, block_q, block_k, window))
    return (
        (walk.steps,), walk.tables,
        lambda b, h, t, qt, kt, ft: (b, h, qt[t], 0),
        lambda b, h, t, qt, kt, ft: (b, h // groups, kt[t], 0),
    )


def _key_launch(nq, nk, block_q, block_k, groups, window, causal):
    """``dkv``'s launch over ``(b, kv)``, as ``_row_launch``: a key block's
    steps run over (group member, row block that sees it)."""
    if not causal:
        return (
            (nk, groups * nq), (),
            lambda b, kv, j, i: (b, kv * groups + i // nq, i % nq, 0),
            lambda b, kv, j, i: (b, kv, j, 0),
        )
    live = _live_blocks(nq, nk, block_q, block_k, window)
    if isinstance(window, Pooled):
        # a key block no row sees (the LAST window's summaries where they fill
        # a block, the padding) has its zeros written by one visit, all masked
        live[-1] |= ~live.any(axis=0)
    walk = _walk(live, groups)
    return (
        (walk.steps,), walk.tables,
        lambda b, kv, t, qt, kt, ft, mt: (b, kv * groups + mt[t], qt[t], 0),
        lambda b, kv, t, qt, kt, ft, mt: (b, kv, kt[t], 0),
    )


def _where(tables, axis=2):
    """Where a kernel's grid step is: (row block, key block, whether it is the
    first visit of the block whose output it accumulates, whether the last).
    With the walk's ``tables`` all four are read from them, at the step of the
    grid's ``axis``; on the rectangle (no causality) the accumulation runs
    over the innermost axis and nothing asks for the blocks (there is no
    mask)."""
    if not tables:
        inner = pl.program_id(3)
        return None, None, inner == 0, inner == pl.num_programs(3) - 1
    step = pl.program_id(axis)
    flags = tables[2][step]
    return tables[0][step], tables[1][step], (flags & _FIRST) != 0, (flags & _LAST) != 0


def _masked(s, qi, ki, block_q, block_k, window, keys_major=False):
    """Scores [bq, bk] of row block ``qi`` against key block ``ki`` with the
    dead pairs (a later key; with a window, one ``window`` or more back) at
    ``_NEG_INF``.  Under ``Pooled`` a key block is of one kind: of a summary
    block the row block's window sees the summaries before its own, of a token
    block (its own window's, by liveness) a row sees up to itself.
    ``keys_major``: the tile is the forward's, [bk, bq] with the keys down the
    sublanes, and the same compares run over it."""
    shape, of_rows, of_cols = ((block_k, block_q), 1, 0) if keys_major else ((block_q, block_k), 0, 1)
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, of_rows)
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, of_cols)
    if isinstance(window, Pooled):
        seen = (qi * block_q) // window.window * window.per_window
        last = jnp.where(ki * block_k < window.summaries, seen - 1, rows + window.summaries)
        return jnp.where(cols <= last, s, _NEG_INF)
    keep = rows >= cols
    if window is not None:
        keep = keep & (cols > rows - window)
    return jnp.where(keep, s, _NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, sm_scale: float, causal: bool, block_q: int, block_k: int, window: Optional[int]):
    """One live (row block, key block) pair of the online softmax, the tile
    keys-major (the module docstring): every reduction over the keys runs
    down the sublanes and across vector registers."""
    (
        *tables,  # SMEM [steps] int32: the walk (none without causality)
        q_ref,  # [1, 1, bq, D]
        k_ref,  # [1, 1, bk, D]
        v_ref,  # [1, 1, bk, Dv]
        o_ref,  # [1, 1, bq, Dv]
        lse_ref,  # [1, 1, 1, bq]
        m_scr,  # VMEM [1, bq] f32: running row max
        l_scr,  # VMEM [1, bq] f32: running denominator
        acc_scr,  # VMEM [Dv, bq] f32: running (unnormalized) output, transposed
    ) = refs
    qi, ki, first, last = _where(tables)

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]  # [bq, D]
    k = k_ref[0, 0]  # [bk, D]
    v = v_ref[0, 0]

    s = (
        jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * sm_scale
    )  # [bk, bq] f32
    if causal:
        s = _masked(s, qi, ki, block_q, block_k, window, keys_major=True)

    m_prev = m_scr[...]  # [1, bq]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    p = jnp.exp(s - m_new)  # [bk, bq]
    correction = jnp.exp(m_prev - m_new)  # [1, bq]
    l_scr[...] = l_scr[...] * correction + jnp.sum(p, axis=0, keepdims=True)
    acc_scr[...] = acc_scr[...] * correction + jax.lax.dot_general(
        v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # v^T @ p: [Dv, bq]
    m_scr[...] = m_new

    @pl.when(last)
    def _finalize():
        l = l_scr[...]
        denom = jnp.where(l > 0.0, l, 1.0)  # fully-masked rows guard
        o_ref[0, 0] = (acc_scr[...] / denom).T.astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(denom)


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
    lse [B,H,Sq] f32).  Rectangular (Sq != Sk) is allowed when not causal; v's
    head size may differ from q's and k's (latent attention: 192 and 128)."""
    B, H, S, D = q.shape
    Dv = v.shape[3]
    KV = k.shape[1]
    steps, tables, q_map, kv_map = _row_launch(
        S // block_q, k.shape[2] // block_k, block_q, block_k, H // KV, window, causal
    )
    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        window=window,
    )

    def row_map(*at):  # a row block's statistics lie along the lanes of [B, H, 1, S]
        b, h, i, _ = q_map(*at)
        return b, h, 0, i

    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(B, H, *steps),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, D), q_map),
                pl.BlockSpec((1, 1, block_k, D), kv_map),
                pl.BlockSpec((1, 1, block_k, Dv), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, Dv), q_map),
                pl.BlockSpec((1, 1, 1, block_q), row_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((1, block_q), jnp.float32),
                pltpu.VMEM((1, block_q), jnp.float32),
                pltpu.VMEM((Dv, block_q), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32),
        ],
        interpret=interpret,
        name=_name(window, "fwd"),
    )(*tables, q, k, v)
    return o, lse.reshape(B, H, S)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _recompute_p_ds(
    q, k, lse, do, v, delta, sm_scale, causal, qi, ki, block_q, block_k, window,
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


def _dq_kernel(*refs, sm_scale, causal, block_q, block_k, window):
    *tables, q_ref, k_ref, v_ref, lse_ref, do_ref, delta_ref, dq_ref, dq_scr = refs
    qi, ki, first, last = _where(tables)  # the forward's walk

    @pl.when(first)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    _, ds = _recompute_p_ds(
        q_ref[0, 0], k_ref[0, 0], lse_ref[0, 0][:, :1], do_ref[0, 0],
        v_ref[0, 0], delta_ref[0, 0][:, :1], sm_scale, causal, qi, ki,
        block_q, block_k, window,
    )
    dq_scr[...] += jax.lax.dot_general(
        ds.astype(k_ref.dtype), k_ref[0, 0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(last)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(*refs, sm_scale, causal, block_q, block_k, window):
    (
        *tables, q_ref, k_ref, v_ref, lse_ref, do_ref, delta_ref, dk_ref, dv_ref,
        dk_scr, dv_scr,
    ) = refs
    # a key block's steps run over (group member, row block that sees it):
    # the accumulators sum the whole GQA group
    qi, ki, first, last = _where(tables)

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

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

    @pl.when(last)
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
    blocks = (S // block_q, k.shape[2] // block_k, block_q, block_k, H // KV, window, causal)
    static = dict(sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k, window=window)

    delta_rows = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32),
        axis=-1,
        keepdims=True,
    )
    if dlse is not None:
        delta_rows = delta_rows - dlse[..., None].astype(jnp.float32)
    delta = jnp.broadcast_to(delta_rows, (B, H, S, _ROW_LANES))

    steps, tables, q_map, kv_map = _row_launch(*blocks)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(B, H, *steps),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, D), q_map),
                pl.BlockSpec((1, 1, block_k, D), kv_map),
                pl.BlockSpec((1, 1, block_k, Dv), kv_map),
                pl.BlockSpec((1, 1, block_q, _ROW_LANES), q_map),
                pl.BlockSpec((1, 1, block_q, Dv), q_map),
                pl.BlockSpec((1, 1, block_q, _ROW_LANES), q_map),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, D), q_map),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name=_name(window, "dq"),
    )(*tables, q, k, v, lse, do, delta)

    steps, tables, g_q_map, g_kv_map = _key_launch(*blocks)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(B, KV, *steps),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, D), g_q_map),
                pl.BlockSpec((1, 1, block_k, D), g_kv_map),
                pl.BlockSpec((1, 1, block_k, Dv), g_kv_map),
                pl.BlockSpec((1, 1, block_q, _ROW_LANES), g_q_map),
                pl.BlockSpec((1, 1, block_q, Dv), g_q_map),
                pl.BlockSpec((1, 1, block_q, _ROW_LANES), g_q_map),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_k, D), g_kv_map),
                pl.BlockSpec((1, 1, block_k, Dv), g_kv_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, Dv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
        name=_name(window, "dkv"),
    )(*tables, q, k, v, lse, do, delta)
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
# as ``nothing_saveable``, is served as before): ``o`` and ONE float32 a row,
# ``[B, H, S]`` (1 MB at 16 heads and 16,384 positions), which is what the
# forward kernel writes.  The BACKWARD kernels read ``[B, H, S, 8]``, padded
# to 128 lanes in HBM (134 MB a layer there): the backward rules spread it
KEPT_NAMES = ("flash_o", "flash_lse")


def _spread(lse):
    """A row statistic ``[B, H, S]`` as the backward kernels read it."""
    return jnp.broadcast_to(lse[..., None], (*lse.shape, _ROW_LANES))


def _flash_hm_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret, window):
    o, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret, window)
    o, lse = (checkpoint_name(a, n) for a, n in zip((o, lse), KEPT_NAMES))
    return o, (q, k, v, o, lse)


def _flash_hm_bwd(sm_scale, causal, block_q, block_k, interpret, window, res, do):
    *rest, lse = res
    return _bwd(sm_scale, causal, block_q, block_k, interpret, (*rest, _spread(lse)), do, window=window)


_flash_hm.defvjp(_flash_hm_fwd, _flash_hm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_hm_lse(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    """Heads-major flash returning (o, lse [B,H,S] f32) — for callers that
    merge partial attention results across blocks (ring attention)."""
    return _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)


def _flash_hm_lse_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    o, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return (o, lse), (q, k, v, o, lse)


def _flash_hm_lse_bwd(sm_scale, causal, block_q, block_k, interpret, res, cts):
    do, dlse = cts
    *rest, lse = res
    return _bwd(
        sm_scale, causal, block_q, block_k, interpret, (*rest, _spread(lse)), do, dlse=dlse
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


def eva_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    k_pooled: jax.Array,
    v_pooled: jax.Array,
    *,
    window: int,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Attention over two key sources under ONE softmax (the module
    docstring, ``Pooled``): q [B, S, H, D]; the tokens' k, v [B, S, KV, D];
    the chunk summaries ``k_pooled``, ``v_pooled`` [B, S // chunk, KV, D], a
    pooled key and value a chunk, ``window // chunk`` of them a window.
    Query ``i`` sees the tokens ``j <= i`` of its own window ``i // window``
    and the summaries of every earlier window.  Returns [B, S, H, D];
    differentiable in all five (``eva_fwd``, ``eva_dq``, ``eva_dkv``: the key
    axis' gradient is cut back into the summaries' and the tokens').

    ``window`` divides the sequence and the blocks divide ``window``.  The
    summaries are padded with dead keys to a whole number of key blocks.  A
    window that covers the sequence sees no summary and IS causal attention
    (``flash_fwd``, ``flash_dq``, ``flash_dkv``): the summaries then take no
    gradient."""
    B, S, H, D = q.shape
    chunks = k_pooled.shape[1]
    if isinstance(window, bool) or not isinstance(window, (int, np.integer)) or window < 1:
        raise ValueError(f"a window is a static whole number of positions >= 1, got {window!r}")
    if window >= S:
        return flash_attention(
            q, k, v, causal=True, sm_scale=sm_scale, block_q=block_q, block_k=block_k, interpret=interpret
        )
    block_q, block_k = min(block_q, window), min(block_k, window)
    if S % window or window % block_q or window % block_k or chunks % (S // window):
        raise ValueError(
            f"S={S} holds whole windows of {window}, a window whole blocks ({block_q}, {block_k}) and "
            f"the {chunks} summaries as many a window"
        )
    if k.shape != v.shape or k_pooled.shape != v_pooled.shape or k_pooled.shape[2:] != k.shape[2:] or H % k.shape[2]:
        raise ValueError(
            f"tokens and summaries share KV heads and a head size, got q={q.shape} k={k.shape} v={v.shape} "
            f"k_pooled={k_pooled.shape} v_pooled={v_pooled.shape}"
        )
    padding = -chunks % block_k
    rule = Pooled(int(window), chunks // (S // window), chunks + padding)

    def key_axis(pooled, tokens):  # heads-major: the summaries, their padding, the tokens
        pooled = jnp.pad(pooled, ((0, 0), (0, padding), (0, 0), (0, 0)))
        return jnp.concatenate([pooled, tokens], axis=1).transpose(0, 2, 1, 3)

    out = _flash_hm(
        q.transpose(0, 2, 1, 3), key_axis(k_pooled, k), key_axis(v_pooled, v),
        float(1.0 / np.sqrt(D) if sm_scale is None else sm_scale), True, block_q, block_k, interpret, rule,
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
