"""A state-space scan with a scalar decay a head (Mamba-2's SSD,
arXiv:2405.21060) in its chunked form, forward and backward (Pallas, TPU).

One head ``j`` of width ``P`` keeps a state ``S`` of ``[P, N]`` and, token by
token, with ``B_t`` and ``C_t`` in ``R^N`` shared by the heads of its group,

    S_t = a_t S_{t-1} + dt_t x_t B_t^T,      a_t = exp(-dt_t exp(A_log_j))
    y_t = S_t C_t + D_j x_t

That recurrence is the plain reference's (``ftbench/architectures/
ssm_hybrid_moe_reference.py``) and the tests'; the program never runs it.
Here a sequence is cut into chunks of ``C`` tokens.  With ``g`` the running
sum of ``log a`` inside a chunk (so ``g <= 0`` and falling), ``S0`` the state
the chunk starts from and ``xd = dt * x``:

    L[t, s] = exp(g[t] - g[s])  for t >= s, else 0
    Y  = (L o (C B^T)) xd + exp(g) (C S0^T)
    S1 = exp(g_end) S0 + (exp(g_end - g) xd)^T B

Every exponent is at most 0, whatever the decay: nothing is factored into
``exp(g[t]) exp(-g[s])``, nothing is rounded to 0 or 1 and no chunk is
skipped.  ``C B^T`` is computed once for a group's heads.  The state, the
decays and every accumulation are float32; the other products take their
operands in the inputs' type (bfloat16 on the chip, float32 in the CPU
tests).

Kernels: ``ssd_fwd`` walks a group's chunks in order with its heads' states
in VMEM and keeps each chunk's starting state for the backward; ``ssd_bwd``
walks them in reverse with the states' cotangent in VMEM and applies the
hand-written transpose of the algebra above from the kept states.  A grid
step takes a BLOCK of a group's heads (``head_block``: ``HEAD_BLOCK`` of them,
the body unrolls a loop over them); a wider group's blocks are
the grid's innermost axis, under one chunk of ``B`` and ``C`` that stays where
it is: ``C B^T`` is made by the chunk's first block and kept in VMEM, and what
the group's heads SHARE of the cotangents (``dB``, ``dC`` and ``d(C B^T)``) is
summed over the blocks in VMEM, in float32, and written by the last.  A group
of one block runs the program it ran before there were blocks, to the letter.
What is elementwise in the tokens stays outside, in XLA, differentiated by jax:
``dt * x``, the running sum of ``log a`` inside a chunk (so ``dt``, ``A_log``
and ``D`` get their gradients there) and ``D x``.  The kernels read the
running sum twice, once with the tokens along the lanes and once along the
sublanes (``g[t] - g[s]`` needs both and a kernel transposes nothing), and
hand its cotangent back in the same two parts.

The forward rule's residuals carry a ``checkpoint_name`` (``KEPT_NAMES``: the
output and the chunk-start states): a caller that rematerialises its layers
lists the names in its policy and ``ssd_fwd`` runs once a step.

``ssd_chunked_plain`` is the same chunk algebra as plain ``jax.numpy`` under
a ``lax.scan``, differentiated by jax: what a model takes off the TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.ops.kda import _NN, _NT, _TN, _dot

_F32 = jnp.float32
# the forward rule's residuals that a rematerialising caller's policy may keep
KEPT_NAMES = ("ssd_y", "ssd_states")


def _causal(C):
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return row >= col


def _head_parts(xd, CB, mask, g_row, g_col):
    """What both directions need of one head's chunk: ``g_row`` ``[1, C]``
    and ``g_col`` ``[C, 1]`` are the running sum of the log decay, ``CB``
    ``[C, C]`` float32 the group's ``C B^T``."""
    C = xd.shape[0]
    # the masked exponents are set to 0 BEFORE exp: above the diagonal g[t] - g[s] > 0
    L = jnp.where(mask, jnp.exp(jnp.where(mask, g_col - g_row, 0.0)), 0.0)
    # the log decay of the whole chunk, [1, 1], read from either copy so that
    # every broadcast below is along ONE axis (Mosaic has no other)
    last = g_row[:, C - 1 : C]
    into_end = jnp.exp(g_col[C - 1 : C, :] - g_col)  # [C, 1]: from a token to the chunk's end
    return CB * L, L, last, into_end, xd.astype(_F32) * into_end


def _decayed(last, S):
    """``exp(last) S`` for a state ``[P, N]`` and ``last`` ``[1, 1]``."""
    return jnp.exp(jnp.broadcast_to(last, (1, S.shape[1]))) * S


def _head_fwd(xd, Bm, Cm, CB, mask, g_row, g_col, S0):
    """One head, one chunk: ``(Y [C, P], S1 [P, N])``, both float32."""
    mm = xd.dtype
    W, _, last, _, xe = _head_parts(xd, CB, mask, g_row, g_col)
    Y = _dot(W, xd, _NN, mm) + jnp.exp(g_col) * _dot(Cm, S0, _NT, mm)
    S1 = _decayed(last, S0) + _dot(xe, Bm, _TN, mm)
    return Y, S1


def _head_bwd(xd, Bm, Cm, CB, mask, g_row, g_col, S0, dY, dS1):
    """The transpose of :func:`_head_fwd`: cotangents of ``xd`` ``[C, P]``,
    ``CB`` ``[C, C]``, what the state's terms give ``B`` and ``C`` ``[C, N]``,
    the two parts of ``g``'s (``[1, C]`` and ``[C, 1]``) and ``S0``'s, all
    float32."""
    mm = xd.dtype
    C = xd.shape[0]
    W, L, last, into_end, xe = _head_parts(xd, CB, mask, g_row, g_col)
    dYg = dY.astype(_F32) * jnp.exp(g_col)
    dW = jnp.where(mask, _dot(dY, xd, _NT, mm), 0.0)
    dxe = _dot(Bm, dS1, _NT, mm)
    dxd = _dot(W, dY, _TN, mm) + into_end * dxe
    dB_state = _dot(xe, dS1, _NN, mm)
    dC_state = _dot(dYg, S0, _NN, mm)
    dS0 = _decayed(last, dS1) + _dot(dYg, Cm, _TN, mm)
    # g enters through L (rows up, columns down), exp(g) (C S0^T), and
    # exp(g_end - g) xd; g_end also through exp(g_end) S0
    M = dW * W
    dxe_xe = dxe * xe
    dg_col = (
        jnp.sum(M, axis=1, keepdims=True)
        + jnp.sum(dYg * _dot(Cm, S0, _NT, mm), axis=1, keepdims=True)
        - jnp.sum(dxe_xe, axis=1, keepdims=True)
    )
    d_last = jnp.sum(dxe_xe, keepdims=True) + jnp.exp(last) * jnp.sum(S0 * dS1, keepdims=True)
    at_end = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1) == C - 1
    dg_row = jnp.where(at_end, d_last, 0.0) - jnp.sum(M, axis=0, keepdims=True)
    return dxd, dW * L, dB_state, dC_state, dg_row, dg_col, dS0


def _group_cb(Bm, Cm):
    return _dot(Cm, Bm, _NT, Bm.dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def _one_hot_lane(width, h):
    return (jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) == h).astype(_F32)


def _first_block(blocks):
    """(Which of the group's head blocks a grid step works on, whether it is
    the first step of the (batch, group)'s walk)."""
    j = pl.program_id(3) if blocks > 1 else 0
    first = pl.program_id(2) == 0
    return j, first & (j == 0) if blocks > 1 else first


def _chunk_cb(Bm, Cm, j, blocks, cb_scr=None):
    """The group's ``C B^T`` of this chunk: made once, by the first head block."""
    if blocks == 1:
        return _group_cb(Bm, Cm)

    @pl.when(j == 0)
    def _make():
        cb_scr[...] = _group_cb(Bm, Cm)

    return cb_scr[...]


def _fwd_kernel(xd_ref, b_ref, c_ref, gr_ref, gc_ref, y_ref, h_ref, s_scr, *shared, heads, blocks):
    j, first = _first_block(blocks)

    @pl.when(first)
    def _start():
        s_scr[...] = jnp.zeros_like(s_scr)

    Bm, Cm = b_ref[0, 0], c_ref[0, 0]
    CB = _chunk_cb(Bm, Cm, j, blocks, *shared)
    mask = _causal(Bm.shape[0])
    g_cols = gc_ref[0, 0, 0]
    for h in range(heads):
        S0 = s_scr[j * heads + h]
        h_ref[0, h, 0] = S0
        Y, S1 = _head_fwd(
            xd_ref[0, h], Bm, Cm, CB, mask, gr_ref[0, 0, h : h + 1, :], g_cols[:, h : h + 1], S0
        )
        y_ref[0, h] = Y.astype(y_ref.dtype)
        s_scr[j * heads + h] = S1


def _bwd_kernel(
    xd_ref, b_ref, c_ref, gr_ref, gc_ref, h_ref, dy_ref,
    dxd_ref, db_ref, dc_ref, dgr_ref, dgc_ref, ds_scr, *shared, heads, blocks,
):
    j, first = _first_block(blocks)

    @pl.when(first)
    def _start():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    Bm, Cm = b_ref[0, 0], c_ref[0, 0]
    mm = Bm.dtype
    CB = _chunk_cb(Bm, Cm, j, blocks, *shared[:1])
    mask = _causal(Bm.shape[0])
    g_cols = gc_ref[0, 0, 0]
    dCB = jnp.zeros_like(CB)
    dB = jnp.zeros(Bm.shape, _F32)
    dC = jnp.zeros(Cm.shape, _F32)
    dg_cols = jnp.zeros(g_cols.shape, _F32)
    for h in range(heads):
        dxd, dCB_h, dB_h, dC_h, dg_row, dg_col, dS0 = _head_bwd(
            xd_ref[0, h], Bm, Cm, CB, mask, gr_ref[0, 0, h : h + 1, :], g_cols[:, h : h + 1],
            h_ref[0, h, 0], dy_ref[0, h], ds_scr[j * heads + h],
        )
        dxd_ref[0, h] = dxd.astype(dxd_ref.dtype)
        dgr_ref[0, 0, h : h + 1, :] = dg_row
        dg_cols = dg_cols + dg_col * _one_hot_lane(heads, h)
        dCB, dB, dC = dCB + dCB_h, dB + dB_h, dC + dC_h
        ds_scr[j * heads + h] = dS0
    dgc_ref[0, 0, 0] = dg_cols

    def write(dCB, dB, dC):
        db_ref[0, 0] = (dB + _dot(dCB, Cm, _TN, mm)).astype(db_ref.dtype)
        dc_ref[0, 0] = (dC + _dot(dCB, Bm, _NN, mm)).astype(dc_ref.dtype)

    if blocks == 1:
        return write(dCB, dB, dC)
    # what the group's heads share, summed over its blocks where it lies
    sums = shared[1:]

    @pl.when(j == 0)
    def _set():
        for ref, part in zip(sums, (dCB, dB, dC)):
            ref[...] = part

    @pl.when(j > 0)
    def _add():
        for ref, part in zip(sums, (dCB, dB, dC)):
            ref[...] += part

    @pl.when(j == blocks - 1)
    def _write():
        write(*(ref[...] for ref in sums))


def _specs(heads, blocks, chunk, P, N, at):
    """Block specs of a grid step (batch, group, chunk and, where a group is
    ``blocks`` > 1 blocks of ``heads`` heads, the block), ``at(c)`` the chunk a
    step works on: the block's rows, the group's rows, the running sum with
    the tokens along the lanes and along the sublanes, the block's states.
    The heads' arrays count the blocks of all groups along one axis."""

    def block(g, j):
        return g * blocks + j[0] if j else g

    return dict(
        head=pl.BlockSpec((1, heads, chunk, P), lambda b, g, c, *j: (b, block(g, j), at(c), 0)),
        group=pl.BlockSpec((1, 1, chunk, N), lambda b, g, c, *j: (b, g, at(c), 0)),
        g_row=pl.BlockSpec((1, 1, heads, chunk), lambda b, g, c, *j: (b, block(g, j), 0, at(c))),
        g_col=pl.BlockSpec((1, 1, 1, chunk, heads), lambda b, g, c, *j: (b, block(g, j), at(c), 0, 0)),
        state=pl.BlockSpec((1, heads, 1, P, N), lambda b, g, c, *j: (b, block(g, j), at(c), 0, 0)),
    )


def _grid(B, G, nt, blocks):
    """(grid, compiler parameters): the blocks of a group that has several
    are the innermost axis, walked in order under one chunk."""
    more = (blocks,) if blocks > 1 else ()
    semantics = ("parallel", "parallel", "arbitrary") + ("arbitrary",) * len(more)
    return (B, G, nt) + more, pltpu.CompilerParams(dimension_semantics=semantics)


def _rows(g_cols):
    """``[B, blocks, nt, C, heads]`` (tokens along the sublanes) to ``[B,
    blocks, heads, S]`` (along the lanes)."""
    B, G, nt, C, heads = g_cols.shape
    return g_cols.transpose(0, 1, 4, 2, 3).reshape(B, G, heads, nt * C)


def _shared_scratch(blocks, chunk, N, cotangents):
    """What a group of several head blocks keeps in VMEM for a chunk: ``C
    B^T`` and, in the backward kernel, the sums of ``d(C B^T)``, ``dB`` and
    ``dC`` over the blocks, all float32."""
    if blocks == 1:
        return []
    square, rows = pltpu.VMEM((chunk, chunk), _F32), pltpu.VMEM((chunk, N), _F32)
    return [square, square, rows, rows] if cotangents else [square]


def _fwd(xd, Bm, Cm, g_cols, interpret):
    """Heads-major ``xd [B, H, S, P]``, ``Bm, Cm [B, G, S, N]``, ``g_cols [B,
    H/b, S/C, C, b]`` for head blocks of ``b`` heads (a group is a whole
    number of them); ``(y [B, H, S, P], h [B, H, S/C, P, N])`` out, ``h`` the
    state every chunk started from."""
    B, H, S, P = xd.shape
    G, N = Bm.shape[1], Bm.shape[-1]
    nt, chunk, heads = g_cols.shape[2:]
    blocks = H // (G * heads)
    spec = _specs(heads, blocks, chunk, P, N, lambda c: c)
    grid, params = _grid(B, G, nt, blocks)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, blocks=blocks),
        grid=grid,
        in_specs=[spec["head"], spec["group"], spec["group"], spec["g_row"], spec["g_col"]],
        out_specs=[spec["head"], spec["state"]],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), xd.dtype),
            jax.ShapeDtypeStruct((B, H, nt, P, N), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((blocks * heads, P, N), _F32), *_shared_scratch(blocks, chunk, N, False)],
        compiler_params=params,
        interpret=interpret,
        name="ssd_fwd",
    )(xd, Bm, Cm, _rows(g_cols), g_cols)


def _bwd(xd, Bm, Cm, g_cols, h, dy, interpret):
    B, H, S, P = xd.shape
    G, N = Bm.shape[1], Bm.shape[-1]
    nt, chunk, heads = g_cols.shape[2:]
    blocks = H // (G * heads)
    # the chunks in reverse: the states' cotangent flows from the last one
    spec = _specs(heads, blocks, chunk, P, N, lambda c: nt - 1 - c)
    grid, params = _grid(B, G, nt, blocks)
    like = lambda x, dtype=None: jax.ShapeDtypeStruct(x.shape, dtype or x.dtype)  # noqa: E731
    g_rows = _rows(g_cols)
    dxd, dB, dC, dg_rows, dg_cols = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, blocks=blocks),
        grid=grid,
        in_specs=[
            spec["head"], spec["group"], spec["group"], spec["g_row"], spec["g_col"],
            spec["state"], spec["head"],
        ],
        out_specs=[spec["head"], spec["group"], spec["group"], spec["g_row"], spec["g_col"]],
        out_shape=[like(xd), like(Bm), like(Cm), like(g_rows, _F32), like(g_cols, _F32)],
        scratch_shapes=[pltpu.VMEM((blocks * heads, P, N), _F32), *_shared_scratch(blocks, chunk, N, True)],
        compiler_params=params,
        interpret=interpret,
        name="ssd_bwd",
    )(xd, Bm, Cm, g_rows, g_cols, h, dy)
    dg = dg_cols + dg_rows.reshape(*g_cols.shape[:2], heads, nt, chunk).transpose(0, 1, 3, 4, 2)
    return dxd, dB, dC, dg


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ssd_hm(xd, Bm, Cm, g_cols, interpret):
    return _fwd(xd, Bm, Cm, g_cols, interpret)[0]


def _ssd_hm_fwd(xd, Bm, Cm, g_cols, interpret):
    y, h = _fwd(xd, Bm, Cm, g_cols, interpret)
    # the named values are what the backward pass and the layer's later
    # parts need of the kernel: a policy that keeps the names leaves a
    # rematerialised layer no use for a second ``ssd_fwd``
    y, h = (checkpoint_name(a, n) for a, n in zip((y, h), KEPT_NAMES))
    return y, (xd, Bm, Cm, g_cols, h)


def _ssd_hm_bwd(interpret, res, dy):
    return _bwd(*res, dy, interpret)


_ssd_hm.defvjp(_ssd_hm_fwd, _ssd_hm_bwd)


# ---------------------------------------------------------------------------
# public entries ([B, S, H, P], the model's layout)
# ---------------------------------------------------------------------------


# the most heads of a group that one grid step takes: the kernels' bodies
# unroll a loop over them and their blocks are ``(heads, chunk, P)`` and
# ``(heads, P, N)``
HEAD_BLOCK = 8


def head_block(heads: int, block: Optional[int] = None) -> int:
    """How many of a group's ``heads`` a grid step of the kernels takes:
    ``block`` if given, else ``HEAD_BLOCK`` where that divides the group and
    the whole group where it does not."""
    block = block or (HEAD_BLOCK if heads % HEAD_BLOCK == 0 else heads)
    if heads % block or (block < heads and block % 8):
        raise ValueError(f"a group of {heads} heads does not divide into blocks of {block} (a multiple of 8 where it is not the group)")
    return block


def _prepare(x, dt, A_log, Bm, Cm, chunk, block=None):
    """Heads-major ``dt * x``, ``B`` and ``C``, and the running sum of the
    log decay inside each chunk ``[B, H/b, S/C, C, b]`` (float32) for blocks
    of ``b`` heads, a group's heads unless ``block`` says fewer."""
    B, S, H, _ = x.shape
    G = Bm.shape[2]
    if S % chunk or H % G:
        raise ValueError(f"S={S} not divisible by the chunk {chunk}, or {H} heads by {G} groups")
    block = block or H // G
    hm = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
    dt = dt.astype(_F32)
    xd = (x.astype(_F32) * dt[..., None]).astype(x.dtype)
    log_a = -dt * jnp.exp(A_log.astype(_F32))
    g_cols = jnp.cumsum(log_a.reshape(B, S // chunk, chunk, H // block, block), axis=2).transpose(0, 3, 1, 2, 4)
    return hm(xd), hm(Bm), hm(Cm), g_cols


def _with_skip(y_hm, x, D):
    """``y + D x`` in the model's layout and ``x``'s type."""
    y = y_hm.transpose(0, 2, 1, 3).astype(_F32) + D.astype(_F32)[:, None] * x.astype(_F32)
    return y.astype(x.dtype)


def ssd_chunked(
    x: jax.Array,
    dt: jax.Array,
    A_log: jax.Array,
    Bm: jax.Array,
    Cm: jax.Array,
    D: jax.Array,
    *,
    chunk: int = 128,
    block: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """The scan over whole sequences from a zero state, by the chunked
    kernels.  ``x`` ``[B, S, H, P]``, ``dt`` ``[B, S, H]`` (positive: after its
    softplus), ``A_log`` and ``D`` ``[H]``, ``Bm`` and ``Cm`` ``[B, S, G, N]``
    with ``H`` a multiple of ``G``.  Returns ``[B, S, H, P]`` in ``x``'s
    type.  ``S`` must be a multiple of ``chunk``; ``block`` is the heads of a
    group that a grid step takes (:func:`head_block`)."""
    chunk = min(chunk, x.shape[1])
    block = head_block(x.shape[2] // Bm.shape[2], block)
    return _with_skip(_ssd_hm(*_prepare(x, dt, A_log, Bm, Cm, chunk, block), interpret), x, D)


def ssd_chunked_plain(
    x: jax.Array,
    dt: jax.Array,
    A_log: jax.Array,
    Bm: jax.Array,
    Cm: jax.Array,
    D: jax.Array,
    *,
    chunk: int = 128,
) -> jax.Array:
    """:func:`ssd_chunked`'s algebra with no kernel: a ``lax.scan`` over the
    chunks of :func:`_head_fwd`, differentiated by jax."""
    chunk = min(chunk, x.shape[1])
    xd, Bh, Ch, g_cols = _prepare(x, dt, A_log, Bm, Cm, chunk)
    B, H, S, P = xd.shape
    G, N = Bh.shape[1], Bh.shape[-1]
    nt, heads = S // chunk, H // G
    mask = _causal(chunk)

    def group_chunk(xd, Bm, Cm, g_cols, S0):
        """One group, one chunk: ``xd [heads, C, P]``, ``g_cols [C, heads]``."""
        CB = _group_cb(Bm, Cm)
        outs = [
            _head_fwd(xd[h], Bm, Cm, CB, mask, g_cols[:, h][None, :], g_cols[:, h : h + 1], S0[h])
            for h in range(heads)
        ]
        return jnp.stack([o[0] for o in outs]), jnp.stack([o[1] for o in outs])

    step = jax.vmap(jax.vmap(group_chunk))

    def body(S0, xs):
        Y, S1 = step(*xs, S0)
        return S1, Y

    _, Y = jax.lax.scan(
        body,
        jnp.zeros((B, G, heads, P, N), _F32),
        (
            xd.reshape(B, G, heads, nt, chunk, P).transpose(3, 0, 1, 2, 4, 5),
            Bh.reshape(B, G, nt, chunk, N).transpose(2, 0, 1, 3, 4),
            Ch.reshape(B, G, nt, chunk, N).transpose(2, 0, 1, 3, 4),
            g_cols.transpose(2, 0, 1, 3, 4),
        ),
    )  # [nt, B, G, heads, C, P]
    y_hm = Y.transpose(1, 2, 3, 0, 4, 5).reshape(B, H, S, P).astype(x.dtype)
    return _with_skip(y_hm, x, D)
