"""A selective state-space scan with a decay for every (channel, state) pair
(Mamba's, arXiv:2312.00752), forward and backward (Pallas, TPU).

A channel ``c`` keeps a state ``h[c, :]`` of ``N`` numbers and, token by token,
with ``B_t`` and ``C_t`` in ``R^N`` shared by all channels,

    h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n C_t[n] h_t[c, n] + D[c] u_t[c],        A < 0, dt > 0

That recurrence is the plain reference's (``ftbench/architectures/
sambay_reference.py``) and the tests'.  The decay is a number for every
``(c, n)``: there is no ``exp(g[t] - g[s])`` a head that would let a chunk be
written as matrix products (``ops/ssd.py``), so the kernels RUN the recurrence,
on the vector unit, with the state in VMEM: a state is ``[N, channels]``, the
states along the sublanes and 512 channels along the lanes, and a token costs a
few operations on it.  By ``lax.scan`` or ``associative_scan`` XLA holds every
token's state in HBM (5.4 GB a layer at 16,384 tokens of 5,120 channels); here
none ever reaches it.

Kernels: ``selscan_fwd`` walks a block of channels' chunks in order and keeps
``y`` and each chunk's STARTING state; ``selscan_bwd`` walks the chunks in
reverse with the state's cotangent in VMEM, makes the states inside a chunk
again from the kept one (in VMEM, a chunk's worth) and applies the
hand-written transpose of the recurrence.  Every exponent is ``dt A <= 0``:
nothing is factored into a growing and a falling part, nothing is skipped.
The state, ``dt``, the decays and every accumulation are float32.  What is
elementwise in the tokens stays outside, in XLA, differentiated by jax: ``dt *
u`` and ``D u``.  ``B_t[n]`` and ``C_t[n]`` enter with the tokens along the
lanes and are spread over the lanes once a chunk; their gradients leave as a
row of ``(token, state)`` sums made by one product with ones a chunk.

The forward rule's residuals carry a ``checkpoint_name`` (``KEPT_NAMES``: the
output and the chunk-start states): a caller that rematerialises its layers
lists the names in its policy and ``selscan_fwd`` runs once a step.

``selscan_plain`` is the same chunk walk as ``lax.scan`` in ``jax.numpy``,
differentiated by jax with a chunk made again from its starting state: what a
model takes off the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.ops.kda import _NT, _dot

_F32 = jnp.float32
# the forward rule's residuals that a rematerialising caller's policy may keep
KEPT_NAMES = ("selscan_y", "selscan_states")
_LANES = 128
_GROUP = 8  # tokens a step of the inner loop: one tile of sublanes
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=48 * 1024 * 1024
)


def channel_block(channels: int) -> int:
    """Channels a grid step holds: 512 lanes where they divide the channels."""
    return next((w for w in (512, 256, 128) if channels % w == 0), channels)


def _spread(rows_ref, out_ref, chunk):
    """``rows_ref``'s block ``[1, N, chunk]`` (a token a lane) into ``out_ref`` ``[chunk
    * N, 128]``: token ``t``'s ``N`` numbers down the sublanes of rows ``t N
    ..``, each across all lanes."""
    rows = rows_ref[0].astype(_F32)
    N = rows.shape[0]
    for t in range(chunk):
        out_ref[t * N : (t + 1) * N, :] = jnp.broadcast_to(rows[:, t : t + 1], (N, _LANES))


def _wide(tile, width):
    """``[N, 128]`` beside itself up to ``width`` lanes."""
    return tile if width == _LANES else jnp.concatenate([tile] * (width // _LANES), axis=1)


def _fold(x):
    """The lanes of ``x`` ``[N, width]`` added 128 on 128: ``[N, 128]``."""
    out = x[:, :_LANES]
    for lo in range(_LANES, x.shape[1], _LANES):
        out = out + x[:, lo : lo + _LANES]
    return out


def _row_into(tile, k, row):
    """``tile`` ``[8, width]`` with row ``k`` set to ``row`` ``[1, width]``."""
    return jnp.where(jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) == k, row, tile)


def _at(i, k, N):
    """Rows of token ``8 i + k`` in a ``[chunk * N, ..]`` scratch."""
    return pl.ds(pl.multiple_of((i * _GROUP + k) * N, N), N)


def _advance(H, A, dt, dtu, Bw):
    """The state after a token: ``exp(dt A) H + (dt u) B``; ``dt`` and ``dtu``
    ``[1, width]``, ``Bw`` the token's ``B`` across the lanes."""
    return jnp.exp(dt * A) * H + dtu * Bw


def _fwd_kernel(dtu_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref, s_scr, bb_scr, cb_scr, y_scr, *, chunk):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_scr[...] = jnp.zeros_like(s_scr)

    A = a_ref[...]
    N, width = A.shape
    h_ref[0, 0] = s_scr[...]
    _spread(b_ref, bb_scr, chunk)
    _spread(c_ref, cb_scr, chunk)

    def group(i, H):
        at = pl.ds(pl.multiple_of(i * _GROUP, _GROUP), _GROUP)
        dt8, dtu8 = dt_ref[0, at, :], dtu_ref[0, at, :]
        y8 = jnp.zeros((_GROUP, width), _F32)
        for k in range(_GROUP):
            H = _advance(H, A, dt8[k : k + 1], dtu8[k : k + 1], _wide(bb_scr[_at(i, k, N), :], width))
            y8 = _row_into(y8, k, jnp.sum(_wide(cb_scr[_at(i, k, N), :], width) * H, axis=0, keepdims=True))
        y_scr[at, :] = y8
        return H

    s_scr[...] = jax.lax.fori_loop(0, chunk // _GROUP, group, s_scr[...])
    y_ref[0] = y_scr[...].astype(y_ref.dtype)


def _bwd_kernel(
    dtu_ref, dt_ref, a_ref, b_ref, c_ref, h_ref, dy_ref,
    ddtu_ref, ddt_ref, da_ref, db_ref, dc_ref,
    g_scr, hs_scr, bb_scr, cb_scr, sb_scr, sc_scr, dy_scr, *, chunk,
):
    first = pl.program_id(2) == 0

    @pl.when(first)
    def _start():
        g_scr[...] = jnp.zeros_like(g_scr)
        da_ref[...] = jnp.zeros_like(da_ref)

    A = a_ref[...]
    N, width = A.shape
    _spread(b_ref, bb_scr, chunk)
    _spread(c_ref, cb_scr, chunk)
    dy_scr[...] = dy_ref[0].astype(_F32)

    def again(i, H):
        """The chunk's states once more: ``hs_scr`` holds the state BEFORE
        every token."""
        at = pl.ds(pl.multiple_of(i * _GROUP, _GROUP), _GROUP)
        dt8, dtu8 = dt_ref[0, at, :], dtu_ref[0, at, :]
        for k in range(_GROUP):
            hs_scr[_at(i, k, N), :] = H
            H = _advance(H, A, dt8[k : k + 1], dtu8[k : k + 1], _wide(bb_scr[_at(i, k, N), :], width))
        return H

    jax.lax.fori_loop(0, chunk // _GROUP, again, h_ref[0, 0])

    def group(j, carry):
        G_next, dA = carry  # the state's cotangent from the tokens after, already decayed
        i = chunk // _GROUP - 1 - j
        at = pl.ds(pl.multiple_of(i * _GROUP, _GROUP), _GROUP)
        dt8, dtu8, dy8 = dt_ref[0, at, :], dtu_ref[0, at, :], dy_scr[at, :]
        ddtu8 = jnp.zeros((_GROUP, width), _F32)
        ddt8 = jnp.zeros((_GROUP, width), _F32)
        for k in reversed(range(_GROUP)):
            rows = _at(i, k, N)
            dt, dtu, dy = dt8[k : k + 1], dtu8[k : k + 1], dy8[k : k + 1]
            Bw = _wide(bb_scr[rows, :], width)
            before = hs_scr[rows, :]
            decay = jnp.exp(dt * A)
            H = decay * before + dtu * Bw
            G = _wide(cb_scr[rows, :], width) * dy + G_next
            sc_scr[rows, :] = _fold(dy * H)
            sb_scr[rows, :] = _fold(G * dtu)
            ddtu8 = _row_into(ddtu8, k, jnp.sum(G * Bw, axis=0, keepdims=True))
            d_exponent = G * before * decay  # of dt A
            ddt8 = _row_into(ddt8, k, jnp.sum(d_exponent * A, axis=0, keepdims=True))
            dA = dA + d_exponent * dt
            G_next = decay * G
        ddtu_ref[0, at, :] = ddtu8
        ddt_ref[0, at, :] = ddt8
        return G_next, dA

    G_next, dA = jax.lax.fori_loop(0, chunk // _GROUP, group, (g_scr[...], jnp.zeros_like(A)))
    g_scr[...] = G_next
    da_ref[0, 0] += dA
    # a (token, state) sum over this block's channels: the lanes by ONE
    # product with ones, the answers along the lanes in the order of [chunk, N]
    ones = jnp.ones((_GROUP, _LANES), _F32)
    db_ref[0, 0] = _dot(ones, sb_scr[...], _NT, _F32)
    dc_ref[0, 0] = _dot(ones, sc_scr[...], _NT, _F32)


def _specs(chunk, N, width, at):
    """Block specs of a grid step (batch, channel block, chunk), ``at(c)`` the
    chunk a step works on."""
    return dict(
        tokens=pl.BlockSpec((1, chunk, width), lambda b, j, c: (b, at(c), j)),
        a=pl.BlockSpec((N, width), lambda b, j, c: (0, j)),
        bc=pl.BlockSpec((1, N, chunk), lambda b, j, c: (b, 0, at(c))),
        state=pl.BlockSpec((1, 1, N, width), lambda b, j, c: (b, at(c), 0, j)),
        da=pl.BlockSpec((1, 1, N, width), lambda b, j, c: (b, 0, 0, j)),
        sums=pl.BlockSpec((1, 1, _GROUP, chunk * N), lambda b, j, c: (b, j, 0, at(c))),
    )


def _sizes(dtu, At, chunk):
    B, S, C = dtu.shape
    N = At.shape[0]
    return B, S, C, N, channel_block(C), S // chunk


def _fwd(dtu, dt, At, Bt, Ct, dtype, chunk, interpret):
    """``dtu``, ``dt`` ``[B, S, C]`` float32, ``At`` ``[N, C]``, ``Bt``, ``Ct``
    ``[B, N, S]``; ``(y [B, S, C] in dtype, h [B, S / chunk, N, C])`` out, ``h``
    the state every chunk started from."""
    B, S, C, N, width, nt = _sizes(dtu, At, chunk)
    spec = _specs(chunk, N, width, lambda c: c)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk),
        grid=(B, C // width, nt),
        in_specs=[spec["tokens"], spec["tokens"], spec["a"], spec["bc"], spec["bc"]],
        out_specs=[spec["tokens"], spec["state"]],
        out_shape=[jax.ShapeDtypeStruct((B, S, C), dtype), jax.ShapeDtypeStruct((B, nt, N, C), _F32)],
        scratch_shapes=[
            pltpu.VMEM((N, width), _F32), pltpu.VMEM((chunk * N, _LANES), _F32), pltpu.VMEM((chunk * N, _LANES), _F32),
            pltpu.VMEM((chunk, width), _F32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="selscan_fwd",
    )(dtu, dt, At, Bt, Ct)


def _bwd(dtu, dt, At, Bt, Ct, h, dy, chunk, interpret):
    B, S, C, N, width, nt = _sizes(dtu, At, chunk)
    # the chunks in reverse: the state's cotangent flows from the last one
    spec = _specs(chunk, N, width, lambda c: nt - 1 - c)
    blocks = C // width
    sums = jax.ShapeDtypeStruct((B, blocks, _GROUP, S * N), _F32)
    scratch = lambda rows, lanes: pltpu.VMEM((rows, lanes), _F32)  # noqa: E731
    ddtu, ddt, dA, dB, dC = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        grid=(B, blocks, nt),
        in_specs=[spec["tokens"], spec["tokens"], spec["a"], spec["bc"], spec["bc"], spec["state"], spec["tokens"]],
        out_specs=[spec["tokens"], spec["tokens"], spec["da"], spec["sums"], spec["sums"]],
        out_shape=[
            jax.ShapeDtypeStruct(dtu.shape, _F32), jax.ShapeDtypeStruct(dtu.shape, _F32),
            jax.ShapeDtypeStruct((B, 1, N, C), _F32), sums, sums,
        ],
        scratch_shapes=[
            scratch(N, width), scratch(chunk * N, width), scratch(chunk * N, _LANES), scratch(chunk * N, _LANES),
            scratch(chunk * N, _LANES), scratch(chunk * N, _LANES), scratch(chunk, width),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="selscan_bwd",
    )(dtu, dt, At, Bt, Ct, h, dy)
    # every row of a block's sums is the same; the blocks' are added here
    token_state = lambda sums: jnp.sum(sums[:, :, 0], axis=1).reshape(B, S, N).transpose(0, 2, 1)  # noqa: E731
    return ddtu, ddt, jnp.sum(dA[:, 0], axis=0), token_state(dB), token_state(dC)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan(dtu, dt, At, Bt, Ct, dtype, chunk, interpret):
    return _fwd(dtu, dt, At, Bt, Ct, dtype, chunk, interpret)[0]


def _scan_fwd(dtu, dt, At, Bt, Ct, dtype, chunk, interpret):
    y, h = _fwd(dtu, dt, At, Bt, Ct, dtype, chunk, interpret)
    # the named values are what the backward pass and the layer's later parts
    # need of the kernel: a policy that keeps the names leaves a
    # rematerialised layer no use for a second ``selscan_fwd``
    y, h = (checkpoint_name(a, n) for a, n in zip((y, h), KEPT_NAMES))
    return y, (dtu, dt, At, Bt, Ct, h)


def _scan_bwd(dtype, chunk, interpret, res, dy):
    dtu, dt, At, Bt, Ct, h = res
    ddtu, ddt, dAt, dBt, dCt = _bwd(dtu, dt, At, Bt, Ct, h, dy, chunk, interpret)
    return ddtu, ddt, dAt, dBt.astype(Bt.dtype), dCt.astype(Ct.dtype)


_scan.defvjp(_scan_fwd, _scan_bwd)


# ---------------------------------------------------------------------------
# public entries ([B, S, C], the model's layout)
# ---------------------------------------------------------------------------


def _prepare(u, dt, A, Bm, Cm, chunk):
    """``dt * u`` and ``dt`` in float32, ``A`` with the states down the
    sublanes, ``B`` and ``C`` with the tokens along the lanes."""
    S = u.shape[1]
    if S % chunk or chunk % _GROUP:
        raise ValueError(f"S={S} not divisible by the chunk {chunk}, or the chunk by {_GROUP}")
    dt = dt.astype(_F32)
    tokens_last = lambda a: a.astype(_F32).transpose(0, 2, 1)  # noqa: E731
    return dt * u.astype(_F32), dt, A.astype(_F32).T, tokens_last(Bm), tokens_last(Cm)


def _with_skip(y, u, D):
    """``y + D u`` in ``u``'s type."""
    return (y.astype(_F32) + D.astype(_F32) * u.astype(_F32)).astype(u.dtype)


def selscan(
    u: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array, Cm: jax.Array, D: jax.Array,
    *, chunk: int = 128, interpret: bool = False,
) -> jax.Array:
    """The scan over whole sequences from a zero state, by the kernels.  ``u``
    ``[B, S, C]``, ``dt`` ``[B, S, C]`` (positive: after its softplus), ``A``
    ``[C, N]`` (negative), ``Bm`` and ``Cm`` ``[B, S, N]``, ``D`` ``[C]``.
    Returns ``[B, S, C]`` in ``u``'s type.  ``S`` must be a multiple of
    ``chunk`` and ``chunk`` of 8."""
    chunk = min(chunk, u.shape[1])
    return _with_skip(_scan(*_prepare(u, dt, A, Bm, Cm, chunk), u.dtype, chunk, interpret), u, D)


def selscan_plain(
    u: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array, Cm: jax.Array, D: jax.Array, *, chunk: int = 128,
) -> jax.Array:
    """:func:`selscan`'s walk with no kernel: a ``lax.scan`` over the chunks of
    a ``lax.scan`` over a chunk's tokens, differentiated by jax, a chunk made
    again from its starting state in the backward pass."""
    chunk = min(chunk, u.shape[1])
    dtu, dt, At, Bt, Ct = _prepare(u, dt, A, Bm, Cm, chunk)
    B, S, C = dtu.shape
    N = At.shape[0]

    def token(H, xs):  # H [B, N, C]
        dtu, dt, b, c = xs  # [B, C], [B, C], [B, N], [B, N]
        H = jnp.exp(dt[:, None, :] * At) * H + dtu[:, None, :] * b[:, :, None]
        return H, jnp.sum(c[:, :, None] * H, axis=1)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def one_chunk(H, xs):
        return jax.lax.scan(token, H, xs)

    by_chunk = lambda a: jnp.moveaxis(a, 1, 0).reshape(S // chunk, chunk, *a.shape[:1], *a.shape[2:])  # noqa: E731
    xs = (by_chunk(dtu), by_chunk(dt), by_chunk(Bt.transpose(0, 2, 1)), by_chunk(Ct.transpose(0, 2, 1)))
    _, y = jax.lax.scan(one_chunk, jnp.zeros((B, N, C), _F32), xs)  # [S / chunk, chunk, B, C]
    return _with_skip(jnp.moveaxis(y.reshape(S, B, C), 0, 1), u, D)
