"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

Net-new relative to the reference (torchft has no sequence parallelism,
SURVEY.md §5.7) but first-class here: long-context training must scale past
one chip's HBM, and the TPU-native way is blockwise causal attention with
K/V blocks rotating around the ``sp`` ring via ``lax.ppermute`` over ICI
(the Ring Attention construction, with flash-style online-softmax
accumulation so memory stays O(block)).

Layout: Q/K/V are sharded on the sequence dim over ``sp`` (and heads over
``tp``); each of the ``n`` ring steps overlaps one neighbor exchange with
one block of attention math.  Causality across blocks falls out of global
block indices: a K/V block from a later position contributes nothing, the
diagonal block is masked triangularly, earlier blocks attend fully.
"""

from __future__ import annotations

import logging
import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


logger = logging.getLogger(__name__)


def _block_flash_refusal(S: int) -> Optional[str]:
    """Why the per-block math is NOT the Pallas kernel at local block length
    ``S``, or None when it is (``TORCHFT_FLASH`` forces/kills; interpret
    mode off TPU) — the same gates as ``Llama._flash_refusal``."""
    env = os.environ.get("TORCHFT_FLASH", "")
    if env == "0":
        return "TORCHFT_FLASH=0"
    # S % 8: Mosaic sublane-divisibility
    if S < 128 or S % 8 or S % min(512, S):
        return f"block length {S} is under 128 or not divisible by 8 and 512"
    if env != "1" and jax.default_backend() != "tpu":
        return f"backend is {jax.default_backend()}, not tpu"
    return None


def _ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
) -> jax.Array:
    """shard_map body: q is a LOCAL block [B, S_blk, H, D]; k/v are LOCAL
    blocks [B, S_blk, KV, D] with H % KV == 0 (GQA **un-repeated** — the
    ring ships the grouped K/V and broadcasts to full heads only at
    compute time, cutting ppermute bytes by the group factor).

    Online softmax across ring steps (numerically stable streaming
    accumulation); one ppermute per step rotates the K/V block to the next
    neighbor so every block visits every rank.
    """
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    groups = H // k.shape[2]
    scale = 1.0 / np.sqrt(D)

    # per-block flash: the Pallas kernel replaces the einsum-softmax block
    # math when block shapes qualify (trace-time decision, logged)
    refusal = _block_flash_refusal(S)
    if refusal is None:
        logger.info("ring attention block math: flash")
        return _ring_attention_flash(q, k, v, axis_name, n, my_idx)
    logger.info("ring attention block math: naive: %s", refusal)

    q32 = q.astype(jnp.float32)
    # accumulators: running output (unnormalized), row max, denominator
    o = jnp.zeros((B, S, H, D), dtype=jnp.float32)
    m = jnp.full((B, S, H), -jnp.inf, dtype=jnp.float32)
    l = jnp.zeros((B, S, H), dtype=jnp.float32)

    # local positions within a block (global offset falls out of block idx)
    row_pos = jnp.arange(S)
    col_pos = jnp.arange(S)

    def step(carry, step_idx):
        o, m, l, k_blk, v_blk = carry
        src_idx = (my_idx - step_idx) % n  # whose block we hold this step

        # broadcast the grouped K/V block to full heads at compute time
        k_full = jnp.repeat(k_blk, groups, axis=2)
        v_full = jnp.repeat(v_blk, groups, axis=2)
        scores = (
            jnp.einsum("bqhd,bkhd->bqhk", q32, k_full.astype(jnp.float32))
            * scale
        )
        # causal mask from global block indices:
        #   src block earlier   → attend fully
        #   same block          → lower triangle
        #   src block later     → nothing
        tri = row_pos[:, None] >= col_pos[None, :]
        allow = jnp.where(
            src_idx < my_idx,
            jnp.ones((S, S), dtype=bool),
            jnp.where(src_idx == my_idx, tri, jnp.zeros((S, S), dtype=bool)),
        )
        scores = jnp.where(allow[None, :, None, :], scores, -1e30)

        blk_max = jnp.max(scores, axis=-1)  # [B,S,H]
        m_new = jnp.maximum(m, blk_max)
        correction = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])  # [B,S,H,K]
        l_new = l * correction + jnp.sum(p, axis=-1)
        o_new = o * correction[..., None] + jnp.einsum(
            "bqhk,bkhd->bqhd", p, v_full.astype(jnp.float32)
        )

        # rotate K/V to the next rank (ring over ICI)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (o_new, m_new, l_new, k_next, v_next), None

    (o, m, l, _, _), _ = jax.lax.scan(
        step, (o, m, l, k, v), jnp.arange(n)
    )
    # rows that attended to nothing (can't happen causally, but guard /0)
    denom = jnp.where(l > 0, l, 1.0)
    return (o / denom[..., None]).astype(q.dtype)


def _ring_attention_flash(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    n: int,
    my_idx: jax.Array,
) -> jax.Array:
    """Ring attention with the fused Pallas kernel as the per-block math.

    Each ring step runs :func:`flash_attention_lse` on the held K/V block
    (causal for the diagonal block, unmasked for earlier blocks, skipped
    for later ones — the same block relationship the einsum path masks
    with) and merges the normalized partials exactly via logsumexp:
    ``lse' = logaddexp(lse, lse_b)``,
    ``o' = o·exp(lse−lse') + o_b·exp(lse_b−lse')``.

    Step 0 is always the diagonal block, so ``lse`` is finite from the
    first merge and the −inf initializations never meet each other.
    """
    from torchft_tpu.ops.flash_attention import flash_attention_lse

    interpret = jax.default_backend() != "tpu"
    B, S, H, D = q.shape

    def _block(causal):
        def run(k_blk, v_blk):
            return flash_attention_lse(
                q, k_blk, v_blk, causal=causal, interpret=interpret
            )

        return run

    diag, full = _block(True), _block(False)

    def skip(k_blk, v_blk):
        return (
            jnp.zeros((B, S, H, D), q.dtype),
            jnp.full((B, S, H), -jnp.inf, jnp.float32),
        )

    o0 = jnp.zeros((B, S, H, D), jnp.float32)
    lse0 = jnp.full((B, S, H), -jnp.inf, jnp.float32)

    def step(carry, step_idx):
        o, lse, k_blk, v_blk = carry
        src_idx = (my_idx - step_idx) % n
        o_b, lse_b = jax.lax.cond(
            src_idx == my_idx,
            diag,
            lambda kb, vb: jax.lax.cond(src_idx < my_idx, full, skip, kb, vb),
            k_blk,
            v_blk,
        )
        lse_new = jnp.logaddexp(lse, lse_b)
        w_old = jnp.exp(lse - lse_new)
        w_new = jnp.exp(lse_b - lse_new)
        o = o * w_old[..., None] + o_b.astype(jnp.float32) * w_new[..., None]

        perm = [(j, (j + 1) % n) for j in range(n)]
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (o, lse_new, k_next, v_next), None

    (o, _, _, _), _ = jax.lax.scan(step, (o0, lse0, k, v), jnp.arange(n))
    return o.astype(q.dtype)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    sp_axis: str = "sp",
) -> jax.Array:
    """Ring attention entry point for jit-traced (global-shape) arrays.

    q: [B, S, H, D]; k/v: [B, S, KV, D] un-repeated (H % KV == 0), with S
    sharded over ``sp_axis``, B over ``(dp, fsdp)`` (activations shard
    over the fsdp axis too — ``Llama.batch_specs``), and heads over
    ``tp``; returns attention output in q's layout.
    """
    batch_entry = ("dp", "fsdp") if "fsdp" in mesh.shape else "dp"
    spec = P(batch_entry, sp_axis, "tp", None)
    fn = jax.shard_map(
        partial(_ring_attention_local, axis_name=sp_axis),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def ring_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, axis_name: str = "sp"
) -> jax.Array:
    """Raw collective form for callers already inside shard_map/pmap."""
    return _ring_attention_local(q, k, v, axis_name)
