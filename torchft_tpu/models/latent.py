"""What the models of DeepSeek-V3's line share, each defined ONCE: the
latent-attention mixer (MLA) and the multi-token-prediction module (MTP,
arXiv:2412.19437 section 2.2).  ``models/ling_hybrid.py`` (one MLA layer in
six, no query latent, a head-wise gate) and ``models/latent_moe.py`` (MLA in
every layer, a query latent, no gate, the module in the objective) both call
these; what differs between them is in the configuration a caller hands
over, never in a copy.

**The mixer** (:class:`LatentAttention`).  Keys and values come from a
normalised latent of ``kv_lora_rank`` channels, ``[k_nope, v] = RMSNorm(c_kv)
W_kvb`` a head; ONE rotary key of ``qk_rope_head_dim`` channels is shared by
every head; queries are ``h W_q`` or, with ``q_lora_rank``, ``RMSNorm(h W_qa)
W_qb``; rope turns the interleaved pairs (0, 1), (2, 3), ... of the rotary
channels; causal softmax of ``q k^T / sqrt(qk_nope + qk_rope)``; with
``head_gate`` the output is multiplied, a head, by ``sigmoid(h W_gate)``; then
``W_o``.  The scores are ``ops/flash_attention.py``'s at q and k heads of
``qk_nope + qk_rope`` and v heads of ``v_head_dim`` where ``kernels`` says so,
plain ``jax.numpy`` otherwise.

**The module** (:func:`mtp_token_nll`), depth 1: ``z_i = [RMSNorm(E[t_{i+1}])
; RMSNorm(x_i)] W_p`` from the trunk's last hidden state BEFORE its final
norm, one layer of the caller's (an MLA expert layer), a final norm of its
own, the caller's head (the SHARED embedding and head), the label
``t_{i+2}``.  It returns the cross-entropy of every position; which of them a
caller averages over (the last has no token after next) is the caller's.
Its own work traces under ``tpuft.mtp`` (``obs/spans.py``); its layer names
its parts as any layer does.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.models import decoder
from torchft_tpu.obs.spans import part


def rope_interleaved(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding on the pairs (0, 1), (2, 3), ... of the last axis;
    x [B, S, ..., R], float32 arithmetic."""
    S, R = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R))
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    shape = (1, S) + (1,) * (x.ndim - 3) + (R // 2, 1)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (R // 2, 2))
    even, odd = pairs[..., :1], pairs[..., 1:]
    out = jnp.concatenate([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


@dataclass(frozen=True)
class LatentAttention:
    dim: int
    n_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    rope_theta: float
    norm_eps: float
    q_lora_rank: Optional[int] = None  # None: queries straight from the hidden state
    head_gate: bool = False  # sigmoid(h W_gate), one a head, on the output
    # what the fan-in of ``wo`` is multiplied by at ``init`` (a writer of the
    # residual stream scaled down by the depth, where a caller wants that)
    out_fan_scale: float = 1.0
    dtype: Any = jnp.bfloat16

    def init(self, keys: Sequence[jax.Array]) -> Dict[str, jax.Array]:
        """The mixer's leaves from ``keys`` (six of them; the sixth is the
        query latent's second matrix, so that a mixer without one draws what
        it always drew)."""
        D, H = self.dim, self.n_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim

        normal = functools.partial(decoder.seeded, dtype=self.dtype)

        if self.q_lora_rank is None:
            w = {"wq": normal(keys[0], (D, H * qk), D)}
        else:
            w = {
                "w_q_a": normal(keys[0], (D, self.q_lora_rank), D),
                "q_norm": jnp.ones((self.q_lora_rank,), jnp.float32),
                "w_q_b": normal(keys[5], (self.q_lora_rank, H * qk), self.q_lora_rank),
            }
        w.update(
            w_kv_a=normal(keys[1], (D, self.kv_lora_rank + self.qk_rope_head_dim), D),
            kv_norm=jnp.ones((self.kv_lora_rank,), jnp.float32),
            w_kv_b=normal(
                keys[2], (self.kv_lora_rank, H * (self.qk_nope_head_dim + self.v_head_dim)), self.kv_lora_rank
            ),
            wo=normal(keys[4], (H * self.v_head_dim, D), H * self.v_head_dim * self.out_fan_scale),
        )
        if self.head_gate:
            w["w_gate"] = normal(keys[3], (D, H), D)
        return w

    @part("mixer_glue")
    def apply(self, h: jax.Array, w: Dict[str, jax.Array], kernels: bool) -> jax.Array:
        """h [B, S, D], the normalised hidden state in the matrices' dtype →
        the mixer's output [B, S, D]."""
        B, S, _ = h.shape
        H, nope, rot = self.n_heads, self.qk_nope_head_dim, self.qk_rope_head_dim
        if self.q_lora_rank is None:
            q = decoder.proj(h, w["wq"])
        else:
            q = decoder.proj(decoder.rms_norm(decoder.proj(h, w["w_q_a"]), w["q_norm"], self.norm_eps), w["w_q_b"])
        q = q.reshape(B, S, H, nope + rot)
        q = jnp.concatenate([q[..., :nope], rope_interleaved(q[..., nope:], self.rope_theta)], axis=-1)
        kv_a = decoder.proj(h, w["w_kv_a"])
        latent = decoder.rms_norm(kv_a[..., : self.kv_lora_rank], w["kv_norm"], self.norm_eps)
        k_rot = rope_interleaved(kv_a[..., self.kv_lora_rank :], self.rope_theta)
        kv = decoder.proj(latent, w["w_kv_b"]).reshape(B, S, H, nope + self.v_head_dim)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rot[:, :, None, :], (B, S, H, rot))], axis=-1
        )
        v = kv[..., nope:]
        if kernels:
            from torchft_tpu.ops.flash_attention import flash_attention

            block_q, block_k = decoder.flash_blocks(S)
            o = flash_attention(
                q, k, v, causal=True, block_q=block_q, block_k=block_k,
                interpret=decoder.assumed_backend() != "tpu",
            )
        else:
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / np.sqrt(nope + rot)
            scores = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], scores, -1e30)
            o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1).astype(q.dtype), v)
        if self.head_gate:
            o = o * jax.nn.sigmoid(decoder.proj(h, w["w_gate"]).astype(jnp.float32))[..., None].astype(o.dtype)
        return decoder.proj(o.reshape(B, S, -1), w["wo"])


def mtp_init(dim: int, k_proj: jax.Array, layer: Dict[str, Any], dtype: Any) -> Dict[str, Any]:
    """The module's leaves around ``layer``, a layer of the caller's making."""
    return {
        "enorm": jnp.ones((dim,), jnp.float32),
        "hnorm": jnp.ones((dim,), jnp.float32),
        "proj": decoder.seeded(k_proj, (2 * dim, dim), 2 * dim, dtype),
        "layer": layer,
        "final_norm": jnp.ones((dim,), jnp.float32),
    }


def mtp_token_nll(
    m: Dict[str, Any], embed: jax.Array, x: jax.Array, targets: jax.Array, *,
    layer: Callable[[jax.Array, Dict[str, Any]], Tuple[jax.Array, jax.Array, jax.Array]],
    head_nll: Callable[[jax.Array, jax.Array, jax.Array], jax.Array], norm_eps: float, dtype: Any,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(the cross-entropy of the token after next at every position [B, S],
    the module's router's load, its balance loss).

    ``x`` [B, S, D] is the trunk's last hidden state before its final norm,
    in the residual stream's dtype, which the module's stream keeps;
    ``targets`` [B, S] the next tokens, so position ``i`` pairs ``x_i`` with
    ``E[targets_i]`` and is labelled ``targets_{i+1}``.  The LAST position's
    label is the sequence's first target, wrapped: a caller that has no
    token after next there leaves that position out of its mean.
    ``layer(z, w) -> (z, load, balance)`` is one layer of the caller's,
    ``head_nll(z, norm_weight, labels)`` the cross-entropy [B, S] through the
    module's own final norm and the caller's head."""
    with part("mtp"):
        pair = jnp.concatenate(
            [
                decoder.rms_norm(embed[targets].astype(x.dtype), m["enorm"], norm_eps).astype(dtype),
                decoder.rms_norm(x, m["hnorm"], norm_eps).astype(dtype),
            ],
            axis=-1,
        )
        z = (pair @ m["proj"]).astype(x.dtype)
    z, load, balance = layer(z, m["layer"])
    with part("mtp"):
        return head_nll(z, m["final_norm"], jnp.roll(targets, -1, axis=1)), load, balance
