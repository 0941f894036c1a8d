"""A dense decoder over BYTES whose every layer mixes with chunk-summary
attention (EVA, arXiv:2302.04542, as EvaByte runs it: deterministic pooling,
no random features) and whose one head predicts several bytes ahead; for
training on one chip's share.

The published configuration this was built for is EvaByte's (``model_type``
``evabyte``, ``attention_class`` ``eva``, ``chunk_size`` 16, ``window_size``
2,048, ``num_pred_heads`` 8, a vocabulary of 320).  The stream is float32
(``fp32_skip_add``); a layer is

- the mixer: ``h = RMSNorm(x)`` with the weight ``1 + g``
  (``norm_add_unit_offset``); ``q, k, v = h W_q, h W_k, h W_v`` in heads of
  ``head_dim``, no grouping; rope over all of a head's channels on ``q`` and
  ``k``, the halves paired.  Position ``j`` lies in chunk ``j // chunk`` and
  window ``j // window``.  **Pooling**: with a head's learned ``phi`` and
  ``mu`` and ``s = head_dim ** -0.5``, a chunk's weights are the softmax over
  its positions of ``s (k_j . phi)``, its summary key their weighted sum of
  ``k`` plus ``mu`` and its summary value the same weights' sum of ``v``.
  **Attention**: query ``i`` sees the tokens ``j <= i`` of its own window,
  exactly, and the summaries of every chunk of every EARLIER window, under ONE
  softmax at scale ``s`` (one maximum, one denominator over both kinds of
  key).  No summary of the query's own window is ever seen, so no summary
  holds a later key: causality is exact.  ``x += concat_heads(o) W_o``;
- the feed-forward part: ``x += W_down(silu(W_gate h') * W_up h')``, ``h' =
  RMSNorm(x)``.

A final norm, then ONE head matrix of ``n_pred_heads x vocab_size`` columns,
its logits float32 (``fp32_logits``): slice ``m`` predicts the byte at ``t + 1
+ m``.  ``apply`` gives slice 0's logits.  ``loss`` is slice 0's mean
cross-entropy ALONE: it is what the mean of ``apply``'s cross-entropy is, and
the benchmark ties the two (``ftbench/harness.py`` ``forward_passes``).
``objective``, what a training step differentiates, is the mean over the
slices of each slice's mean over the positions that have its label (``t <= S -
1 - m``: left out, not wrapped).  The step's summary is ONE number,
``multibyte_nll``, the mean cross-entropy of slices 1 and up, which
``summary_stats`` hands the step's flight event.  The model has no state the
optimizer does not own.

What is the model's and what a kernel's: projections, norms, rope and the
pooling are here, plain ``jax.numpy`` (the pooling under its own part of the
compiled step, ``tpuft.mixer_pool``); the attention is
``ops/flash_attention.py`` ``eva_attention``, whose walk visits a row block's
live summary blocks and then its live token blocks (``eva_fwd``, ``eva_dq``,
``eva_dkv``) and hands back the summaries' gradient, which flows through the
pooling into ``k``, ``v``, ``phi`` and ``mu``.  ``attention_path`` is
``"flash"`` only if the kernels ran; off the TPU the two key sources are a
mask over plain attention and the path is named ``"plain: <why>"``.  With a
window that covers the sequence the layer IS causal softmax attention.

The layers are stacked and run under one ``lax.scan``.  A layer is
rematerialised in the backward pass but for its float32 input and what the
forward kernel made (``ops/flash_attention.py`` ``KEPT_NAMES``), so that
``eva_fwd`` stands once a layer in a step's program; the slices' logits are
made again in the backward pass and never kept.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.models import decoder
from torchft_tpu.obs.spans import part
from torchft_tpu.ops import flash_attention as flash
from torchft_tpu.parallel.moe import swiglu

KERNEL_PATH = "flash"


@dataclass(frozen=True)
class EvaConfig:
    vocab_size: int = 320
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    head_dim: int = 128
    ffn_hidden: int = 11_008
    window_size: int = 2048
    chunk_size: int = 16
    n_pred_heads: int = 8
    rope_theta: float = 100_000.0
    norm_eps: float = 1e-5
    init_std: float = 0.01275
    dtype: Any = jnp.bfloat16


def eva_debug(**over: Any) -> EvaConfig:
    """Tiny widths, two windows of four chunks in the tests' sequences, and
    matrices large enough for the attention to be far from uniform."""
    return replace(
        EvaConfig(
            vocab_size=40, dim=64, n_layers=2, n_heads=4, head_dim=16, ffn_hidden=128, window_size=32,
            chunk_size=8, n_pred_heads=4, init_std=0.2, dtype=jnp.float32,
        ),
        **over,
    )


class Eva:
    def __init__(self, config: EvaConfig, mesh: Optional[Any] = None) -> None:
        self.config = config
        self.mesh = mesh
        cfg = config
        if cfg.head_dim % 2 or cfg.chunk_size < 1 or cfg.window_size % cfg.chunk_size or cfg.n_pred_heads < 1:
            raise ValueError("rope pairs a head's halves, a window holds whole chunks, a head predicts a byte at least")
        # set when the layers are traced: KERNEL_PATH or "plain: <why>"
        self.attention_path: Optional[str] = None

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------

    def _init_layer(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        D, F, H, hd = cfg.dim, cfg.ffn_hidden, cfg.n_heads, cfg.head_dim
        keys = jax.random.split(key, 9)

        def normal(k, shape):
            return (cfg.init_std * jax.random.normal(k, shape, jnp.float32)).astype(cfg.dtype)

        def pooling(k):  # normal, clipped to [-1, 1], times the scores' scale
            return jnp.clip(jax.random.normal(k, (H, hd), jnp.float32), -1.0, 1.0) * hd ** -0.5

        return {
            # the norms' weights are 1 + g (``norm_add_unit_offset``): g starts at 0
            "attn_norm": jnp.zeros((D,), jnp.float32), "mlp_norm": jnp.zeros((D,), jnp.float32),
            "wq": normal(keys[0], (D, H * hd)), "wk": normal(keys[1], (D, H * hd)),
            "wv": normal(keys[2], (D, H * hd)), "wo": normal(keys[3], (H * hd, D)),
            # a head's two learned vectors: what a chunk's positions are scored
            # against, and what is added to a pooled key
            "phi": pooling(keys[4]), "mu": pooling(keys[5]),
            "w_gate": normal(keys[6], (D, F)), "w_up": normal(keys[7], (D, F)), "w_down": normal(keys[8], (F, D)),
        }

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        k_embed, k_out, k_layers = jax.random.split(key, 3)

        def normal(k, shape):
            return (cfg.init_std * jax.random.normal(k, shape, jnp.float32)).astype(cfg.dtype)

        return {
            "embed": normal(k_embed, (cfg.vocab_size, cfg.dim)),
            "layers": jax.vmap(self._init_layer)(jax.random.split(k_layers, cfg.n_layers)),
            "final_norm": jnp.zeros((cfg.dim,), jnp.float32),
            # ONE matrix: slice m's columns are [m * vocab_size, (m + 1) * vocab_size)
            "lm_head": normal(k_out, (cfg.dim, cfg.n_pred_heads * cfg.vocab_size)),
        }

    @functools.cached_property
    def _shapes(self) -> Any:
        return decoder.shapes(self.init)

    def param_specs(self) -> Dict[str, Any]:
        return decoder.one_chip_param_specs(self._shapes)

    def batch_specs(self) -> Tuple[Any, Any]:
        return decoder.batch_specs()

    def num_params(self) -> int:
        return decoder.num_params(self._shapes)

    @staticmethod
    def summary_stats(summary: np.ndarray) -> Dict[str, Any]:
        """``objective``'s summary on the host, as the flight event's detail:
        ``multibyte_nll``, the mean cross-entropy of the slices after the
        first (absent where the head predicts the next byte alone)."""
        flat = np.asarray(summary, np.float64).reshape(-1)
        return dict(multibyte_nll=float(flat[0])) if flat.size else {}

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------

    def _kernel_refusal(self, seq: int) -> Optional[str]:
        """Why the Mosaic kernels do NOT apply, or None when they do."""
        span = min(seq, self.config.window_size)  # a window that covers the sequence: causal attention
        block_q, block_k = decoder.flash_blocks(span)
        shape_refusal = None
        if seq < 32 or seq % 8 or seq % span or span % block_q or span % block_k:
            shape_refusal = f"seq={seq} does not divide into windows of {span} of whole blocks ({block_q}, {block_k})"
        return decoder.one_chip_refusal(shape_refusal, self.mesh)

    def _normed(self, x: jax.Array, g: jax.Array) -> jax.Array:
        """What a layer reads of the float32 residual stream: its RMS norm
        under the weight ``1 + g``, in the matrices' dtype."""
        return decoder.rms_norm(x, 1.0 + g, self.config.norm_eps).astype(self.config.dtype)

    @part("mixer_pool")
    def _pool(self, k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """k, v [B, S, H, hd] → the chunks' summary keys and values [B, S /
        chunk, H, hd]: float32 statistics over products' dtype operands."""
        cfg = self.config
        B, S, H, hd = k.shape
        chunked = (B, S // cfg.chunk_size, cfg.chunk_size, H, hd)
        k32, v32 = k.reshape(chunked).astype(jnp.float32), v.reshape(chunked).astype(jnp.float32)
        weights = jax.nn.softmax(jnp.sum(k32 * phi, axis=-1) * hd ** -0.5, axis=2)[..., None]  # [B, N, C, H, 1]
        k_pooled = jnp.sum(weights * k32, axis=2) + mu
        v_pooled = jnp.sum(weights * v32, axis=2)
        return k_pooled.astype(k.dtype), v_pooled.astype(v.dtype)

    @part("mixer_glue")
    def _attention(self, h: jax.Array, w: Dict[str, jax.Array], rope: Tuple[jax.Array, jax.Array], kernels: bool) -> jax.Array:
        cfg = self.config
        B, S, _ = h.shape
        H, hd, W, C = cfg.n_heads, cfg.head_dim, cfg.window_size, cfg.chunk_size
        q = decoder.apply_rope(decoder.proj(h, w["wq"]).reshape(B, S, H, hd), *rope)
        k = decoder.apply_rope(decoder.proj(h, w["wk"]).reshape(B, S, H, hd), *rope)  # rope BEFORE pooling
        v = decoder.proj(h, w["wv"]).reshape(B, S, H, hd)
        k_pooled, v_pooled = self._pool(k, v, w["phi"], w["mu"])
        if kernels:
            block_q, block_k = decoder.flash_blocks(min(S, W))
            o = flash.eva_attention(
                q, k, v, k_pooled, v_pooled, window=W, block_q=block_q, block_k=block_k,
                interpret=decoder.assumed_backend() != "tpu",
            )
        else:
            i, j, c = jnp.arange(S)[:, None], jnp.arange(S)[None, :], jnp.arange(S // C)[None, :]
            seen = jnp.concatenate([c * C // W < i // W, (j // W == i // W) & (j <= i)], axis=1)
            keys, values = jnp.concatenate([k_pooled, k], axis=1), jnp.concatenate([v_pooled, v], axis=1)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, keys).astype(jnp.float32) * hd ** -0.5
            probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1).astype(q.dtype)
            o = jnp.einsum("bhqk,bkhd->bqhd", probs, values)
        return decoder.proj(o.reshape(B, S, H * hd), w["wo"])

    def _block(self, x: jax.Array, w: Dict[str, Any], rope: Tuple[jax.Array, jax.Array], kernels: bool) -> jax.Array:
        """One residual block on the float32 stream."""
        with part("stream"):
            h = self._normed(x, w["attn_norm"])
        mixed = self._attention(h, w, rope, kernels)
        with part("stream"):
            x = x + mixed
            h = self._normed(x, w["mlp_norm"])
        with part("ffn"):
            out = swiglu(h @ w["w_gate"], h @ w["w_up"], 0.0) @ w["w_down"]
        with part("stream"):
            return x + out

    def _trunk(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """tokens [B, S] → the residual stream after the last layer."""
        cfg = self.config
        S = tokens.shape[1]
        if S % cfg.chunk_size:
            raise ValueError(f"seq={S} holds no whole number of chunks of {cfg.chunk_size}")
        refusal = self._kernel_refusal(S)
        kernels = refusal is None
        with part("embed"):
            x = params["embed"][tokens].astype(jnp.float32)  # the residual stream
        with part("mixer_glue"):
            rope = decoder.rope_table(S, cfg.head_dim, cfg.rope_theta)
        # kept through a layer's rematerialisation: its float32 input and the
        # forward kernel's output and row statistics, so that ``eva_fwd``
        # stands once a layer in a step
        x, _ = decoder.scan_run(
            lambda carry, w: (self._block(carry, w, rope, kernels), None), x, params["layers"], cfg.n_layers,
            keep=flash.KEPT_NAMES,
        )
        decoder.kernel_path(self, KERNEL_PATH, refusal)
        return x

    def _head(self, params: Dict[str, Any], x: jax.Array, slices: int) -> jax.Array:
        """The final norm and the head's first ``slices`` slices, [B, S,
        slices, vocab]; the products' float32 sums as they are: a logit is
        never rounded to the model's dtype."""
        cfg = self.config
        head = params["lm_head"][:, : slices * cfg.vocab_size]
        logits = decoder.head_logits(x, 1.0 + params["final_norm"], head, cfg.norm_eps, cfg.dtype)
        return logits.reshape(*x.shape[:2], slices, cfg.vocab_size)

    def apply_all(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """tokens [B, S] → every slice's logits [B, S, n_pred_heads, vocab]
        (fp32): slice ``m`` at ``t`` is of the byte at ``t + 1 + m``."""
        x = self._trunk(params, tokens)
        with part("head"):
            return self._head(params, x, self.config.n_pred_heads)

    def apply(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """tokens [B, S] → the next byte's logits [B, S, vocab] (fp32): slice 0."""
        x = self._trunk(params, tokens)
        with part("head"):
            return self._head(params, x, 1)[:, :, 0]

    @functools.partial(jax.checkpoint, static_argnums=(0, 4), prevent_cse=False)
    def _slice_nll(self, params: Dict[str, Any], x: jax.Array, targets: jax.Array, slices: int) -> jax.Array:
        """The mean cross-entropy of each of the first ``slices`` slices,
        [slices]: slice ``m`` at ``t`` against ``targets[t + m]`` (the byte at
        ``t + 1 + m``) over ``t <= S - 1 - m``; a position without the label is
        left out, not wrapped.  The logits are made again in the backward
        pass, never kept."""
        S = targets.shape[1]
        ahead = jnp.arange(slices)
        labels = jnp.stack([jnp.roll(targets, -m, axis=1) for m in range(slices)], axis=-1)  # [B, S, slices]
        has_label = jnp.arange(S)[:, None] + ahead < S  # [S, slices]
        nll = decoder.token_nll(self._head(params, x, slices), labels)
        return jnp.sum(jnp.where(has_label, nll, 0.0), axis=(0, 1)) / (targets.shape[0] * (S - ahead))

    def loss(self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]) -> jax.Array:
        """Mean next-byte cross-entropy, and nothing else: the mean of
        ``apply``'s cross-entropy; batch = (tokens, targets)."""
        tokens, targets = batch
        x = self._trunk(params, tokens)
        with part("head"):
            return self._slice_nll(params, x, targets, 1)[0]

    def objective(
        self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]
    ) -> Tuple[jax.Array, Tuple[List[jax.Array], jax.Array]]:
        """What a training step differentiates (the plain mean of the slices'
        mean cross-entropies), no signal (the model has no state of its own)
        and the step's summary: the mean over slices 1 and up."""
        tokens, targets = batch
        x = self._trunk(params, tokens)
        with part("head"):
            means = self._slice_nll(params, x, targets, self.config.n_pred_heads)
            return jnp.mean(means), ([], jnp.mean(means[1:], keepdims=True) if means.size > 1 else means[:0])
