"""A decoder whose attention reads only the keys a learned index picks, with
routed experts in every layer, for training on one chip's share.

Every layer is the same pre-norm block (the published configuration this was
built for is Keye-VL-2.0-30B-A3B's language model, ``model_type``
``KeyeVL2``):

- grouped-query attention with an RMSNorm a head on q and k and multimodal
  rope (three position streams, each turning its own section of the
  frequency pairs; a text batch gives the three alike, which is plain rope);
- beside it the index (DeepSeek-V3.2's sparse attention): from the
  normalised hidden state, WITHOUT its gradient, ``index_heads`` small query
  heads, one key head and a weight a head score every earlier position, the
  ``index_topk`` best are the query's key set, and attention runs over that
  set alone (``ops/indexed_attention.py``).  The index learns from its own
  loss ``L_I``, the divergence of its softmax over the set from the
  attention's head-mean probabilities; that loss moves the index's three
  matrices and nothing else, and the language-model loss moves none of them;
- routed experts (``parallel/moe.py`` ``RoutedExperts``, told which experts
  are here): a float32 softmax over all experts, the ``top_k`` best,
  renormalised; no groups, no selection bias, no shared expert.

Layers are stacked and run under one ``lax.scan``, each rematerialised in
the backward pass but for what costs most to make again for its bytes: the
key sets (``SELECTION_NAMES``: bits, 32 MB a layer at 16,384 positions) and
what the attention kernel and the index's loss made of them (``KEPT_NAMES``
of ``ops/indexed_attention.py``: the attention's output and row statistics,
``L_I``'s finished gradient; 212 MB a layer at 16,384 positions and 32 query
heads, linear in both).  So each of the six kernels runs once a layer and
step; the projections, norms, rope and experts around them run again.

``attention_path`` is ``"dsa"`` only if every layer took the Mosaic kernels
and the experts the grouped kernel; off the TPU the same mathematics runs as
plain ``jax.numpy`` with dense ``[S, S]`` arrays and the path is named
``"plain: <why>"``.

``loss`` is the next-token cross-entropy; ``objective`` (what a training
step differentiates) adds ``L_I`` and the routers' balance loss and gives
``HSDPTrainer`` the step's summary: a layer's rows on the held experts, its
largest and mean load, ``L_I`` and the keys a query read.

A batch is ``(tokens, targets)``; :meth:`apply`, :meth:`loss` and
:meth:`objective` also take ``(tokens, targets, positions)`` with position
streams ``[3, B, S]`` and ``(tokens, targets, positions, embeds, given)``
where ``embeds [B, S, D]`` stand in for the token embeddings wherever
``given [B, S]`` is set: what a vision tower hands over.  No tower is built.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from torchft_tpu.models import decoder
from torchft_tpu.obs.spans import part
from torchft_tpu.ops.indexed_attention import (
    KEPT_NAMES, Blocks, indexed_attention, indexed_attention_plain, select_keys,
)
from torchft_tpu.parallel import moe
from torchft_tpu.parallel.moe import RoutedExperts, RoutedExpertsConfig

KERNEL_PATH = "dsa"
SELECTION_NAMES = ("dsa_mask", "dsa_lse", "dsa_keys")
SUMMARY_FIELDS = ("rows_here", "load_max", "load_mean", "index_kl", "keys_per_query", "buffer_rows")


@dataclass(frozen=True)
class IndexedSparseMoEConfig:
    vocab_size: int = 151_936
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 10_000_000.0
    mrope_section: Tuple[int, int, int] = (16, 24, 24)  # frequency pairs a position stream
    index_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    index_loss_weight: float = 1.0
    num_experts: int = 128
    experts_held: Tuple[int, int] = (0, 128)  # (first, count): this chip's share
    top_k: int = 8
    expert_hidden: int = 768
    norm_topk_prob: bool = True
    balance_loss_weight: float = 1e-3
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    blocks: Blocks = Blocks()  # the kernels' tiles (tests shrink them)


def indexed_sparse_debug(**over: Any) -> IndexedSparseMoEConfig:
    """Tiny widths, for tests."""
    return replace(
        IndexedSparseMoEConfig(
            vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
            mrope_section=(2, 2, 4), index_heads=2, index_head_dim=8, index_topk=16,
            num_experts=8, experts_held=(2, 4), top_k=2, expert_hidden=32, dtype=jnp.float32,
            blocks=Blocks(16, 16, 32, 16),
        ),
        **over,
    )


def text_positions(batch: int, seq: int) -> jax.Array:
    """The three streams of a text batch: the position, three times."""
    return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (3, batch, seq))


def _mrope(x: jax.Array, positions: jax.Array, sections: Tuple[int, ...], theta: float) -> jax.Array:
    """Rotary embedding of x [B, S, ..., R] on the pairs (i, i + R/2), the
    angle of pair ``i`` taken from the position stream whose section holds
    it; ``sections`` count pairs and sum to R/2; float32 arithmetic."""
    half = x.shape[-1] // 2
    if sum(sections) != half:
        raise ValueError(f"sections {sections} do not sum to {half} frequency pairs")
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    stream = np.repeat(np.arange(len(sections)), sections)  # [half]: whose position turns the pair
    angles = jnp.moveaxis(positions.astype(jnp.float32), 0, -1)[..., stream] * freqs  # [B, S, half]
    shape = angles.shape[:2] + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def _unit_rms(x: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)).astype(x.dtype)


class IndexedSparseMoE:
    def __init__(self, config: IndexedSparseMoEConfig, mesh: Optional[Any] = None) -> None:
        self.config = config
        self.mesh = mesh
        cfg = config
        if cfg.n_heads % cfg.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        scale = cfg.index_head_dim / cfg.head_dim
        # the index turns its smaller heads by the same three streams, each
        # section scaled to its width
        self.index_sections = tuple(int(s * scale) for s in cfg.mrope_section)
        self.moe = RoutedExperts(
            RoutedExpertsConfig(
                dim=cfg.dim, expert_hidden=cfg.expert_hidden, num_experts=cfg.num_experts,
                experts_held=tuple(cfg.experts_held), top_k=cfg.top_k, score_func="softmax",
                selection_bias=False, norm_topk_prob=cfg.norm_topk_prob,
                balance_loss_weight=cfg.balance_loss_weight, dtype=cfg.dtype,
            )
        )
        # set when the layers are traced: KERNEL_PATH or "plain: <why>"
        self.attention_path: Optional[str] = None

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------

    def _init_layer(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        D, H, KV, hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        J, DI = cfg.index_heads, cfg.index_head_dim
        keys = jax.random.split(key, 8)

        normal = functools.partial(decoder.seeded, dtype=cfg.dtype)

        return {
            "attn_norm": jnp.ones((D,), jnp.float32),
            "mlp_norm": jnp.ones((D,), jnp.float32),
            "attn": {
                "wq": normal(keys[0], (D, H * hd), D),
                "wk": normal(keys[1], (D, KV * hd), D),
                "wv": normal(keys[2], (D, KV * hd), D),
                # the residual stream's writer is scaled down by the depth
                # (GPT-2's 1 / sqrt(2 layers)): unscaled, what every token's
                # attention output has in common (the mean of the values it
                # read, itself fed by the common part of the layer before)
                # doubled in energy layer by layer, and by the sixth layer the
                # routers saw mostly that one vector (PERF.md section 6, PR 33)
                "wo": normal(keys[3], (H * hd, D), H * hd * 2 * cfg.n_layers),
                "q_norm": jnp.ones((hd,), jnp.float32),
                "k_norm": jnp.ones((hd,), jnp.float32),
            },
            "index": {
                "wq": normal(keys[4], (D, J * DI), D),
                "wk": normal(keys[5], (D, DI), D),
                "ww": normal(keys[6], (D, J), D),
            },
            "ffn": self.moe.init(keys[7]),
        }

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        k_embed, k_out, k_layers = jax.random.split(key, 3)
        # rows of unit variance, so that a token's own embedding leads
        # the residual stream it enters.  With rows of 1 / sqrt(dim) the
        # first layers' attention output, a near-uniform mean over 2,048
        # values and so nearly the SAME vector for every token, was 40 %
        # of what the first routers saw: every token then preferred the
        # same few experts (the busiest held expert at 12 times the
        # mean, PERF.md section 6, PR 33), and a step's work followed the seed
        embed, lm_head = decoder.embed_and_head(k_embed, k_out, cfg.vocab_size, cfg.dim, cfg.dtype)
        return {
            "embed": embed,
            "layers": jax.vmap(self._init_layer)(jax.random.split(k_layers, cfg.n_layers)),
            "final_norm": jnp.ones((cfg.dim,), jnp.float32),
            "lm_head": lm_head,
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

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------

    def _kernel_refusal(self, seq: int) -> Optional[str]:
        """Why the Mosaic kernels do NOT apply, or None when they do."""
        return decoder.one_chip_refusal(self.config.blocks.refusal(seq, self.config.head_dim), self.mesh)

    @part("mixer_glue")
    def _index(
        self, h: jax.Array, ix: Dict[str, Any], positions: jax.Array
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """The index's operands from the normalised hidden state, which it
        reads and sends nothing back into: (queries [B, S, J, DI], the one
        key head [B, S, DI], a weight a head [B, S, J] float32)."""
        cfg = self.config
        B, S, _ = h.shape
        J, DI = cfg.index_heads, cfg.index_head_dim
        hs = jax.lax.stop_gradient(h)
        rope = lambda x: _mrope(x, positions, self.index_sections, cfg.rope_theta)  # noqa: E731
        q_index = rope(decoder.proj(hs, ix["wq"]).reshape(B, S, J, DI))
        k_index = rope(_unit_rms(decoder.proj(hs, ix["wk"]), cfg.norm_eps))
        return q_index, k_index, decoder.proj(hs, ix["ww"]).astype(jnp.float32) * float((J * DI) ** -0.5)

    @part("mixer_glue")
    def _attention(
        self, h: jax.Array, w: Dict[str, Any], positions: jax.Array, kernels: bool
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """(the mixer's output [B, S, D], ``L_I`` a row, keys a query)."""
        cfg = self.config
        B, S, _ = h.shape
        H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        a = w["attn"]
        rope = lambda x: _mrope(x, positions, cfg.mrope_section, cfg.rope_theta)  # noqa: E731
        q = rope(decoder.rms_norm(decoder.proj(h, a["wq"]).reshape(B, S, H, hd), a["q_norm"], cfg.norm_eps))
        k = rope(decoder.rms_norm(decoder.proj(h, a["wk"]).reshape(B, S, KV, hd), a["k_norm"], cfg.norm_eps))
        v = decoder.proj(h, a["wv"]).reshape(B, S, KV, hd)
        q_index, k_index, weight = self._index(h, w["index"], positions)
        if kernels:
            interpret = decoder.assumed_backend() != "tpu"
            mask, lse_index, keys = select_keys(
                q_index, k_index, weight, topk=cfg.index_topk, blocks=cfg.blocks, interpret=interpret
            )
            # a rematerialised layer keeps the selection (named here) and what
            # ``indexed_attention`` makes of it (named there, ``KEPT_NAMES``)
            mask, lse_index, keys = (
                checkpoint_name(a, n) for a, n in zip((mask, lse_index, keys), SELECTION_NAMES)
            )
            o, kl = indexed_attention(
                q, k, v, q_index, k_index, weight, mask, lse_index, blocks=cfg.blocks, interpret=interpret
            )
        else:
            o, kl, keys = indexed_attention_plain(q, k, v, q_index, k_index, weight, topk=cfg.index_topk)
        return decoder.proj(o.reshape(B, S, H * hd), a["wo"]), kl / (B * S), jnp.mean(keys)

    def _normed(self, x: jax.Array, weight: jax.Array) -> jax.Array:
        """What a layer reads of the float32 residual stream: its RMS norm,
        in the matrices' dtype."""
        return decoder.rms_norm(x, weight, self.config.norm_eps).astype(self.config.dtype)

    def _block(
        self, x: jax.Array, w: Dict[str, Any], positions: jax.Array, kernels: bool
    ) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
        cfg = self.config
        with part("stream"):
            h = self._normed(x, w["attn_norm"])
        mixed, kl, keys = self._attention(h, w, positions, kernels)
        with part("stream"):
            x = x + mixed
            # the router reads the float32 norm itself: which 8 of 128 experts a
            # token takes is a step function of it
            h = decoder.rms_norm(x, w["mlp_norm"], cfg.norm_eps)
        out, load, balance = self.moe.apply(w["ffn"], h)
        with part("stream"):
            return x + out, (load, balance, kl, keys)

    def _trunk(self, params: Dict[str, Any], batch: Tuple[Any, ...]) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
        """batch → (the residual stream after the last layer, a layer's
        (loads [L, E], balance loss [L], ``L_I`` [L], keys a query [L]))."""
        cfg = self.config
        tokens = batch[0]
        B, S = tokens.shape
        positions = batch[2] if len(batch) > 2 else text_positions(B, S)
        refusal = self._kernel_refusal(S)
        kernels = refusal is None
        # the residual stream is float32 whatever the matrices' dtype
        # (Megatron's fp32_residual_connection): rows of unit scale take
        # twenty layer outputs of 0.03-0.08, and in bfloat16 each sum's
        # rounding, not the products', was the forward pass's distance from
        # the float32 reference (PERF.md section 6, PR 33)
        with part("embed"):
            x = params["embed"][tokens].astype(jnp.float32)
            if len(batch) > 3:
                embeds, given = batch[3], batch[4]
                x = jnp.where(given[..., None], embeds.astype(jnp.float32), x)

        # a rematerialised layer keeps the selection and what the kernels
        # make of it; ``prevent_cse`` off whatever the depth, the constant
        # this model has always passed
        x, per_layer = decoder.scan_run(
            lambda carry, w: self._block(carry, w, positions, kernels), x, params["layers"], cfg.n_layers,
            keep=(*SELECTION_NAMES, *KEPT_NAMES), prevent_cse=False,
        )
        decoder.kernel_path(self, KERNEL_PATH, refusal, self.moe.path)
        return x, per_layer

    @part("head")
    def _logits(self, params: Dict[str, Any], x: jax.Array) -> jax.Array:
        cfg = self.config
        return decoder.head_logits(x, params["final_norm"], params["lm_head"], cfg.norm_eps, cfg.dtype)

    def apply(self, params: Dict[str, Any], tokens: jax.Array, *more: Any) -> jax.Array:
        """tokens [B, S] (and what a batch may hold after its targets) →
        logits [B, S, vocab] (fp32)."""
        x, _ = self._trunk(params, (tokens, None, *more))
        return self._logits(params, x)

    def _losses(self, params: Dict[str, Any], batch: Tuple[Any, ...]) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
        x, per_layer = self._trunk(params, batch)
        return decoder.mean_nll(self._logits(params, x), batch[1]), per_layer

    def loss(self, params: Dict[str, Any], batch: Tuple[Any, ...]) -> jax.Array:
        """Mean next-token cross-entropy."""
        return self._losses(params, batch)[0]

    def objective(
        self, params: Dict[str, Any], batch: Tuple[Any, ...]
    ) -> Tuple[jax.Array, Tuple[List[jax.Array], jax.Array]]:
        """What a training step differentiates (``loss``, the index's loss
        at its weight, the routers' balance loss), no signal (no leaf here
        is the optimizer's to leave alone) and the step's summary."""
        cfg = self.config
        loss, (load, balance, kl, keys) = self._losses(params, batch)
        with part("head"):
            total = loss + cfg.index_loss_weight * jnp.sum(kl) + jnp.sum(balance)
            return total, ([], self.step_summary(load, kl, keys, batch[0].size))

    def step_summary(self, load: jax.Array, kl: jax.Array, keys: jax.Array, tokens: int) -> jax.Array:
        """Of this replica's step of ``tokens`` tokens, on the device:
        ``[layers, 6]`` in the order of ``SUMMARY_FIELDS`` (``buffer_rows``:
        the rows of the experts' buffer a layer's pairs went through,
        ``RoutedExperts.buffer_rows``)."""
        first, held = self.config.experts_held
        here = load[:, first : first + held]
        rows = here.sum(axis=1)
        return jnp.stack(
            [rows, here.max(axis=1), here.mean(axis=1), kl, keys, self.moe.buffer_rows(tokens, rows)], axis=1
        )

    # :meth:`step_summary` on the host, as the flight event's detail
    summary_stats = staticmethod(functools.partial(moe.summary_stats, fields=SUMMARY_FIELDS))
