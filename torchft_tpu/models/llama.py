"""Llama-3-family transformer, TPU-first.

The flagship model for torchft_tpu's fault-tolerant training (the reference
trains Llama 3 8B/70B through torchtitan HSDP, ``README.md:62-69``; here the
model is in-repo because the framework is standalone).

Design choices for the TPU/XLA compilation model:

- **Pure functional**: params are a pytree dict; ``apply`` is a pure
  function — jit/pjit/shard_map compose without a module system.
- **Stacked layers + ``lax.scan``**: per-layer weights carry a leading
  ``n_layers`` dim and the decoder runs as one scanned block, so compile
  time is O(1) in depth and XLA pipelines the layer loop.
- **bf16 matmuls on the MXU**: params and activations default to bfloat16
  with fp32 RMSNorm statistics and fp32 logits for the loss.
- **Sharding as data**: :func:`param_specs` returns a PartitionSpec pytree
  matching ``init`` — megatron TP on the head/ffn dims, FSDP on the
  complementary dim, so HSDP = shard_pytree(params, param_specs(...), mesh).
- **Sequence parallelism**: with ``sp > 1`` attention switches to ring
  attention (``torchft_tpu.parallel.ring_attention``) over the ``sp`` axis.
"""

from __future__ import annotations

import functools
import logging
import os
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from torchft_tpu.models import decoder
from torchft_tpu.obs.spans import part

logger = logging.getLogger(__name__)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_hidden: int = 14_336
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    # sequence parallelism: ring attention over this mesh axis when set
    sp_axis: Optional[str] = None
    # rematerialization: recompute activations in the backward pass (the
    # reference leans on torch's activation checkpointing via torchtitan
    # for the same reason).  ``remat=True`` is per-layer ("layer" mode);
    # ``remat_mode`` selects the policy explicitly:
    #   - "none":  save everything (fastest; biggest activation HBM)
    #   - "attn":  recompute only the attention half — attention is the
    #     cheap-to-recompute minority of a layer's FLOPs (~10% extra
    #     hardware work) while its qkv/out tensors are a meaningful bite
    #     of saved bytes; the FFN's big gate/up intermediates stay saved.
    #     The best MFU of the remat modes when it fits.
    #   - "ffn":   recompute only the FFN half — frees the majority of
    #     saved bytes (gate/up, 2×ffn_hidden wide) at ~26% extra hardware
    #     FLOPs
    #   - "layer": recompute whole layers, saving only the [B,S,dim]
    #     layer-boundary residuals — O(layers) less activation HBM (~33%
    #     extra FLOPs); what lets a ~1B-param config train on one chip
    remat: bool = False
    remat_mode: Optional[str] = None  # None → "layer" if remat else "none"

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def effective_remat_mode(self) -> str:
        mode = self.remat_mode or ("layer" if self.remat else "none")
        if mode not in ("none", "attn", "ffn", "layer"):
            raise ValueError(f"unknown remat_mode {mode!r}")
        return mode


def llama3_8b() -> LlamaConfig:
    return LlamaConfig()


def llama3_70b() -> LlamaConfig:
    return LlamaConfig(
        dim=8192, n_layers=80, n_heads=64, n_kv_heads=8, ffn_hidden=28_672
    )


def llama_debug(sp_axis: Optional[str] = None) -> LlamaConfig:
    """Tiny config for tests/dryruns."""
    return LlamaConfig(
        vocab_size=512,
        dim=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        ffn_hidden=128,
        max_seq_len=256,
        dtype=jnp.float32,
        sp_axis=sp_axis,
    )


# presets by name, for entry points that take the model as an argument
CONFIGS = {
    "llama_debug": llama_debug,
    "llama3_8b": llama3_8b,
    "llama3_70b": llama3_70b,
}


class Llama:
    def __init__(self, config: LlamaConfig, mesh: Optional[Any] = None) -> None:
        """``mesh`` is required when ``config.sp_axis`` is set: the ring
        attention shard_map needs the concrete mesh object."""
        self.config = config
        self.mesh = mesh
        # set when attention is traced: "flash", "ring" or "naive: <why>"
        self.attention_path: Optional[str] = None

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------

    def init(self, key: jax.Array, include_ffn: bool = True) -> Dict[str, Any]:
        """``include_ffn=False`` skips the dense FFN stacks (subclasses with
        their own FFN, e.g. MoE, must never materialize them)."""
        cfg = self.config
        k_embed, k_layers, k_out = jax.random.split(key, 3)

        _norm = functools.partial(decoder.seeded, dtype=cfg.dtype)

        hd = cfg.head_dim
        L = cfg.n_layers
        keys = jax.random.split(k_layers, 7)
        layers = {
            "wq": _norm(keys[0], (L, cfg.dim, cfg.n_heads * hd), cfg.dim),
            "wk": _norm(keys[1], (L, cfg.dim, cfg.n_kv_heads * hd), cfg.dim),
            "wv": _norm(keys[2], (L, cfg.dim, cfg.n_kv_heads * hd), cfg.dim),
            "wo": _norm(keys[3], (L, cfg.n_heads * hd, cfg.dim), cfg.n_heads * hd),
            "attn_norm": jnp.ones((L, cfg.dim), dtype=jnp.float32),
            "mlp_norm": jnp.ones((L, cfg.dim), dtype=jnp.float32),
        }
        if include_ffn:
            layers.update(
                {
                    "w_gate": _norm(keys[4], (L, cfg.dim, cfg.ffn_hidden), cfg.dim),
                    "w_up": _norm(keys[5], (L, cfg.dim, cfg.ffn_hidden), cfg.dim),
                    "w_down": _norm(
                        keys[6], (L, cfg.ffn_hidden, cfg.dim), cfg.ffn_hidden
                    ),
                }
            )
        return {
            "embed": _norm(k_embed, (cfg.vocab_size, cfg.dim), cfg.dim),
            "layers": layers,
            "final_norm": jnp.ones(cfg.dim, dtype=jnp.float32),
            "lm_head": _norm(k_out, (cfg.dim, cfg.vocab_size), cfg.dim),
        }

    def param_specs(self) -> Dict[str, Any]:
        """PartitionSpecs matching :meth:`init`.

        Megatron layout: column-parallel (out dim on ``tp``) for wq/wk/wv and
        gate/up, row-parallel (in dim on ``tp``) for wo/w_down; ``fsdp``
        shards the complementary dim.  Embeddings shard vocab on ``tp``.
        Layer-stacked arrays keep the leading layer dim replicated.
        """
        return {
            "embed": P("tp", "fsdp"),
            "layers": {
                "wq": P(None, "fsdp", "tp"),
                "wk": P(None, "fsdp", "tp"),
                "wv": P(None, "fsdp", "tp"),
                "wo": P(None, "tp", "fsdp"),
                "w_gate": P(None, "fsdp", "tp"),
                "w_up": P(None, "fsdp", "tp"),
                "w_down": P(None, "tp", "fsdp"),
                "attn_norm": P(None, None),
                "mlp_norm": P(None, None),
            },
            "final_norm": P(None),
            "lm_head": P("fsdp", "tp"),
        }

    def batch_specs(self) -> Tuple[Any, Any]:
        """(tokens, targets) PartitionSpecs: batch over (dp, fsdp), sequence
        over sp.  FSDP *is* data parallelism (ZeRO): each fsdp shard must
        process its own batch slice — batch over dp alone would replicate
        activations across the fsdp axis and blow HBM at scale (caught by
        ``parallel/rehearsal.py``: 8B at seq 8192 on a dp=1×fsdp=8 group
        costs ~66 GB/chip of activations replicated vs ~8 GB sharded)."""
        spec = (
            P(("dp", "fsdp"), "sp")
            if self.config.sp_axis
            else P(("dp", "fsdp"), None)
        )
        return spec, spec

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------

    # what this model shares with every decoder (``models/decoder.py``),
    # under the names its own program and its tests call
    _rms_norm = staticmethod(decoder.rms_norm)
    _apply_rope = staticmethod(decoder.apply_rope)
    _assumed_backend = staticmethod(decoder.assumed_backend)
    _flash_blocks = staticmethod(decoder.flash_blocks)

    def _rope(self, positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
        cfg = self.config
        half = cfg.head_dim // 2
        freqs = 1.0 / (
            cfg.rope_theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
        )
        angles = positions[:, :, None].astype(jnp.float32) * freqs  # [B,S,half]
        return jnp.cos(angles), jnp.sin(angles)

    def _flash_refusal(self, seq: int) -> Optional[str]:
        """Why the fused Pallas kernel (``ops/flash_attention.py``) does NOT
        apply at this sequence length, or None when it does: TPU backend (or
        forced), flash-friendly shapes, no ring attention.  ``TORCHFT_FLASH``
        = 1 forces on (interpret mode off TPU), 0 kills it, unset = auto."""
        cfg = self.config
        if cfg.sp_axis is not None:
            return f"sp_axis={cfg.sp_axis!r} routes to ring attention"
        env = os.environ.get("TORCHFT_FLASH", "")
        if env == "0":
            return "TORCHFT_FLASH=0"
        # seq % 8: Mosaic requires 8-divisible sublane dims — a 130-long seq
        # in [128, 512) would otherwise pick block_q=seq and fail to lower.
        # the divisibility gate uses the RESOLVED block sizes, so an env
        # override that doesn't divide seq takes the naive path instead of
        # crashing the trace
        block_q, block_k = self._flash_blocks(seq)
        if seq < 128 or seq % 8 or seq % block_q or seq % block_k:
            return (
                f"seq={seq} is under 128 or not divisible by 8 and the "
                f"blocks ({block_q}, {block_k})"
            )
        if getattr(self, "_disable_flash", False):
            return "disabled by the pipeline wrapper"
        if env == "1":
            return None
        # auto: single-device programs use the bare kernel; multi-device
        # needs a mesh for the shard_map variant (a bare pallas_call is not
        # SPMD-partitionable — inside a tp/fsdp-sharded jit it would force
        # operand replication)
        backend = self._assumed_backend()
        if backend != "tpu":
            return f"backend is {backend}, not tpu"
        if jax.device_count() > 1 and self._flash_mesh() is None:
            return (
                f"{jax.device_count()} devices and no (dp, tp) mesh on the "
                "model for the shard_map variant"
            )
        return None

    def _use_flash(self, seq: int) -> bool:
        return self._flash_refusal(seq) is None

    def _record_attention_path(self, path: str) -> None:
        if path != self.attention_path:
            logger.info("attention path: %s", path)
        self.attention_path = path

    def _flash_mesh(self) -> Optional[Any]:
        """The mesh for ``flash_attention_sharded``, if attention under it
        is purely (batch, head)-parallel: dp/tp axes present, sp/ep/pp all
        size 1 (those paths carry their own attention plumbing)."""
        mesh = self.mesh
        if mesh is None or "dp" not in mesh.shape or "tp" not in mesh.shape:
            return None
        for axis in ("sp", "ep", "pp"):
            if mesh.shape.get(axis, 1) != 1:
                return None
        return mesh

    def _attention(
        self,
        q: jax.Array,
        k: jax.Array,
        v: jax.Array,
        positions: jax.Array,
    ) -> jax.Array:
        """Causal GQA attention. q: [B,S,H,D], k/v: [B,S,KV,D]."""
        cfg = self.config

        # trace-time record of the path taken ("flash", or "naive: <why>"):
        # a silent naive path costs the [B, H, S, S] score matrix in HBM
        refusal = self._flash_refusal(q.shape[1])
        if refusal is None:
            from torchft_tpu.ops.flash_attention import (
                flash_attention,
                flash_attention_sharded,
            )

            interpret = self._assumed_backend() != "tpu"
            mesh = self._flash_mesh()
            B, _, H, _ = q.shape
            block_q, block_k = self._flash_blocks(q.shape[1])
            mesh_size = (
                1 if mesh is None
                else int(np.prod(list(mesh.shape.values())))
            )
            if mesh_size == 1:
                # bare kernel: single-device programs, or forced via env
                # without a mesh (then operands replicate — caller's call)
                self._record_attention_path("flash")
                return flash_attention(
                    q, k, v, causal=True, interpret=interpret,
                    block_q=block_q, block_k=block_k,
                )
            bp = mesh.shape["dp"] * mesh.shape.get("fsdp", 1)
            tp = mesh.shape["tp"]
            if (
                B % bp == 0  # batch shards over (dp, fsdp)
                and H % tp == 0
                and cfg.n_kv_heads % tp == 0
            ):
                self._record_attention_path("flash")
                return flash_attention_sharded(
                    q, k, v, mesh=mesh, causal=True, interpret=interpret,
                    block_q=block_q, block_k=block_k,
                )
            refusal = (
                f"batch={B} heads={H}/{cfg.n_kv_heads} do not divide the "
                f"mesh (dp*fsdp={bp}, tp={tp})"
            )
        self._record_attention_path(
            "ring" if cfg.sp_axis is not None else f"naive: {refusal}"
        )

        if cfg.sp_axis is not None:
            # the ring ships GQA K/V un-repeated (group-factor fewer
            # ppermute bytes); the body broadcasts at compute time
            from torchft_tpu.parallel.ring_attention import (
                ring_attention,
                ring_attention_sharded,
            )

            if getattr(self, "_in_manual_sp", False):
                # already inside a manual region over sp (the pp × sp
                # pipeline): use the raw collective form
                return ring_attention(q, k, v, cfg.sp_axis)
            assert self.mesh is not None, "sp requires a mesh on the model"
            return ring_attention_sharded(
                q, k, v, mesh=self.mesh, sp_axis=cfg.sp_axis
            )

        groups = cfg.n_heads // cfg.n_kv_heads
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)

        scale = 1.0 / np.sqrt(cfg.head_dim)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        seq = q.shape[1]
        causal = jnp.tril(jnp.ones((seq, seq), dtype=bool))
        scores = jnp.where(causal[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    def _attn_block(
        self, x: jax.Array, layer_params: Dict[str, jax.Array], rope, positions
    ) -> jax.Array:
        """Pre-norm RoPE/GQA attention + residual — shared by dense and MoE
        variants (the FFN half is the pluggable part)."""
        cfg = self.config
        cos, sin = rope
        B, S, _ = x.shape
        hd = cfg.head_dim
        # the parts of the compiled step (``obs/spans.py``): the innermost
        # scope on an operation's path says which part made it
        with part("stream"):
            h = self._rms_norm(x, layer_params["attn_norm"], cfg.norm_eps)
        with part("mixer_proj"):
            q = (h @ layer_params["wq"]).reshape(B, S, cfg.n_heads, hd)
            k = (h @ layer_params["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
            v = (h @ layer_params["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
        with part("mixer_glue"):
            q = self._apply_rope(q, cos, sin)
            k = self._apply_rope(k, cos, sin)
            attn = self._attention(q, k, v, positions)
        with part("mixer_proj"):
            out = attn.reshape(B, S, cfg.n_heads * hd) @ layer_params["wo"]
        with part("stream"):
            return x + out

    def _ffn_block(
        self, x: jax.Array, layer_params: Dict[str, jax.Array]
    ) -> jax.Array:
        cfg = self.config
        with part("stream"):
            h = self._rms_norm(x, layer_params["mlp_norm"], cfg.norm_eps)
        with part("ffn"):
            gate = jax.nn.silu(h @ layer_params["w_gate"])
            up = h @ layer_params["w_up"]
            out = (gate * up) @ layer_params["w_down"]
        with part("stream"):
            return x + out

    def _layer(
        self, x: jax.Array, layer_params: Dict[str, jax.Array], rope, positions
    ) -> jax.Array:
        mode = self.config.effective_remat_mode
        attn = self._attn_block
        ffn = self._ffn_block
        ckpt = functools.partial(
            jax.checkpoint,
            policy=jax.checkpoint_policies.nothing_saveable,
            prevent_cse=False,
        )
        if mode == "attn":
            attn = ckpt(attn)
        elif mode == "ffn":
            ffn = ckpt(ffn)
        x = attn(x, layer_params, rope, positions)
        return ffn(x, layer_params)

    def apply(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """tokens [B, S] → logits [B, S, vocab] (fp32)."""
        cfg = self.config
        B, S = tokens.shape
        with part("embed"):
            x = params["embed"][tokens].astype(cfg.dtype)

        # Shapes under jit are GLOBAL even when the sequence dim is sharded
        # over sp — only the ring-attention shard_map body sees local blocks.
        with part("mixer_glue"):
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
            rope = self._rope(positions)

        def scan_body(carry, layer_params):
            return self._layer(carry, layer_params, rope, positions), None

        # "layer" mode keeps only the residual stream at layer boundaries and
        # each layer recomputes in the backward pass; prevent_cse is
        # unnecessary under lax.scan (per jax docs) and its optimization
        # barriers cost step time: off whatever the depth
        keep = () if cfg.effective_remat_mode == "layer" else None
        x, _ = decoder.scan_run(scan_body, x, params["layers"], cfg.n_layers, keep=keep, prevent_cse=False)
        with part("head"):
            x = self._rms_norm(x, params["final_norm"], cfg.norm_eps)
            return (x @ params["lm_head"]).astype(jnp.float32)

    def loss(
        self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]
    ) -> jax.Array:
        """Mean next-token cross-entropy; batch = (tokens, targets)."""
        tokens, targets = batch
        logits = self.apply(params, tokens)
        return decoder.mean_nll(logits, targets)

    def _attn_params_per_layer(self) -> int:
        cfg = self.config
        hd = cfg.head_dim
        return (
            cfg.dim * cfg.n_heads * hd  # wq
            + 2 * cfg.dim * cfg.n_kv_heads * hd  # wk, wv
            + cfg.n_heads * hd * cfg.dim  # wo
            + 2 * cfg.dim  # norms
        )

    def _embed_params(self) -> int:
        cfg = self.config
        return cfg.vocab_size * cfg.dim * 2 + cfg.dim  # embed + lm_head + final norm

    def num_params(self) -> int:
        cfg = self.config
        per_layer = self._attn_params_per_layer() + 3 * cfg.dim * cfg.ffn_hidden
        return self._embed_params() + cfg.n_layers * per_layer
