"""Llama with Mixture-of-Experts FFN layers (expert-parallel).

A Mixtral-style variant of :mod:`torchft_tpu.models.llama`: the dense SwiGLU
FFN in each block is replaced by a switch MoE
(:mod:`torchft_tpu.parallel.moe`), with experts sharded over the ``ep`` mesh
axis and token routing via ``lax.all_to_all``.  Attention/embeddings keep the
dense model's megatron TP layout.

Because expert weights carry a leading ``num_experts`` dim, layers are NOT
stacked under ``lax.scan`` here — the per-layer Python loop keeps each MoE
dispatch its own XLA op (scan would force identical routing shapes anyway;
MoE models are typically shallow-wide, so compile time stays acceptable).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from torchft_tpu.models.llama import Llama, LlamaConfig
from torchft_tpu.parallel.moe import MoE, MoEConfig


@dataclass(frozen=True)
class LlamaMoEConfig(LlamaConfig):
    num_experts: int = 8
    capacity_factor: float = 1.5
    ep_axis: str = "ep"


def llama_moe_debug(ep_axis: str = "ep") -> LlamaMoEConfig:
    return LlamaMoEConfig(
        vocab_size=512,
        dim=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        ffn_hidden=128,
        max_seq_len=256,
        dtype=jnp.float32,
        num_experts=4,
        capacity_factor=4.0,
        ep_axis=ep_axis,
    )


class LlamaMoE(Llama):
    """Llama backbone with per-layer expert-parallel MoE FFNs."""

    def __init__(self, config: LlamaMoEConfig, mesh: Optional[Any] = None) -> None:
        super().__init__(config, mesh=mesh)
        self.moe = MoE(
            MoEConfig(
                dim=config.dim,
                ffn_hidden=config.ffn_hidden,
                num_experts=config.num_experts,
                capacity_factor=config.capacity_factor,
                dtype=config.dtype,
            ),
            mesh=mesh,
            ep_axis=config.ep_axis,
        )

    # ------------------------------------------------------------------

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg: LlamaMoEConfig = self.config  # type: ignore[assignment]
        # include_ffn=False: the dense FFN stacks (the model's largest
        # allocations) are never materialized
        base = super().init(key, include_ffn=False)
        moe_keys = jax.random.split(jax.random.fold_in(key, 17), cfg.n_layers)
        base["moe_layers"] = [self.moe.init(k) for k in moe_keys]
        return base

    def param_specs(self) -> Dict[str, Any]:
        cfg: LlamaMoEConfig = self.config  # type: ignore[assignment]
        specs = super().param_specs()
        layers = specs["layers"]
        for name in ("w_gate", "w_up", "w_down"):
            del layers[name]
        specs["moe_layers"] = [self.moe.param_specs() for _ in range(cfg.n_layers)]
        return specs

    # ------------------------------------------------------------------

    def apply(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        cfg: LlamaMoEConfig = self.config  # type: ignore[assignment]
        B, S = tokens.shape
        x = params["embed"][tokens].astype(cfg.dtype)
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        rope = self._rope(positions)

        for layer in range(cfg.n_layers):
            lp = {k: v[layer] for k, v in params["layers"].items()}
            # the attention half is the dense model's (``_attn_block``, inherited); only the FFN differs
            x = self._attn_block(x, lp, rope, positions)
            h = self._rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            x = x + self.moe.apply(params["moe_layers"][layer], h).astype(cfg.dtype)

        x = self._rms_norm(x, params["final_norm"], cfg.norm_eps)
        return (x @ params["lm_head"]).astype(jnp.float32)

    def num_params(self) -> int:
        cfg: LlamaMoEConfig = self.config  # type: ignore[assignment]
        moe = (
            cfg.dim * cfg.num_experts  # router
            + cfg.num_experts * cfg.dim * cfg.ffn_hidden * 2  # up + down
        )
        return self._embed_params() + cfg.n_layers * (
            self._attn_params_per_layer() + moe
        )
