"""Expert parallelism: a Mixture-of-Experts block sharded over an ``ep`` axis.

Net-new relative to the reference (torchft has no expert parallelism,
SURVEY.md §2.3) but part of torchft_tpu's first-class parallelism surface:
experts are sharded over a mesh axis and tokens route to their expert via
``lax.all_to_all`` over ICI — the TPU-native analog of NCCL alltoall MoE
dispatch.

Design (compiler-friendly, static shapes):

- top-1 switch routing with a fixed per-expert **capacity**; overflow tokens
  pass through the residual (standard Switch-Transformer form — no dynamic
  shapes inside jit).
- dispatch/combine are einsums against a one-hot dispatch mask, so the MXU
  does the data movement math and XLA lays out the ``all_to_all`` over the
  ``ep`` axis.
- runs inside ``shard_map`` over ``ep`` (experts local to each shard); the
  dense reference path (no mesh) computes identical math for testing.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P



@dataclass(frozen=True)
class MoEConfig:
    dim: int
    ffn_hidden: int
    num_experts: int
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32  # expert weights/compute (bf16 for MXU models)


class MoE:
    """Top-1 switch MoE layer with optional expert parallelism."""

    def __init__(self, config: MoEConfig, mesh: Optional[Mesh] = None, ep_axis: str = "ep") -> None:
        self.config = config
        self.mesh = mesh
        self.ep_axis = ep_axis

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        k_router, k_up, k_down = jax.random.split(key, 3)
        scale_in = 1.0 / np.sqrt(cfg.dim)
        scale_hidden = 1.0 / np.sqrt(cfg.ffn_hidden)
        return {
            # router stays fp32: routing logits are precision-sensitive
            "router": jax.random.normal(k_router, (cfg.dim, cfg.num_experts))
            * scale_in,
            "w_up": (
                jax.random.normal(k_up, (cfg.num_experts, cfg.dim, cfg.ffn_hidden))
                * scale_in
            ).astype(cfg.dtype),
            "w_down": (
                jax.random.normal(k_down, (cfg.num_experts, cfg.ffn_hidden, cfg.dim))
                * scale_hidden
            ).astype(cfg.dtype),
        }

    def param_specs(self) -> Dict[str, Any]:
        """Experts sharded over ``ep`` (leading expert dim); router replicated."""
        return {
            "router": P(None, None),
            "w_up": P(self.ep_axis, None, None),
            "w_down": P(self.ep_axis, None, None),
        }

    # ------------------------------------------------------------------

    def _route(
        self, params: Dict[str, Any], x: jax.Array, capacity: int
    ) -> Tuple[jax.Array, jax.Array]:
        """x [T, D] → (dispatch [E, C, T] one-hot-ish, combine [E, C, T])."""
        cfg = self.config
        logits = x.astype(jnp.float32) @ params["router"]  # [T, E] fp32
        probs = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(probs, axis=-1)  # [T]
        gate = jnp.max(probs, axis=-1)  # [T]

        # position of each token within its expert's capacity buffer
        onehot = jax.nn.one_hot(expert, cfg.num_experts, dtype=jnp.int32)  # [T, E]
        position = jnp.cumsum(onehot, axis=0) * onehot  # 1-based [T, E]
        pos_in_expert = jnp.sum(position, axis=-1) - 1  # [T]
        keep = pos_in_expert < capacity

        dispatch = (
            jax.nn.one_hot(expert, cfg.num_experts, dtype=x.dtype)[:, :, None]
            * jax.nn.one_hot(
                jnp.where(keep, pos_in_expert, capacity), capacity + 1, dtype=x.dtype
            )[:, None, :capacity]
        )  # [T, E, C]
        dispatch = dispatch.transpose(1, 2, 0)  # [E, C, T]
        combine = dispatch * gate[None, None, :]
        return dispatch, combine

    def _expert_ffn(self, w_up: jax.Array, w_down: jax.Array, x: jax.Array) -> jax.Array:
        """x [E, C, D] with per-expert weights [E, D, F] / [E, F, D]."""
        h = jax.nn.relu(jnp.einsum("ecd,edf->ecf", x, w_up))
        return jnp.einsum("ecf,efd->ecd", h, w_down)

    def _apply_dense(self, params: Dict[str, Any], x: jax.Array) -> jax.Array:
        """Reference path: all experts local. x [T, D] → [T, D]."""
        cfg = self.config
        T = x.shape[0]
        capacity = max(1, int(cfg.capacity_factor * T / cfg.num_experts))
        dispatch, combine = self._route(params, x, capacity)
        expert_in = jnp.einsum("ect,td->ecd", dispatch, x)
        expert_out = self._expert_ffn(params["w_up"], params["w_down"], expert_in)
        return jnp.einsum("ect,ecd->td", combine, expert_out)

    def _apply_ep_local(self, params: Dict[str, Any], x: jax.Array) -> jax.Array:
        """shard_map body over ep: x is this shard's token block [T_loc, D];
        w_up/w_down hold the shard's local experts [E_loc, ...]."""
        cfg = self.config
        axis = self.ep_axis
        n = jax.lax.psum(1, axis)
        T_loc = x.shape[0]
        e_loc = params["w_up"].shape[0]
        capacity = max(1, int(cfg.capacity_factor * T_loc / cfg.num_experts))

        dispatch, combine = self._route(params, x, capacity)  # [E, C, T_loc]
        expert_in = jnp.einsum("ect,td->ecd", dispatch, x)  # [E, C, D]

        # ship each expert-shard's token buffers to its owner: [E, C, D] →
        # regroup E = n * e_loc (experts are contiguous per shard) →
        # all_to_all over the ep axis
        expert_in = expert_in.reshape(n, e_loc, capacity, cfg.dim)
        routed = jax.lax.all_to_all(
            expert_in, axis, split_axis=0, concat_axis=0, tiled=False
        )  # [n_src, e_loc, C, D]: every shard's tokens for our local experts
        routed = routed.transpose(1, 0, 2, 3).reshape(
            e_loc, n * capacity, cfg.dim
        )

        out = self._expert_ffn(params["w_up"], params["w_down"], routed)

        # send results back to the token owners (all_to_all is self-inverse)
        out = out.reshape(e_loc, n, capacity, cfg.dim).transpose(1, 0, 2, 3)
        returned = jax.lax.all_to_all(
            out, axis, split_axis=0, concat_axis=0, tiled=False
        ).reshape(n * e_loc, capacity, cfg.dim)  # [E, C, D] back home
        return jnp.einsum("ect,ecd->td", combine, returned)

    def apply(self, params: Dict[str, Any], x: jax.Array) -> jax.Array:
        """x [B, S, D] → [B, S, D] (residual added by the caller)."""
        B, S, D = x.shape
        flat = x.reshape(B * S, D)
        if self.mesh is None:
            out = self._apply_dense(params, flat)
        else:
            fn = jax.shard_map(
                partial(self._apply_ep_local),
                mesh=self.mesh,
                in_specs=(
                    self.param_specs(),
                    P(self.ep_axis, None),  # tokens sharded over ep
                ),
                out_specs=P(self.ep_axis, None),
                check_vma=False,
            )
            out = fn(params, flat)
        return out.reshape(B, S, D)
