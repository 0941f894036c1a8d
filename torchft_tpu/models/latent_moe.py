"""A decoder whose EVERY layer mixes with latent attention (a query latent
beside the key-value latent), a dense layer first and routed experts after,
with a multi-token-prediction module in what a step differentiates; for
training on one chip's share.

The published configuration this was built for is JoyAI-LLM-Flash's
(``model_type`` ``joyai_llm_flash``, DeepSeek-V3's block at hidden 2,048): a
layer is

- the mixer (``models/latent.py`` ``LatentAttention``, the definition
  ``LingHybrid``'s MLA layers run too): ``c_q = RMSNorm(h W_qa)``, ``q = c_q
  W_qb`` a head of ``qk_nope + qk_rope``; ``[c_kv, k_r] = h W_kva``, ``[k_nope,
  v] = RMSNorm(c_kv) W_kvb``; interleaved rope on q's rotary channels and on
  the ONE ``k_r`` every head shares; causal softmax at ``1 / sqrt(qk_nope +
  qk_rope)``; ``W_o``.  No gate;
- the feed-forward part: below ``first_k_dense`` a SwiGLU of ``dense_hidden``,
  else ``parallel/moe.py`` ``RoutedExperts``, told which experts are here
  (sigmoid scores in float32 over all of them, one group, a selection bias,
  the ``top_k`` best, the chosen scores normalised and scaled, one shared
  expert).

Pre-norm, two RMSNorms a layer, a final norm, then the head; embedding and
head are not tied.  The residual stream is float32 from the embedding to the
last norm whatever the matrices' dtype, and a router reads its float32 norm
(PERF.md section 6, PR 33).

**Multi-token prediction** (``models/latent.py`` ``mtp_token_nll``, depth 1):
the trunk's last hidden state beside the next token's embedding, one expert
layer of this model's own, a final norm of its own, the shared embedding and
head, the token after next.  The last position has no token after next and is
left out of the mean, not wrapped.

``loss`` is the next-token cross-entropy ALONE: it is what the mean of
``apply``'s cross-entropy is, and the benchmark ties the two
(``ftbench/harness.py`` ``forward_passes``).  ``objective``, what a training
step differentiates, is ``loss + mtp_loss_weight * mtp + balance`` (the
routers' sequence-wise balance loss, the module's router among them).

**State the optimizer does not own**: every router's selection bias, the
module's too, moved after a committed step by ``parallel/moe.py``'s rule
(``state_mask``, ``objective``, ``advance_state``; ``HSDPTrainer``).  The
step's summary is ``RoutedExperts.route_summary``'s rows, one an expert layer (the module's
last), and then ONE number more, ``mtp_nll``: the module's mean loss of the
step, which ``summary_stats`` hands the MOE_ROUTE flight event beside the
routing fields.

Contiguous layers of one kind are stacked and run under one ``lax.scan``.  A
layer is rematerialised in the backward pass but for its float32 input and
what flash made (``ops/flash_attention.py`` ``KEPT_NAMES``: ``o`` and ONE
float32 a row), in the stacked run and in the two single layers (the dense
layer, the module's) alike, so that ``flash_fwd``, 43.5 ms a layer at 16,384
positions, stands once in all eight: 136 MB a layer, and the gradient step
reads 11.0 GB by the compiler's count for a DESCRIBED v5e, under the 16 GB
the benchmark's compile test allows a step (PERF.md section 6, PR 62).
Each head's logits are made again in the backward pass and never kept
(1.06 GB of float32 a head at 16,384 positions over 16,160 rows).  ``attention_path`` is
``"flash"`` only if every layer took the flash kernels and every expert layer
the grouped kernel; off the TPU the plain path, named ``"plain: <why>"``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.models import decoder
from torchft_tpu.models.latent import LatentAttention, mtp_init, mtp_token_nll
from torchft_tpu.obs.spans import part
from torchft_tpu.ops import flash_attention as flash
from torchft_tpu.parallel import moe
from torchft_tpu.parallel.moe import RoutedExperts, RoutedExpertsConfig, swiglu

KERNEL_PATH = "flash"


@dataclass(frozen=True)
class LatentMoEConfig:
    vocab_size: int = 129_280
    dim: int = 2048
    n_layers: int = 40
    n_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32_000_000.0
    first_k_dense: int = 1
    dense_hidden: int = 7168
    num_experts: int = 256
    experts_held: Tuple[int, int] = (0, 256)  # (first, count): this chip's share
    top_k: int = 8
    expert_hidden: int = 768
    shared_hidden: int = 768
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    n_mtp: int = 1  # 0: no module; 1: DeepSeek-V3's, depth 1
    mtp_loss_weight: float = 0.1
    bias_update_rate: float = 1e-3
    balance_loss_weight: float = 1e-4
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def groups(self) -> List[Tuple[str, int]]:
        """Runs of contiguous layers of one feed-forward kind: (``"dense"``
        or ``"moe"``, how many)."""
        return decoder.runs("dense" if i < self.first_k_dense else "moe" for i in range(self.n_layers))


def latent_moe_debug(**over: Any) -> LatentMoEConfig:
    """Tiny widths, a dense layer and three of experts, the module on."""
    return replace(
        LatentMoEConfig(
            vocab_size=512, dim=64, n_layers=4, n_heads=2, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32, dense_hidden=128, num_experts=16,
            experts_held=(4, 4), top_k=4, expert_hidden=32, shared_hidden=32, dtype=jnp.float32,
        ),
        **over,
    )


class LatentMoE:
    def __init__(self, config: LatentMoEConfig, mesh: Optional[Any] = None) -> None:
        self.config = config
        self.mesh = mesh
        cfg = config
        if cfg.n_mtp not in (0, 1) or not 0 <= cfg.first_k_dense <= cfg.n_layers or cfg.qk_rope_head_dim % 2:
            raise ValueError("one prediction module at most, dense layers lead, rope turns pairs")
        self.groups = cfg.groups()
        self.mixer = LatentAttention(
            dim=cfg.dim, n_heads=cfg.n_heads, qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim, kv_lora_rank=cfg.kv_lora_rank,
            rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps, q_lora_rank=cfg.q_lora_rank,
            # the residual stream's writer is scaled down by the depth (GPT-2's
            # 1 / sqrt(2 layers), as ``IndexedSparseMoE``'s: what every token's
            # attention output has in common must not grow layer by layer
            # until the routers see mostly that, PERF.md section 6, PR 33)
            out_fan_scale=2.0 * cfg.n_layers, dtype=cfg.dtype,
        )
        self.moe = RoutedExperts(
            RoutedExpertsConfig(
                dim=cfg.dim, expert_hidden=cfg.expert_hidden, num_experts=cfg.num_experts,
                experts_held=tuple(cfg.experts_held), top_k=cfg.top_k,
                routed_scaling_factor=cfg.routed_scaling_factor, norm_topk_prob=cfg.norm_topk_prob,
                shared_hidden=cfg.shared_hidden, balance_loss_weight=cfg.balance_loss_weight, dtype=cfg.dtype,
            )
        )
        # set when the layers are traced: KERNEL_PATH or "plain: <why>"
        self.attention_path: Optional[str] = None

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------

    def _init_layer(self, kind: str, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        k_mixer, k_ffn = jax.random.split(key)
        if kind == "dense":
            ffn = decoder.dense_ffn_init(jax.random.split(k_ffn, 3), cfg.dim, cfg.dense_hidden, cfg.dtype)
        else:
            ffn = self.moe.init(k_ffn)
        return {
            "attn_norm": jnp.ones((cfg.dim,), jnp.float32),
            "mlp_norm": jnp.ones((cfg.dim,), jnp.float32),
            "mixer": self.mixer.init(jax.random.split(k_mixer, 6)),
            "ffn": ffn,
        }

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        k_embed, k_out, k_layers, k_mtp = jax.random.split(key, 4)
        embed, lm_head = decoder.embed_and_head(k_embed, k_out, cfg.vocab_size, cfg.dim, cfg.dtype)
        params = {
            "embed": embed,
            "groups": decoder.init_runs(self._init_layer, k_layers, self.groups),
            "final_norm": jnp.ones((cfg.dim,), jnp.float32),
            "lm_head": lm_head,
        }
        if cfg.n_mtp:
            k_proj, k_layer = jax.random.split(k_mtp)
            params["mtp"] = mtp_init(cfg.dim, k_proj, self._init_layer("moe", k_layer), cfg.dtype)
        return params

    @functools.cached_property
    def _shapes(self) -> Any:
        return decoder.shapes(self.init)

    def param_specs(self) -> Dict[str, Any]:
        return decoder.one_chip_param_specs(self._shapes)

    def batch_specs(self) -> Tuple[Any, Any]:
        return decoder.batch_specs()

    def num_params(self) -> int:
        return decoder.num_params(self._shapes)

    # the routers' selection biases, the module's among them: state the optimizer does not own (``parallel/moe.py``)
    def state_mask(self) -> Any:
        return moe.state_mask(self.param_specs())

    def advance_state(self, state: List[jax.Array], signal: List[jax.Array]) -> List[jax.Array]:
        return moe.advance_state(self.config.bias_update_rate, state, signal)

    def summary_stats(self, summary: np.ndarray) -> Dict[str, Any]:
        """``objective``'s summary on the host, as the flight event's detail:
        ``parallel/moe.py``'s fields, a list an expert layer, and with the module
        ``mtp_nll``, its mean loss of the step."""
        flat = np.asarray(summary, np.float64).reshape(-1)
        if not self.config.n_mtp:
            return moe.summary_stats(flat)
        return dict(moe.summary_stats(flat[:-1]), mtp_nll=float(flat[-1]))

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------

    def _kernel_refusal(self, seq: int) -> Optional[str]:
        """Why the Mosaic kernels do NOT apply, or None when they do."""
        return decoder.kernel_refusal(seq, self.mesh)

    def _normed(self, x: jax.Array, weight: jax.Array) -> jax.Array:
        """What a layer reads of the float32 residual stream: its RMS norm,
        in the matrices' dtype."""
        return decoder.rms_norm(x, weight, self.config.norm_eps).astype(self.config.dtype)

    def _block(
        self, x: jax.Array, w: Dict[str, Any], kind: str, kernels: bool
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """One residual block on the float32 stream: ``(x, load [E] (zeros
        for a dense layer), balance loss)``."""
        cfg = self.config
        with part("stream"):
            h = self._normed(x, w["attn_norm"])
        mixed = self.mixer.apply(h, w["mixer"], kernels)
        with part("stream"):
            x = x + mixed
            # a router reads the float32 norm itself: which 8 of 256 experts a
            # token takes is a step function of it
            h = decoder.rms_norm(x, w["mlp_norm"], cfg.norm_eps)
        if kind == "dense":
            f = w["ffn"]
            with part("stream"):
                h = h.astype(cfg.dtype)
            with part("ffn"):
                out = swiglu(h @ f["w_gate"], h @ f["w_up"], 0.0) @ f["w_down"]
            with part("stream"):
                return x + out, jnp.zeros((cfg.num_experts,), jnp.float32), jnp.zeros((), jnp.float32)
        out, load, balance = self.moe.apply(w["ffn"], h)
        with part("stream"):
            return x + out, load, balance

    def _trunk(
        self, params: Dict[str, Any], tokens: jax.Array
    ) -> Tuple[jax.Array, List[jax.Array], jax.Array, bool]:
        """tokens [B, S] → (the residual stream after the last layer, the
        loads [depth, E] of every stacked run of expert layers in the layers'
        order, the summed balance loss, whether the kernels ran)."""
        refusal = self._kernel_refusal(tokens.shape[1])
        kernels = refusal is None
        with part("embed"):
            x = params["embed"][tokens].astype(jnp.float32)  # the residual stream
        loads, balance = [], jnp.zeros((), jnp.float32)
        for (kind, depth), stacked in zip(self.groups, params["groups"]):

            def body(carry, w, kind=kind):
                y, load, bal = self._block(carry, w, kind, kernels)
                return y, (load, bal)

            # a layer is rematerialised from its float32 input and from what
            # flash made, whatever the run's depth
            x, (load, bal) = decoder.scan_run(body, x, stacked, depth, keep=flash.KEPT_NAMES)
            if kind == "moe":
                loads.append(load)
                with part("experts_route"):
                    balance = balance + jnp.sum(bal)
        kernels = decoder.kernel_path(self, KERNEL_PATH, refusal, self.moe.path)
        return x, loads, balance, kernels

    def _head(self, head: jax.Array, x: jax.Array, norm: jax.Array) -> jax.Array:
        """A norm and the head; the products' float32 sums as they are: a
        logit is never rounded to the model's dtype."""
        return decoder.head_logits(x, norm, head, self.config.norm_eps, self.config.dtype)

    def apply(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """tokens [B, S] → logits [B, S, vocab] (fp32)."""
        x = self._trunk(params, tokens)[0]
        with part("head"):
            return self._head(params["lm_head"], x, params["final_norm"])

    @functools.partial(jax.checkpoint, static_argnums=0, prevent_cse=False)
    def _token_nll(self, head: jax.Array, x: jax.Array, norm: jax.Array, labels: jax.Array) -> jax.Array:
        """The cross-entropy of ``labels`` at every position [B, S] through a
        norm and the head, under whatever part the caller stands in; the
        logits are made again in the backward pass, never kept."""
        return decoder.token_nll(self._head(head, x, norm), labels)

    def _module(
        self, params: Dict[str, Any], x: jax.Array, targets: jax.Array, kernels: bool
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """The prediction module on the trunk's last hidden state ``x``: (the
        cross-entropy of the token after next [B, S - 1], its router's load,
        its balance loss).  The last position has no token after next and is
        left out, not wrapped."""
        nll, load, balance = mtp_token_nll(
            params["mtp"], params["embed"], x, targets,
            layer=decoder.remat(lambda z, w: self._block(z, w, "moe", kernels), depth=1, keep=flash.KEPT_NAMES),
            head_nll=functools.partial(self._token_nll, params["lm_head"]),
            norm_eps=self.config.norm_eps, dtype=self.config.dtype,
        )
        with part("mtp"):
            return nll[:, :-1], load, balance

    def mtp_token_nll(self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]) -> jax.Array:
        """The module's cross-entropy of the token after next at positions
        ``0..S-2``, [B, S - 1]: what ``objective`` takes the mean of."""
        tokens, targets = batch
        x, _, _, kernels = self._trunk(params, tokens)
        return self._module(params, x, targets, kernels)[0]

    def loss(self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]) -> jax.Array:
        """Mean next-token cross-entropy, and nothing else: the mean of
        ``apply``'s cross-entropy; batch = (tokens, targets)."""
        tokens, targets = batch
        x = self._trunk(params, tokens)[0]
        with part("head"):
            return jnp.mean(self._token_nll(params["lm_head"], x, params["final_norm"], targets))

    def objective(
        self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]
    ) -> Tuple[jax.Array, Tuple[List[jax.Array], jax.Array]]:
        """What a training step differentiates (``loss``, the module's loss at
        its weight, the routers' balance loss), for every leaf of
        ``state_mask`` the step's signal (the tokens each expert was chosen
        by; the module's router last) and the step's summary
        (``RoutedExperts.route_summary`` of this replica's own signal, then the
        module's mean loss)."""
        cfg = self.config
        tokens, targets = batch
        x, signal, balance, kernels = self._trunk(params, tokens)
        with part("head"):
            total = jnp.mean(self._token_nll(params["lm_head"], x, params["final_norm"], targets)) + balance
        more = []
        if cfg.n_mtp:
            nll, load, bal = self._module(params, x, targets, kernels)
            with part("mtp"):
                mtp = jnp.mean(nll)
                total = total + cfg.mtp_loss_weight * mtp + bal
            signal, more = [*signal, load], [mtp.reshape(1)]
        with part("head"):
            # a model of dense layers alone has no router to sum up
            routes = self.moe.route_summary(signal, tokens.size) if signal else jnp.zeros((0, len(moe.ROUTE_FIELDS)))
            return total, (signal, jnp.concatenate([routes.reshape(-1), *more]).astype(jnp.float32))
