"""Ling-3.0-flash's block (``model_type`` ``bailing_hybrid``): layers of
three kinds from a declared pattern, for training on one chip's share.

Layer ``i`` mixes with latent attention (MLA) where ``(i + 1) %
layer_group_size == 0`` and with Kimi Delta Attention (KDA, a gated delta
rule with a decay for every channel) otherwise; the first ``first_k_dense``
layers have a dense SwiGLU, the others routed experts with one shared expert
(``parallel/moe.py``: ``RoutedExperts``, which is told which experts are
here).  The pattern is derived from those two numbers, never listed.
Contiguous layers of one kind are stacked and run under one ``lax.scan``, a
layer rematerialised in the backward pass.

What is the model's and what a kernel's: the projections, the short
convolution with its SiLU, the unit length of q and k, the bounded gate, the
per-head norm and the head-wise output gate are here, plain ``jax.numpy``;
the chunked delta rule is ``ops/kda.py``'s, the MLA mixer
``models/latent.py``'s with its scores ``ops/flash_attention.py``'s (q and k
heads of 192, v heads of 128), the experts' grouped products
``megablox.gmm``.  ``attention_path`` is
``"kda+flash"`` only if every KDA layer took the chunked kernels, every MLA
layer the flash kernels and every expert layer the grouped kernel; off the
TPU the same chunk algebra runs as plain ``jax.numpy`` beside plain
attention and ``lax.ragged_dot`` and the path is named ``"plain: <why>"``.

**State the optimizer does not own.**  Every router has a selection bias
that no gradient moves: after a committed step it goes up by
``bias_update_rate`` for an expert that saw fewer tokens than the mean and
down for one that saw more (DeepSeek-V3's balancing without an auxiliary
loss).  ``HSDPTrainer`` asks three things of a model with such state:
``state_mask()`` (which leaves), ``objective(params, batch)`` (the scalar it
differentiates, for every such leaf the step's signal, here the tokens each
expert was chosen by, and the step's summary for the flight recorder) and
``advance_state(state, signal)``.  The signal
rides the leaf's own slot of the gradient tree through the replica-dimension
average, so replicas stay bit-equal.

``loss`` is the next-token cross-entropy (and the multi-token-prediction
loss at its weight); ``objective`` adds the routers' sequence-wise balance
loss.  Multi-token prediction (``models/latent.py``, one module: norms of the
hidden state and of the next token's embedding, a projection of the two side
by side, one MLA expert layer, the shared head, the token after next) is
built for ``n_mtp`` > 0.  The MLA mixer is ``models/latent.py``'s too.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.models import decoder
from torchft_tpu.models.latent import LatentAttention, mtp_init, mtp_token_nll
from torchft_tpu.obs.spans import part
from torchft_tpu.parallel import moe
from torchft_tpu.parallel.moe import RoutedExperts, RoutedExpertsConfig, swiglu

KERNEL_PATH = "kda+flash"
KDA_CHUNK = 64  # tokens a chunk of the delta rule (ops/kda.py)


@dataclass(frozen=True)
class LingHybridConfig:
    vocab_size: int = 157_184
    dim: int = 2560
    n_layers: int = 42
    n_heads: int = 32
    head_dim: int = 128  # KDA's q, k and v heads
    first_k_dense: int = 2
    layer_group_size: int = 6
    dense_hidden: int = 6144
    expert_hidden: int = 768
    shared_hidden: int = 768
    num_experts: int = 512
    experts_held: Tuple[int, int] = (0, 512)  # (first, count): this chip's share
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6_000_000.0
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    norm_eps: float = 1e-6
    # a layer's SwiGLU clamp where its entry is not 0 (empty: none anywhere)
    expert_swiglu_limits: Tuple[float, ...] = ()
    shared_swiglu_limits: Tuple[float, ...] = ()
    n_mtp: int = 0
    mtp_loss_weight: float = 0.0
    bias_update_rate: float = 1e-3
    balance_loss_weight: float = 1e-4
    dtype: Any = jnp.bfloat16

    def kinds(self) -> List[Tuple[str, str, float, float]]:
        """(mixer, feed-forward, expert clamp, shared clamp) of every layer."""
        limit = lambda xs, i: float(xs[i]) if xs else 0.0  # noqa: E731
        return [
            (
                "mla" if (i + 1) % self.layer_group_size == 0 else "kda",
                "dense" if i < self.first_k_dense else "moe",
                limit(self.expert_swiglu_limits, i),
                limit(self.shared_swiglu_limits, i),
            )
            for i in range(self.n_layers)
        ]

    def groups(self) -> List[Tuple[Tuple[str, str, float, float], int]]:
        """Runs of contiguous layers of one kind: (kind, how many)."""
        return decoder.runs(self.kinds())


def ling_debug(**over: Any) -> LingHybridConfig:
    """Tiny widths in the published pattern, for tests."""
    return replace(
        LingHybridConfig(
            vocab_size=512, dim=64, n_layers=7, n_heads=2, head_dim=32, first_k_dense=1,
            dense_hidden=128, expert_hidden=32, shared_hidden=32, num_experts=16,
            experts_held=(4, 4), top_k=4, n_group=4, topk_group=2, kv_lora_rank=32,
            qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32, dtype=jnp.float32,
        ),
        **over,
    )


class LingHybrid:
    def __init__(self, config: LingHybridConfig, mesh: Optional[Any] = None) -> None:
        self.config = config
        self.mesh = mesh
        cfg = config
        self.moe = RoutedExperts(
            RoutedExpertsConfig(
                dim=cfg.dim, expert_hidden=cfg.expert_hidden, num_experts=cfg.num_experts,
                experts_held=tuple(cfg.experts_held), top_k=cfg.top_k, n_group=cfg.n_group,
                topk_group=cfg.topk_group, routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob, shared_hidden=cfg.shared_hidden,
                balance_loss_weight=cfg.balance_loss_weight, dtype=cfg.dtype,
            )
        )
        # the latent-attention layers' mixer (``models/latent.py``): no query
        # latent (``q_lora_rank`` null), the head-wise gate
        self.mla = LatentAttention(
            dim=cfg.dim, n_heads=cfg.n_heads, qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
            kv_lora_rank=cfg.kv_lora_rank, rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
            head_gate=True, dtype=cfg.dtype,
        )
        # set when the mixers are traced: KERNEL_PATH or "plain: <why>"
        self.attention_path: Optional[str] = None

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------

    def _init_mixer(self, kind: str, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        D, H = cfg.dim, cfg.n_heads
        keys = jax.random.split(key, 10)

        normal = functools.partial(decoder.seeded, dtype=cfg.dtype)

        if kind == "mla":
            return self.mla.init(keys)
        inner = H * cfg.head_dim
        K = cfg.conv_kernel
        return {
            "wq": normal(keys[0], (D, inner), D),
            "wk": normal(keys[1], (D, inner), D),
            "wv": normal(keys[2], (D, inner), D),
            "w_g": normal(keys[3], (D, inner), D),  # full rank: no_kda_lora
            "w_beta": normal(keys[4], (D, H), D),
            "w_gate": normal(keys[5], (D, H), D),
            "conv_q": normal(keys[6], (K, inner), K),
            "conv_k": normal(keys[7], (K, inner), K),
            "conv_v": normal(keys[8], (K, inner), K),
            "a_log": jnp.zeros((H,), jnp.float32),
            "dt_bias": jnp.zeros((inner,), jnp.float32),
            "o_norm": jnp.ones((cfg.head_dim,), jnp.float32),
            "wo": normal(keys[9], (inner, D), inner),
        }

    def _init_layer(self, kind: Tuple[str, str, float, float], key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        k_mixer, k_ffn = jax.random.split(key)
        if kind[1] == "dense":
            ffn = decoder.dense_ffn_init(jax.random.split(k_ffn, 3), cfg.dim, cfg.dense_hidden, cfg.dtype)
        else:
            ffn = self.moe.init(k_ffn)
        return {
            "attn_norm": jnp.ones((cfg.dim,), jnp.float32),
            "mlp_norm": jnp.ones((cfg.dim,), jnp.float32),
            "mixer": self._init_mixer(kind[0], k_mixer),
            "ffn": ffn,
        }

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        k_embed, k_out, k_layers, k_mtp = jax.random.split(key, 4)
        table = functools.partial(decoder.seeded, fan_in=cfg.dim, dtype=cfg.dtype)
        params = {
            "embed": table(k_embed, (cfg.vocab_size, cfg.dim)),
            "groups": decoder.init_runs(self._init_layer, k_layers, cfg.groups()),
            "final_norm": jnp.ones((cfg.dim,), jnp.float32),
            "lm_head": table(k_out, (cfg.dim, cfg.vocab_size)),
        }
        if cfg.n_mtp:
            k_proj, k_layer = jax.random.split(k_mtp)
            params["mtp"] = mtp_init(cfg.dim, k_proj, self._init_layer(("mla", "moe", 0.0, 0.0), k_layer), cfg.dtype)
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

    # the routers' selection biases: state the optimizer does not own (``parallel/moe.py``)
    def state_mask(self) -> Any:
        return moe.state_mask(self.param_specs())

    def advance_state(self, state: List[jax.Array], signal: List[jax.Array]) -> List[jax.Array]:
        return moe.advance_state(self.config.bias_update_rate, state, signal)

    summary_stats = staticmethod(moe.summary_stats)

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------

    def _kernel_refusal(self, seq: int) -> Optional[str]:
        """Why the Mosaic kernels do NOT apply, or None when they do."""
        block_q, block_k = decoder.flash_blocks(seq)
        chunk = min(KDA_CHUNK, seq)
        shape_refusal = None
        if seq < 32 or seq % 8 or seq % block_q or seq % block_k or seq % chunk or chunk % min(32, chunk):
            shape_refusal = f"seq={seq} does not divide into the blocks ({block_q}, {block_k}) and chunks of {chunk}"
        return decoder.one_chip_refusal(shape_refusal, self.mesh)

    @part("mixer_glue")
    def _kda(self, h: jax.Array, w: Dict[str, jax.Array], kernels: bool) -> jax.Array:
        from torchft_tpu.ops.kda import kda_chunked, kda_chunked_plain

        cfg = self.config
        B, S, _ = h.shape
        H = cfg.n_heads
        heads = lambda a: a.reshape(B, S, H, -1)  # noqa: E731
        q = decoder.unit(heads(decoder.short_conv_silu(decoder.proj(h, w["wq"]), w["conv_q"])))
        k = decoder.unit(heads(decoder.short_conv_silu(decoder.proj(h, w["wk"]), w["conv_k"])))
        v = heads(decoder.short_conv_silu(decoder.proj(h, w["wv"]), w["conv_v"]))
        # the log of the decay, for every channel, in [lower_bound, 0]
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(w["a_log"])[None, None, :, None]
            * heads(decoder.proj(h, w["w_g"]).astype(jnp.float32) + w["dt_bias"])
        )
        beta = jax.nn.sigmoid(decoder.proj(h, w["w_beta"]).astype(jnp.float32))
        if kernels:
            o = kda_chunked(
                q, k, v, g, beta, chunk=KDA_CHUNK, interpret=decoder.assumed_backend() != "tpu"
            )
        else:
            o = kda_chunked_plain(q, k, v, g, beta, chunk=KDA_CHUNK)
        o = decoder.rms_norm(o, w["o_norm"], cfg.norm_eps)
        o = o * jax.nn.sigmoid(decoder.proj(h, w["w_gate"]).astype(jnp.float32))[..., None].astype(o.dtype)
        return decoder.proj(o.reshape(B, S, -1), w["wo"])

    def _block(
        self, x: jax.Array, w: Dict[str, Any], kind: Tuple[str, str, float, float], kernels: bool
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """One residual block: ``(x, load [E] (zeros for a dense layer),
        balance loss)``."""
        cfg = self.config
        mixer = self.mla.apply if kind[0] == "mla" else self._kda
        with part("stream"):
            h = decoder.rms_norm(x, w["attn_norm"], cfg.norm_eps)
        mixed = mixer(h, w["mixer"], kernels)
        with part("stream"):
            x = x + mixed
            h = decoder.rms_norm(x, w["mlp_norm"], cfg.norm_eps)
        if kind[1] == "dense":
            f = w["ffn"]
            with part("ffn"):
                out = swiglu(h @ f["w_gate"], h @ f["w_up"], 0.0) @ f["w_down"]
            with part("stream"):
                return x + out, jnp.zeros((cfg.num_experts,), jnp.float32), jnp.zeros((), jnp.float32)
        out, load, balance = self.moe.apply(w["ffn"], h, kind[2], kind[3])
        with part("stream"):
            return x + out, load, balance

    def _trunk(
        self, params: Dict[str, Any], tokens: jax.Array
    ) -> Tuple[jax.Array, List[jax.Array], jax.Array, bool]:
        """tokens [B, S] → (the residual stream after the last layer, the
        loads of every stacked group [depth, E], the summed balance loss,
        whether the kernels ran)."""
        cfg = self.config
        refusal = self._kernel_refusal(tokens.shape[1])
        kernels = refusal is None
        with part("embed"):
            x = params["embed"][tokens].astype(cfg.dtype)
        loads, balance = [], jnp.zeros((), jnp.float32)
        for (kind, depth), stacked in zip(cfg.groups(), params["groups"]):

            def body(carry, w, kind=kind):
                y, load, bal = self._block(carry, w, kind, kernels)
                return y, (load, bal)

            # keep only the residual stream at layer boundaries; a group of
            # one layer is no loop and keeps what it made: the compiler
            # merged its second forward with the first anyway (no barrier
            # forbids it), until the experts' loop of passes stood in its
            # way (PERF.md section 6, PR 42)
            x, (load, bal) = decoder.scan_run(
                body, x, stacked, depth, keep=() if depth > 1 else None, prevent_cse=False
            )
            loads.append(load)
            with part("experts_route"):
                balance = balance + jnp.sum(bal)
        kernels = decoder.kernel_path(self, KERNEL_PATH, refusal, self.moe.path)
        return x, loads, balance, kernels

    def _head(self, params: Dict[str, Any], x: jax.Array, norm: jax.Array) -> jax.Array:
        """A norm and the head, under whatever part the caller stands in."""
        x = decoder.rms_norm(x, norm, self.config.norm_eps)
        return (x @ params["lm_head"]).astype(jnp.float32)

    @part("head")
    def _logits(self, params: Dict[str, Any], x: jax.Array, norm: jax.Array) -> jax.Array:
        return self._head(params, x, norm)

    def apply(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """tokens [B, S] → logits [B, S, vocab] (fp32)."""
        x, _, _, _ = self._trunk(params, tokens)
        return self._logits(params, x, params["final_norm"])

    def _losses(
        self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]
    ) -> Tuple[jax.Array, jax.Array, List[jax.Array]]:
        """(cross-entropy with the MTP term, balance loss, the signal of
        every state leaf in the order ``state_mask`` flattens them)."""
        cfg = self.config
        tokens, targets = batch
        x, loads, balance, kernels = self._trunk(params, tokens)
        loss = decoder.mean_nll(self._logits(params, x, params["final_norm"]), targets)
        # the loads of the groups that have routers, in the groups' order
        signal = [
            load for (kind, _), load in zip(cfg.groups(), loads) if kind[1] == "moe"
        ]
        if cfg.n_mtp:
            # the module wraps its last position's label around, as this
            # model's reference does (``models/latent.py``)
            nll, load, bal = mtp_token_nll(
                params["mtp"], params["embed"], x, targets,
                layer=decoder.remat(
                    lambda z, w: self._block(z, w, ("mla", "moe", 0.0, 0.0), kernels), depth=1, prevent_cse=False
                ),
                head_nll=lambda z, norm, labels: decoder.token_nll(self._head(params, z, norm), labels),
                norm_eps=cfg.norm_eps, dtype=cfg.dtype,
            )
            with part("mtp"):
                loss = loss + cfg.mtp_loss_weight * jnp.mean(nll)
                balance = balance + bal
            signal.append(load)
        return loss, balance, signal

    def loss(self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]) -> jax.Array:
        """Mean next-token cross-entropy (and the multi-token-prediction
        loss at its weight); batch = (tokens, targets)."""
        return self._losses(params, batch)[0]

    def objective(
        self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]
    ) -> Tuple[jax.Array, Tuple[List[jax.Array], jax.Array]]:
        """What a training step differentiates (``loss`` and the routers'
        balance loss), for every leaf of ``state_mask`` the step's signal
        (the tokens each expert was chosen by) and the step's summary
        (``RoutedExperts.route_summary`` of this replica's own signal)."""
        loss, balance, signal = self._losses(params, batch)
        with part("head"):
            return loss + balance, (signal, self.moe.route_summary(signal, batch[0].size))
