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
the chunked delta rule is ``ops/kda.py``'s, the MLA scores
``ops/flash_attention.py``'s (q and k heads of 192, v heads of 128), the
experts' grouped products ``megablox.gmm``.  ``attention_path`` is
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
loss.  Multi-token prediction (one module: norms of the hidden state and of
the next token's embedding, a projection of the two side by side, one MLA
expert layer, the shared head, the token after next) is built for
``n_mtp`` > 0.
"""

from __future__ import annotations

import functools
import logging
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from torchft_tpu.models.llama import Llama, _proj
from torchft_tpu.obs.spans import part
from torchft_tpu.parallel.moe import RoutedExperts, RoutedExpertsConfig, swiglu

logger = logging.getLogger(__name__)

KERNEL_PATH = "kda+flash"
KDA_CHUNK = 64  # tokens a chunk of the delta rule (ops/kda.py)
# a step's summary, one row an expert layer (``route_summary``, ``summary_stats``)
ROUTE_FIELDS = ("rows_here", "load_max", "load_mean", "buffer_rows")


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
        out: List[Tuple[Any, int]] = []
        for kind in self.kinds():
            if out and out[-1][0] == kind:
                out[-1] = (kind, out[-1][1] + 1)
            else:
                out.append((kind, 1))
        return out


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


def _rope_interleaved(x: jax.Array, theta: float) -> jax.Array:
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


def _short_conv_silu(x: jax.Array, w: jax.Array, bias: Optional[jax.Array] = None) -> jax.Array:
    """Causal depthwise convolution (the last tap is the current token's),
    a bias a channel where one is given, and SiLU.  x [B, S, C], w [K, C]."""
    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    acc = sum(padded[:, j : j + S].astype(jnp.float32) * w[j].astype(jnp.float32) for j in range(K))
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    return jax.nn.silu(acc).astype(x.dtype)


def _unit(x: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + 1e-6)).astype(x.dtype)


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
        # set when the mixers are traced: KERNEL_PATH or "plain: <why>"
        self.attention_path: Optional[str] = None

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------

    def _init_mixer(self, kind: str, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        D, H = cfg.dim, cfg.n_heads
        keys = jax.random.split(key, 10)

        def normal(k, shape, fan_in):
            return (jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)).astype(cfg.dtype)

        if kind == "mla":
            qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
            return {
                "wq": normal(keys[0], (D, H * qk), D),
                "w_kv_a": normal(keys[1], (D, cfg.kv_lora_rank + cfg.qk_rope_head_dim), D),
                "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
                "w_kv_b": normal(
                    keys[2], (cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                    cfg.kv_lora_rank,
                ),
                "w_gate": normal(keys[3], (D, H), D),
                "wo": normal(keys[4], (H * cfg.v_head_dim, D), H * cfg.v_head_dim),
            }
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
            ks = jax.random.split(k_ffn, 3)
            shape_in, shape_out = (cfg.dim, cfg.dense_hidden), (cfg.dense_hidden, cfg.dim)
            scale = lambda k, shape: (  # noqa: E731
                jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[0])
            ).astype(cfg.dtype)
            ffn = {"w_gate": scale(ks[0], shape_in), "w_up": scale(ks[1], shape_in),
                   "w_down": scale(ks[2], shape_out)}
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
        groups = []
        for n, (kind, depth) in enumerate(cfg.groups()):
            keys = jax.random.split(jax.random.fold_in(k_layers, n), depth)
            groups.append(jax.vmap(lambda k, kind=kind: self._init_layer(kind, k))(keys))

        def table(k, shape):
            return (jax.random.normal(k, shape, jnp.float32) / np.sqrt(cfg.dim)).astype(cfg.dtype)

        params = {
            "embed": table(k_embed, (cfg.vocab_size, cfg.dim)),
            "groups": groups,
            "final_norm": jnp.ones((cfg.dim,), jnp.float32),
            "lm_head": table(k_out, (cfg.dim, cfg.vocab_size)),
        }
        if cfg.n_mtp:
            k_proj, k_layer = jax.random.split(k_mtp)
            params["mtp"] = {
                "enorm": jnp.ones((cfg.dim,), jnp.float32),
                "hnorm": jnp.ones((cfg.dim,), jnp.float32),
                "proj": (
                    jax.random.normal(k_proj, (2 * cfg.dim, cfg.dim), jnp.float32)
                    / np.sqrt(2 * cfg.dim)
                ).astype(cfg.dtype),
                "layer": self._init_layer(("mla", "moe", 0.0, 0.0), k_layer),
                "final_norm": jnp.ones((cfg.dim,), jnp.float32),
            }
        return params

    def param_specs(self) -> Dict[str, Any]:
        """One chip's share of a larger job: every leaf whole on the group's
        one chip (the ``fsdp`` axis of this model's meshes has size 1)."""
        return jax.tree_util.tree_map(lambda s: P(*([None] * len(s.shape))), self._shapes)

    @functools.cached_property
    def _shapes(self) -> Any:
        """What ``init`` would make, as shapes (traced once a model)."""
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    def batch_specs(self) -> Tuple[Any, Any]:
        spec = P(("dp", "fsdp"), None)
        return spec, spec

    def state_mask(self) -> Any:
        """True for the leaves the optimizer does not own: the routers'
        selection biases."""
        return jax.tree_util.tree_map_with_path(
            lambda path, _: getattr(path[-1], "key", None) == "bias",
            self.param_specs(),
            is_leaf=lambda x: isinstance(x, P),
        )

    def advance_state(self, state: List[jax.Array], signal: List[jax.Array]) -> List[jax.Array]:
        """``bias += rate * sign(mean(load) - load)``, a router at a time
        (the last axis is the router's width)."""
        rate = self.config.bias_update_rate
        return [
            bias + rate * jnp.sign(jnp.mean(load, axis=-1, keepdims=True) - load)
            for bias, load in zip(state, signal)
        ]

    def route_summary(self, loads: List[jax.Array], tokens: int) -> jax.Array:
        """Of this replica's step of ``tokens`` tokens, on the device:
        ``[expert layers, 4]`` in the order of ``ROUTE_FIELDS``: the rows
        routed to the held experts, their largest and mean load and the
        rows of the experts' buffer they went through
        (``RoutedExperts.buffer_rows``), expert layer by expert layer (a
        stacked leaf is one row a layer)."""
        first, held = self.config.experts_held
        here = jnp.concatenate([x.reshape(-1, x.shape[-1]) for x in loads])[:, first : first + held]
        rows = here.sum(axis=1)
        return jnp.stack([rows, here.max(axis=1), here.mean(axis=1), self.moe.buffer_rows(tokens, rows)], axis=1)

    @staticmethod
    def summary_stats(summary: np.ndarray) -> Dict[str, List[float]]:
        """:meth:`route_summary` on the host, as the flight event's detail."""
        columns = np.asarray(summary, np.float64).reshape(-1, len(ROUTE_FIELDS)).T
        return {name: column.tolist() for name, column in zip(ROUTE_FIELDS, columns)}

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------

    def _kernel_refusal(self, seq: int) -> Optional[str]:
        """Why the Mosaic kernels do NOT apply, or None when they do."""
        block_q, block_k = Llama._flash_blocks(seq)
        chunk = min(KDA_CHUNK, seq)
        shape_refusal = None
        if seq < 32 or seq % 8 or seq % block_q or seq % block_k or seq % chunk or chunk % min(32, chunk):
            shape_refusal = f"seq={seq} does not divide into the blocks ({block_q}, {block_k}) and chunks of {chunk}"
        return Llama._one_chip_refusal(shape_refusal, self.mesh)

    def _record_path(self, path: str) -> None:
        if path != self.attention_path:
            logger.info("attention path: %s", path)
        self.attention_path = path

    @part("mixer_glue")
    def _kda(self, h: jax.Array, w: Dict[str, jax.Array], kernels: bool) -> jax.Array:
        from torchft_tpu.ops.kda import kda_chunked, kda_chunked_plain

        cfg = self.config
        B, S, _ = h.shape
        H = cfg.n_heads
        heads = lambda a: a.reshape(B, S, H, -1)  # noqa: E731
        q = _unit(heads(_short_conv_silu(_proj(h, w["wq"]), w["conv_q"])))
        k = _unit(heads(_short_conv_silu(_proj(h, w["wk"]), w["conv_k"])))
        v = heads(_short_conv_silu(_proj(h, w["wv"]), w["conv_v"]))
        # the log of the decay, for every channel, in [lower_bound, 0]
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(w["a_log"])[None, None, :, None]
            * heads(_proj(h, w["w_g"]).astype(jnp.float32) + w["dt_bias"])
        )
        beta = jax.nn.sigmoid(_proj(h, w["w_beta"]).astype(jnp.float32))
        if kernels:
            o = kda_chunked(
                q, k, v, g, beta, chunk=KDA_CHUNK, interpret=Llama._assumed_backend() != "tpu"
            )
        else:
            o = kda_chunked_plain(q, k, v, g, beta, chunk=KDA_CHUNK)
        o = Llama._rms_norm(o, w["o_norm"], cfg.norm_eps)
        o = o * jax.nn.sigmoid(_proj(h, w["w_gate"]).astype(jnp.float32))[..., None].astype(o.dtype)
        return _proj(o.reshape(B, S, -1), w["wo"])

    @part("mixer_glue")
    def _mla(self, h: jax.Array, w: Dict[str, jax.Array], kernels: bool) -> jax.Array:
        cfg = self.config
        B, S, _ = h.shape
        H, nope, rot = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        q = _proj(h, w["wq"]).reshape(B, S, H, nope + rot)
        q = jnp.concatenate([q[..., :nope], _rope_interleaved(q[..., nope:], cfg.rope_theta)], axis=-1)
        kv_a = _proj(h, w["w_kv_a"])
        latent = Llama._rms_norm(kv_a[..., : cfg.kv_lora_rank], w["kv_norm"], cfg.norm_eps)
        k_rot = _rope_interleaved(kv_a[..., cfg.kv_lora_rank :], cfg.rope_theta)
        kv = _proj(latent, w["w_kv_b"]).reshape(B, S, H, nope + cfg.v_head_dim)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rot[:, :, None, :], (B, S, H, rot))], axis=-1
        )
        v = kv[..., nope:]
        if kernels:
            from torchft_tpu.ops.flash_attention import flash_attention

            block_q, block_k = Llama._flash_blocks(S)
            o = flash_attention(
                q, k, v, causal=True, block_q=block_q, block_k=block_k,
                interpret=Llama._assumed_backend() != "tpu",
            )
        else:
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / np.sqrt(nope + rot)
            scores = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], scores, -1e30)
            o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1).astype(q.dtype), v)
        o = o * jax.nn.sigmoid(_proj(h, w["w_gate"]).astype(jnp.float32))[..., None].astype(o.dtype)
        return _proj(o.reshape(B, S, -1), w["wo"])

    def _block(
        self, x: jax.Array, w: Dict[str, Any], kind: Tuple[str, str, float, float], kernels: bool
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """One residual block: ``(x, load [E] (zeros for a dense layer),
        balance loss)``."""
        cfg = self.config
        mixer = self._mla if kind[0] == "mla" else self._kda
        with part("stream"):
            h = Llama._rms_norm(x, w["attn_norm"], cfg.norm_eps)
        mixed = mixer(h, w["mixer"], kernels)
        with part("stream"):
            x = x + mixed
            h = Llama._rms_norm(x, w["mlp_norm"], cfg.norm_eps)
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
        for (kind, _depth), stacked in zip(cfg.groups(), params["groups"]):

            def body(carry, w, kind=kind):
                y, load, bal = self._block(carry, w, kind, kernels)
                return y, (load, bal)

            # keep only the residual stream at layer boundaries; a group of
            # one layer is no loop and keeps what it made: the compiler
            # merged its second forward with the first anyway (no barrier
            # forbids it), until the experts' loop of passes stood in its
            # way (PERF.md section 6, PR 42)
            if _depth > 1:
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable, prevent_cse=False
                )
            with part("layers"):
                x, (load, bal) = jax.lax.scan(body, x, stacked)
            loads.append(load)
            with part("experts_route"):
                balance = balance + jnp.sum(bal)
        if kernels and self.moe.path not in (None, "gmm") and Llama._assumed_backend() == "tpu":
            refusal, kernels = f"the experts took {self.moe.path}", False
        self._record_path(KERNEL_PATH if kernels else f"plain: {refusal}")
        return x, loads, balance, kernels

    @part("head")
    def _logits(self, params: Dict[str, Any], x: jax.Array, norm: jax.Array) -> jax.Array:
        x = Llama._rms_norm(x, norm, self.config.norm_eps)
        return (x @ params["lm_head"]).astype(jnp.float32)

    def apply(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """tokens [B, S] → logits [B, S, vocab] (fp32)."""
        x, _, _, _ = self._trunk(params, tokens)
        return self._logits(params, x, params["final_norm"])

    @staticmethod
    @part("head")
    def _mean_nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
        logp = jax.nn.log_softmax(logits, axis=-1)
        return jnp.mean(-jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0])

    def _losses(
        self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]
    ) -> Tuple[jax.Array, jax.Array, List[jax.Array]]:
        """(cross-entropy with the MTP term, balance loss, the signal of
        every state leaf in the order ``state_mask`` flattens them)."""
        cfg = self.config
        tokens, targets = batch
        x, loads, balance, kernels = self._trunk(params, tokens)
        loss = self._mean_nll(self._logits(params, x, params["final_norm"]), targets)
        # the loads of the groups that have routers, in the groups' order
        signal = [
            load for (kind, _), load in zip(cfg.groups(), loads) if kind[1] == "moe"
        ]
        if cfg.n_mtp:
            with part("head"):  # the module's own layer names its parts itself
                m = params["mtp"]
                z = jnp.concatenate(
                    [
                        Llama._rms_norm(params["embed"][targets].astype(cfg.dtype), m["enorm"], cfg.norm_eps),
                        Llama._rms_norm(x, m["hnorm"], cfg.norm_eps),
                    ],
                    axis=-1,
                ) @ m["proj"]
                z, load, bal = jax.checkpoint(
                    lambda z, w: self._block(z, w, ("mla", "moe", 0.0, 0.0), kernels),
                    policy=jax.checkpoint_policies.nothing_saveable, prevent_cse=False,
                )(z, m["layer"])
                mtp = self._mean_nll(self._logits(params, z, m["final_norm"]), jnp.roll(targets, -1, axis=1))
                loss = loss + cfg.mtp_loss_weight * mtp
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
        (:meth:`route_summary` of this replica's own signal)."""
        loss, balance, signal = self._losses(params, batch)
        with part("head"):
            return loss + balance, (signal, self.route_summary(signal, batch[0].size))

    def num_params(self) -> int:
        return sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(self._shapes))
