"""A decoder whose attention layers are of two kinds from a published list,
a sliding window or every earlier position, over routed experts with a shared
one; for training on one chip's share.

The published configuration this was built for is Trinity-Mini's
(``model_type`` ``afmoe``): ``layer_types`` names each layer's attention,
``"sliding_attention"`` (a window of ``sliding_window`` positions, the
query's own counted) or ``"full_attention"``, three of the first to one of
the second.  The stream is ``E[token] * sqrt(dim)`` (``mup_enabled``), then a
layer is

- the mixer: ``a = RMSNorm(h)``; ``q, k, v, g = a Wq, a Wk, a Wv, a Wg``;
  ``q`` and ``k`` through an RMSNorm over a head's channels (one weight, the
  heads share it); on a WINDOWED layer rope over all of a head's channels,
  the halves paired, on a FULL layer no position encoding at all; grouped-
  query attention; ``h += RMSNorm((o * sigmoid(g)) Wo)``: the gate is a
  channel's own, the second norm is on the branch, before the add;
- the feed-forward part: ``m = RMSNorm(h)``; below ``num_dense_layers`` a
  SwiGLU of ``dense_hidden``, else ``parallel/moe.py`` ``RoutedExperts``, told
  which experts are here (sigmoid scores in float32, a selection bias, the
  ``top_k`` best of one group, the chosen scores normalised and scaled by
  ``route_scale``, one shared expert); ``h += RMSNorm(y)``, the fourth norm.

A final norm, then the head; embedding and head are not tied.

What is the model's and what a kernel's: projections, norms, rope and the
gate are here, plain ``jax.numpy``; the attention is
``ops/flash_attention.py``'s, whose ``window`` makes the kernels WALK only the
key blocks a row block's window touches (``flash_win_fwd``, ``flash_win_dq``,
``flash_win_dkv``; the full layers' are ``flash_fwd``, ``flash_dq``,
``flash_dkv``), the experts' grouped products ``megablox.gmm``.
``attention_path`` is ``"flash_win+flash"`` only if every layer took the flash
kernels and every expert layer the grouped kernel; off the TPU the window is
a mask over plain attention beside ``lax.ragged_dot`` and the path is named
``"plain: <why>"``.

Contiguous layers of one kind (attention kind, feed-forward kind) are stacked
and run under one ``lax.scan``, as ``SsmHybridMoE``'s are.  A layer is
rematerialised in the backward pass but for its float32 input and, on a FULL
layer, what flash made (``ops/flash_attention.py``, ``KEPT_NAMES``: ``o`` and
one float32 a row, 134 + 2 MB a layer at 16,384 positions and 32 heads of
128), so that ``flash_fwd`` stands once a full layer in a step's program.  A
windowed layer keeps nothing of the kind and ``flash_win_fwd`` runs twice:
the walk makes it cheap, and kept on all of the published cut's eight layers
the step did not fit a chip while the row statistics were kept 128 lanes wide.

The residual stream is float32 whatever the matrices' dtype, and the router
reads its float32 norm: which 8 of 128 experts a token takes is a step
function of what the router reads.

**State the optimizer does not own**: every router's selection bias, moved
after a committed step by ``parallel/moe.py``'s rule (``state_mask``, ``objective``,
``advance_state``; ``HSDPTrainer``).  There is no auxiliary loss: ``objective``
IS ``loss``, with the step's signal and summary beside it.
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
from torchft_tpu.parallel import moe
from torchft_tpu.parallel.moe import RoutedExperts, RoutedExpertsConfig, swiglu

KERNEL_PATH = "flash_win+flash"
# what the two norms ON a branch (after the mixer, after the feed-forward
# part) start at: such a weight is a scale a channel on a residual branch, and
# 0.1 is what LayerScale starts one at in networks of up to 18 layers
# (arXiv:2103.17239).  At 1, with seeded weights, the norm blows the mixer's
# small, slowly varying output (near-uniform attention averages some hundred
# zero-mean values) up to the stream's own size, every router downstream
# reads mostly that, and which experts a stretch of the sequence takes turns
# on the seed: the busiest held expert then has 4-6 times the mean load where
# it has 1.3-2.2 at 0.1 (PERF.md section 6, PR 41)
BRANCH_NORM_INIT = 0.1
ATTENTION_KINDS = ("sliding_attention", "full_attention")


@dataclass(frozen=True)
class WindowedMoEConfig:
    vocab_size: int = 200_192
    dim: int = 2048
    layer_types: Tuple[str, ...] = (*ATTENTION_KINDS[:1] * 3, ATTENTION_KINDS[1]) * 8  # a layer each
    sliding_window: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 10_000.0
    num_dense_layers: int = 2
    dense_hidden: int = 6144
    num_experts: int = 128
    experts_held: Tuple[int, int] = (0, 128)  # (first, count): this chip's share
    top_k: int = 8
    expert_hidden: int = 1024
    shared_hidden: int = 1024
    route_scale: float = 2.826
    route_norm: bool = True
    bias_update_rate: float = 1e-3
    embed_scale: bool = True  # ``mup_enabled``: the embedding times sqrt(dim)
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def kinds(self) -> List[Tuple[str, str]]:
        """(attention kind, ``"dense"`` or ``"moe"``) a layer."""
        unknown = set(self.layer_types) - set(ATTENTION_KINDS)
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types: a layer is one of {ATTENTION_KINDS}, not {sorted(unknown)}")
        return [
            (kind, "dense" if i < self.num_dense_layers else "moe")
            for i, kind in enumerate(self.layer_types)
        ]

    def groups(self) -> List[Tuple[Tuple[str, str], int]]:
        """Runs of contiguous layers of one kind: (kind, how many)."""
        return decoder.runs(self.kinds())

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)


def windowed_moe_debug(**over: Any) -> WindowedMoEConfig:
    """Tiny widths on the published list's first eight layers (two whole
    periods, one leading dense layer), the window shorter than the tests'
    sequences."""
    return replace(
        WindowedMoEConfig(
            vocab_size=512, dim=64, layer_types=WindowedMoEConfig.layer_types[:8], sliding_window=24,
            n_heads=4, n_kv_heads=2, head_dim=16, num_dense_layers=1, dense_hidden=128, num_experts=16,
            experts_held=(4, 4), top_k=4, expert_hidden=32, shared_hidden=32, dtype=jnp.float32,
        ),
        **over,
    )


class WindowedMoE:
    def __init__(self, config: WindowedMoEConfig, mesh: Optional[Any] = None) -> None:
        self.config = config
        self.mesh = mesh
        cfg = config
        self.groups = cfg.groups()
        if cfg.n_heads % cfg.n_kv_heads or cfg.head_dim % 2 or cfg.sliding_window < 1:
            raise ValueError("query heads divide into KV heads, rope pairs a head's halves, a window holds the query")
        self.moe = RoutedExperts(
            RoutedExpertsConfig(
                dim=cfg.dim, expert_hidden=cfg.expert_hidden, num_experts=cfg.num_experts,
                experts_held=tuple(cfg.experts_held), top_k=cfg.top_k,
                routed_scaling_factor=cfg.route_scale, norm_topk_prob=cfg.route_norm,
                shared_hidden=cfg.shared_hidden, dtype=cfg.dtype,
            )
        )
        # set when the layers are traced: KERNEL_PATH or "plain: <why>"
        self.attention_path: Optional[str] = None

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------

    def _init_layer(self, kind: Tuple[str, str], key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        D, hd = cfg.dim, cfg.head_dim
        q, kv = cfg.n_heads * hd, cfg.n_kv_heads * hd
        keys = jax.random.split(key, 9)

        normal = functools.partial(decoder.seeded, dtype=cfg.dtype)

        if kind[1] == "dense":
            ffn = decoder.dense_ffn_init(keys[5:8], D, cfg.dense_hidden, cfg.dtype)
        else:
            ffn = self.moe.init(keys[5])
        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        return {
            "norms": {
                "mixer_in": ones(D), "mixer_out": BRANCH_NORM_INIT * ones(D),
                "ffn_in": ones(D), "ffn_out": BRANCH_NORM_INIT * ones(D),
            },
            "wq": normal(keys[0], (D, q), D), "wk": normal(keys[1], (D, kv), D),
            "wv": normal(keys[2], (D, kv), D), "wg": normal(keys[3], (D, q), D),
            "wo": normal(keys[4], (q, D), q),
            "q_norm": ones(hd), "k_norm": ones(hd),
            "ffn": ffn,
        }

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        k_embed, k_out, k_layers = jax.random.split(key, 3)
        # rows of variance 1 / dim, so that the stream, the rows times
        # sqrt(dim), starts at unit variance
        embed, lm_head = decoder.embed_and_head(
            k_embed, k_out, cfg.vocab_size, cfg.dim, cfg.dtype, embed_std=cfg.dim ** -0.5 if cfg.embed_scale else 1.0
        )
        return {
            "embed": embed,
            "groups": decoder.init_runs(self._init_layer, k_layers, self.groups),
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
        return decoder.kernel_refusal(seq, self.mesh)

    @part("mixer_glue")
    def _attention(self, h: jax.Array, w: Dict[str, jax.Array], windowed: bool, kernels: bool) -> jax.Array:
        cfg = self.config
        B, S, _ = h.shape
        H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q = decoder.rms_norm(decoder.proj(h, w["wq"]).reshape(B, S, H, hd), w["q_norm"], cfg.norm_eps)
        k = decoder.rms_norm(decoder.proj(h, w["wk"]).reshape(B, S, KV, hd), w["k_norm"], cfg.norm_eps)
        v = decoder.proj(h, w["wv"]).reshape(B, S, KV, hd)
        gate = decoder.proj(h, w["wg"])
        window = None
        if windowed:  # position is the windowed layers' alone: a full layer has none
            q, k = decoder.rope_halves(q, cfg.rope_theta), decoder.rope_halves(k, cfg.rope_theta)
            window = cfg.sliding_window
        if kernels:
            block_q, block_k = decoder.flash_blocks(S)
            o = flash.flash_attention(
                q, k, v, causal=True, block_q=block_q, block_k=block_k, window=window,
                interpret=decoder.assumed_backend() != "tpu",
            )
        else:
            grouped = q.reshape(B, S, KV, H // KV, hd)
            scores = jnp.einsum("bqgrd,bkgd->bgrqk", grouped, k).astype(jnp.float32) / np.sqrt(hd)
            i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
            seen = (j <= i) if window is None else (j <= i) & (j > i - window)
            scores = jnp.where(seen, scores, -1e30)
            o = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, axis=-1).astype(q.dtype), v)
        o = o.reshape(B, S, H * hd).astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))
        return decoder.proj(o.astype(h.dtype), w["wo"])

    def _block(
        self, x: jax.Array, w: Dict[str, Any], kind: Tuple[str, str], kernels: bool
    ) -> Tuple[jax.Array, jax.Array]:
        """One layer: ``(x, load [E] (zeros for a dense layer))``."""
        cfg = self.config
        norm = lambda a, name: decoder.rms_norm(a.astype(jnp.float32), w["norms"][name], cfg.norm_eps)  # noqa: E731
        with part("stream"):
            h = norm(x, "mixer_in").astype(cfg.dtype)
        mixed = self._attention(h, w, kind[0] == "sliding_attention", kernels)
        with part("stream"):
            x = x + norm(mixed, "mixer_out")
            h = norm(x, "ffn_in")
        if kind[1] == "dense":
            f = w["ffn"]
            with part("stream"):
                h = h.astype(cfg.dtype)
            with part("ffn"):
                out = swiglu(h @ f["w_gate"], h @ f["w_up"], 0.0) @ f["w_down"]
            load = jnp.zeros((cfg.num_experts,), jnp.float32)
        else:
            # the router reads the float32 norm itself
            out, load, _ = self.moe.apply(w["ffn"], h)
        with part("stream"):
            return x + norm(out, "ffn_out"), load

    def _trunk(self, params: Dict[str, Any], tokens: jax.Array) -> Tuple[jax.Array, List[jax.Array]]:
        """tokens [B, S] → (the residual stream after the last layer, the
        loads [depth, E] of every stacked run of expert layers in the
        layers' order)."""
        cfg = self.config
        refusal = self._kernel_refusal(tokens.shape[1])
        kernels = refusal is None
        with part("embed"):
            x = params["embed"][tokens].astype(jnp.float32)  # the residual stream
            if cfg.embed_scale:
                x = x * np.float32(np.sqrt(cfg.dim))
        loads = []
        for (kind, depth), stacked in zip(self.groups, params["groups"]):
            # kept through a layer's rematerialisation: its float32 input and,
            # on a FULL layer, flash's output and row statistics (136 MB at
            # 16,384 positions), so that the dear ``flash_fwd`` stands once in a
            # step.  A WINDOWED layer keeps nothing of the kind and runs its
            # forward kernel again, which the walk makes cheap: kept on all
            # eight layers the first chip run died allocating (PERF.md section
            # 6, PR 41), when the row statistics were 268 MB a layer; at one
            # number a row that policy has not run (ROADMAP.md S14 (2))
            x, load = decoder.scan_run(
                lambda carry, w, kind=kind: self._block(carry, w, kind, kernels), x, stacked, depth,
                keep=flash.KEPT_NAMES if kind[0] == "full_attention" else (),
            )
            if kind[1] == "moe":
                loads.append(load)
        decoder.kernel_path(self, KERNEL_PATH, refusal, self.moe.path)
        return x, loads

    @part("head")
    def _logits(self, params: Dict[str, Any], x: jax.Array) -> jax.Array:
        cfg = self.config
        return decoder.head_logits(x, params["final_norm"], params["lm_head"], cfg.norm_eps, cfg.dtype)

    def apply(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """tokens [B, S] → logits [B, S, vocab] (fp32)."""
        return self._logits(params, self._trunk(params, tokens)[0])

    def loss(self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]) -> jax.Array:
        """Mean next-token cross-entropy; batch = (tokens, targets)."""
        return self.objective(params, batch)[0]

    def objective(
        self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]
    ) -> Tuple[jax.Array, Tuple[List[jax.Array], jax.Array]]:
        """What a training step differentiates (``loss``: the published
        configuration balances by the bias alone), for every leaf of
        ``state_mask`` the step's signal (the tokens each expert was chosen
        by) and the step's summary (``RoutedExperts.route_summary`` of this replica's own
        signal)."""
        tokens, targets = batch
        x, signal = self._trunk(params, tokens)
        loss = decoder.mean_nll(self._logits(params, x), targets)
        with part("head"):
            # a model of dense layers alone has no router to sum up
            summary = self.moe.route_summary(signal, tokens.size) if signal else jnp.zeros((0, len(moe.ROUTE_FIELDS)), jnp.float32)
            return loss, (signal, summary)
