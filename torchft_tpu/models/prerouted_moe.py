"""A decoder whose experts are routed from the layer's INPUT, before its
attention, over ReGLU experts with no shared one and no dense layer, under
attention of two kinds from two published lists; for training on one chip's
share.

The published configuration this was built for is SmallThinker-21BA3B's
(``model_name`` ``smallthinker_21b_instruct``, arXiv:2507.20984):
``sliding_window_layout`` says of each layer whether its attention is under
the window (1) or sees every earlier position (0), ``rope_layout`` whether it
turns q and k by their positions (1) or has no position encoding at all (0);
the published lists agree, one NoPE-global layer FIRST and then three
windowed ones with rope.  The stream is ``E[token]``, no scale, then a layer is

- ``a = RMSNorm_in(h)``; ``r = a W_r``: the router's logits are read off the
  ATTENTION'S input, so which experts a token takes does not wait for the
  attention (the point of the design where experts are fetched from slow
  storage while attention runs);
- the mixer: ``q, k, v = a Wq, a Wk, a Wv``, no bias, no head norm, no gate;
  on a layer with rope, rope over all of a head's channels, the halves
  paired; grouped-query attention, causal, under the window where the layer
  has one; ``h += o Wo``;
- the experts: ``m = RMSNorm_post(h)``; the ``top_k`` largest of ``r`` are the
  token's experts, their weights the softmax over those logits alone
  (``parallel/moe.py`` ``RoutedExperts`` with ``score_func`` softmax,
  ``norm_topk_prob`` and no selection bias: the softmax over all, taken at the
  chosen and normalised, is the same numbers), ``h += sum_e w_e W_down,e
  (relu(m W_gate,e) * (m W_up,e))`` over the experts HELD here
  (``expert_form`` "reglu", ``route_from`` = ``a``).  Every layer is an expert
  layer; nothing is shared.

A final norm, then the head; embedding and head are not tied.

The gradient of the routing weights enters the stream through ``a``, BEFORE
the attention's branch, and never through ``m``: ``RoutedExperts.apply`` hands
``route_from`` to its router and ``m`` to its experts.

What is the model's and what a kernel's: projections, norms and rope are here,
plain ``jax.numpy``; the attention is ``ops/flash_attention.py``'s
(``flash_win_fwd``, ``flash_win_dq``, ``flash_win_dkv`` under the window,
which walk only the key blocks a row block's window touches; ``flash_fwd``,
``flash_dq``, ``flash_dkv`` on a global layer), the experts' grouped products
``megablox.gmm``.  ``attention_path`` is ``"flash_win+flash"`` only if every
layer took the flash kernels and every expert layer the grouped kernel; off
the TPU the window is a mask over plain attention beside ``lax.ragged_dot``
and the path is named ``"plain: <why>"``.

Contiguous layers of one kind (window or not, rope or not) are stacked and run
under one ``lax.scan``.  A layer is rematerialised in the backward pass but for
its float32 input and, on a GLOBAL layer, what flash made
(``ops/flash_attention.py``, ``KEPT_NAMES``: ``o`` and one float32 a row), so
that ``flash_fwd`` stands once a global layer in a step's program; a windowed
layer keeps nothing of the kind and ``flash_win_fwd`` runs twice, as in
``models/windowed_moe.py``.

The residual stream is float32 whatever the matrices' dtype, and the router
reads the float32 norm: which 6 of 64 experts a token takes is a step function
of what the router reads.

There is no state the optimizer does not own (no selection bias:
``state_mask`` is all False and ``advance_state`` hands back what it got) and
no auxiliary loss: ``objective`` IS ``loss``, with the step's routing summary
beside it.
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
from torchft_tpu.parallel.moe import RoutedExperts, RoutedExpertsConfig

KERNEL_PATH = "flash_win+flash"


@dataclass(frozen=True)
class PreroutedMoEConfig:
    vocab_size: int = 151_936
    dim: int = 2560
    # an entry a layer, both: 1 where the layer is under the window / has rope
    sliding_window_layout: Tuple[int, ...] = (0, 1, 1, 1) * 13
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1) * 13
    sliding_window: int = 4096
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1.5e6
    num_experts: int = 64
    experts_held: Tuple[int, int] = (0, 64)  # (first, count): this chip's share
    top_k: int = 6
    expert_hidden: int = 768
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def kinds(self) -> List[Tuple[bool, bool]]:
        """(under the window, with rope) a layer."""
        if len(self.sliding_window_layout) != len(self.rope_layout) or not self.rope_layout:
            raise ValueError("sliding_window_layout and rope_layout have an entry a layer each, and a layer at least")
        unknown = (set(self.sliding_window_layout) | set(self.rope_layout)) - {0, 1}
        if unknown:
            raise ValueError(f"a layout's entry is 0 or 1, not {sorted(unknown)}")
        return [(bool(w), bool(r)) for w, r in zip(self.sliding_window_layout, self.rope_layout)]

    def groups(self) -> List[Tuple[Tuple[bool, bool], int]]:
        """Runs of contiguous layers of one kind: (kind, how many)."""
        return decoder.runs(self.kinds())

    @property
    def n_layers(self) -> int:
        return len(self.rope_layout)


def prerouted_moe_debug(**over: Any) -> PreroutedMoEConfig:
    """Tiny widths on the published lists' first period (one NoPE-global
    layer, three windowed ones with rope), SEVEN query heads to a key head as
    published, the second half of the experts held, the window shorter than
    the tests' sequences."""
    return replace(
        PreroutedMoEConfig(
            vocab_size=512, dim=64, sliding_window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1), sliding_window=24,
            n_heads=7, n_kv_heads=1, head_dim=16, num_experts=16, experts_held=(8, 8), top_k=3, expert_hidden=32,
            dtype=jnp.float32,
        ),
        **over,
    )


class PreroutedMoE:
    def __init__(self, config: PreroutedMoEConfig, mesh: Optional[Any] = None) -> None:
        self.config = config
        self.mesh = mesh
        cfg = config
        self.groups = cfg.groups()
        if cfg.n_heads % cfg.n_kv_heads or cfg.head_dim % 2 or cfg.sliding_window < 1:
            raise ValueError("query heads divide into KV heads, rope pairs a head's halves, a window holds the query")
        self.moe = RoutedExperts(
            RoutedExpertsConfig(
                dim=cfg.dim, expert_hidden=cfg.expert_hidden, num_experts=cfg.num_experts,
                experts_held=tuple(cfg.experts_held), top_k=cfg.top_k, score_func="softmax",
                selection_bias=False, norm_topk_prob=True, expert_form="reglu", dtype=cfg.dtype,
            )
        )
        # set when the layers are traced: KERNEL_PATH or "plain: <why>"
        self.attention_path: Optional[str] = None

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------

    def _init_layer(self, kind: Tuple[bool, bool], key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        D, hd = cfg.dim, cfg.head_dim
        q, kv = cfg.n_heads * hd, cfg.n_kv_heads * hd
        keys = jax.random.split(key, 5)
        normal = functools.partial(decoder.seeded, dtype=cfg.dtype)
        ones = jnp.ones((D,), jnp.float32)
        return {
            "norms": {"mixer_in": ones, "ffn_in": ones},
            "wq": normal(keys[0], (D, q), D), "wk": normal(keys[1], (D, kv), D),
            "wv": normal(keys[2], (D, kv), D), "wo": normal(keys[3], (q, D), q),
            "ffn": self.moe.init(keys[4]),
        }

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        k_embed, k_out, k_layers = jax.random.split(key, 3)
        # rows of unit variance: the stream IS the rows, and a token's own
        # embedding leads what the first routers read
        embed, lm_head = decoder.embed_and_head(k_embed, k_out, cfg.vocab_size, cfg.dim, cfg.dtype)
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

    # no router has a selection bias: no leaf is state the optimizer does not own
    def state_mask(self) -> Any:
        return moe.state_mask(self.param_specs())

    def advance_state(self, state: List[jax.Array], signal: List[jax.Array]) -> List[jax.Array]:
        return state

    summary_stats = staticmethod(moe.summary_stats)

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------

    @part("mixer_glue")
    def _attention(self, a: jax.Array, w: Dict[str, jax.Array], kind: Tuple[bool, bool], kernels: bool) -> jax.Array:
        cfg = self.config
        B, S, _ = a.shape
        H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        windowed, roped = kind
        q = decoder.proj(a, w["wq"]).reshape(B, S, H, hd)
        k = decoder.proj(a, w["wk"]).reshape(B, S, KV, hd)
        v = decoder.proj(a, w["wv"]).reshape(B, S, KV, hd)
        if roped:
            q, k = decoder.rope_halves(q, cfg.rope_theta), decoder.rope_halves(k, cfg.rope_theta)
        window = cfg.sliding_window if windowed else None
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
        return decoder.proj(o.reshape(B, S, H * hd).astype(a.dtype), w["wo"])

    def _block(
        self, x: jax.Array, w: Dict[str, Any], kind: Tuple[bool, bool], kernels: bool
    ) -> Tuple[jax.Array, jax.Array]:
        """One layer: ``(x, load [E])``."""
        cfg = self.config
        norm = lambda h, name: decoder.rms_norm(h, w["norms"][name], cfg.norm_eps)  # noqa: E731
        with part("stream"):
            a = norm(x, "mixer_in")  # float32: the router reads it as it is
        mixed = self._attention(a.astype(cfg.dtype), w, kind, kernels)
        with part("stream"):
            x = x + mixed.astype(jnp.float32)
            m = norm(x, "ffn_in")
        # the experts read m, the router read a: its choice never waited for the attention
        out, load, _ = self.moe.apply(w["ffn"], m, route_from=a)
        with part("stream"):
            return x + out, load

    def _trunk(self, params: Dict[str, Any], tokens: jax.Array) -> Tuple[jax.Array, List[jax.Array]]:
        """tokens [B, S] → (the residual stream after the last layer, the
        loads [depth, E] of every stacked run in the layers' order)."""
        refusal = decoder.kernel_refusal(tokens.shape[1], self.mesh)  # why the Mosaic kernels do NOT apply, or None
        kernels = refusal is None
        with part("embed"):
            x = params["embed"][tokens].astype(jnp.float32)  # the residual stream
        loads = []
        for (kind, depth), stacked in zip(self.groups, params["groups"]):
            # kept through a layer's rematerialisation: its float32 input and,
            # on a GLOBAL layer, flash's output and one float32 a row (120 MB at
            # 16,384 positions and 28 heads), so that the dear ``flash_fwd``
            # stands once in a step; a WINDOWED layer runs its forward kernel
            # again, which the walk makes cheap (``models/windowed_moe.py``)
            x, load = decoder.scan_run(
                lambda carry, w, kind=kind: self._block(carry, w, kind, kernels), x, stacked, depth,
                keep=() if kind[0] else flash.KEPT_NAMES,
            )
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
        """What a training step differentiates (``loss``: no auxiliary loss),
        no signal (no leaf here is the optimizer's to leave alone) and the
        step's summary (``RoutedExperts.route_summary`` of this replica's own
        loads, a row a layer)."""
        tokens, targets = batch
        x, loads = self._trunk(params, tokens)
        loss = decoder.mean_nll(self._logits(params, x), targets)
        with part("head"):
            return loss, ([], self.moe.route_summary(loads, tokens.size))
