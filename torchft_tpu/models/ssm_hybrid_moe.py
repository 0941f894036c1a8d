"""A decoder whose layers are ONE mixer each, of three kinds from a pattern
string: a state-space layer (Mamba-2), grouped-query attention, or routed
experts with a shared one; for training on one chip's share.

The published configuration this was built for is
NVIDIA-Nemotron-3-Nano-30B-A3B's (``model_type`` ``nemotron_h``): the
``pattern`` is its ``hybrid_override_pattern``, a character a layer, ``M`` a
state-space layer, ``*`` attention, ``E`` experts.  Every layer is ``x <- x +
f(RMSNorm(x))`` for one ``f``; a final norm, then the head.

- ``M`` (arXiv:2405.21060): ``[z | xBC | r] = W_in h``; ``xBC <- silu(conv(xBC)
  + c)``, a causal depthwise convolution with a bias; ``xBC = [X | B | C]``,
  ``X`` ``ssm_heads`` heads of ``ssm_head_dim``, ``B`` and ``C`` ``ssm_groups``
  groups of ``ssm_state``; ``dt = softplus(r + dt_bias)``; the scan of
  ``ops/ssd.py`` (``S_t = exp(-dt_t exp(A_log)) S_{t-1} + dt_t X_t B_t^T``,
  ``y_t = S_t C_t + D X_t``); ``f = W_out GroupRMSNorm(y * silu(z))``, the norm
  over each group's channels, with a weight.
- ``*``: ``n_heads`` query heads over ``n_kv_heads`` key and value heads,
  causal, NO position encoding (the state-space layers carry position:
  Nemotron-H's convention, arXiv:2504.03624), ``ops/flash_attention.py``.
- ``E``: ``parallel/moe.py`` ``RoutedExperts``, told which experts are here:
  sigmoid scores in float32, a selection bias, the ``top_k`` best of one
  group, experts of TWO matrices ``w_down relu(w_up h)^2``, one shared.

What is the model's and what a kernel's: the projections, the convolution
with its SiLU, ``softplus``, the gated norm are here, plain ``jax.numpy``; the
chunked scan is ``ops/ssd.py``'s, the attention ``ops/flash_attention.py``'s,
the experts' grouped products ``megablox.gmm``.  ``attention_path`` is
``"ssd+flash"`` only if every ``M`` layer took the scan kernels, every ``*``
layer the flash kernels and every ``E`` layer the grouped kernel; off the
TPU the same chunk algebra runs as plain ``jax.numpy`` beside plain attention
and ``lax.ragged_dot`` and the path is named ``"plain: <why>"``.

Contiguous layers of one kind (a RUN of the pattern) are stacked and run
under one ``lax.scan``, as ``LingHybrid``'s are; no two neighbours of the
published pattern are of one kind, so there every run is one layer and
``params["groups"]`` holds a stack of one a layer.  A layer is
rematerialised in the backward pass but for what flash made
(``ops/flash_attention.py``, ``KEPT_NAMES``: its output and row statistics,
134 + 2 MB a ``*`` layer at 16,384 positions and 32 heads of 128), so that
``flash_fwd`` stands once in a step's program.  ``ops/ssd.py`` names its
output and chunk-start states the same way (134 + 268 MB an ``M`` layer at
64 heads of 64 by 128); this model's policy does not list them, because at
the published widths they do not fit beside the float32 stream, and
``ssd_fwd`` runs twice a layer and step.

The residual stream is float32 whatever the matrices' dtype, and the router
reads its float32 norm: which 6 of 128 experts a token takes is a step
function of what the router reads.

**State the optimizer does not own**: every router's selection bias, moved
after a committed step by ``parallel/moe.py``'s rule (``state_mask``, ``objective``,
``advance_state``; ``HSDPTrainer``).  ``loss`` is the next-token
cross-entropy; ``objective`` adds the routers' sequence-wise balance loss.
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
from torchft_tpu.ops import ssd
from torchft_tpu.parallel import moe
from torchft_tpu.parallel.moe import RoutedExperts, RoutedExpertsConfig

KERNEL_PATH = "ssd+flash"
KINDS = {"M": "ssm", "*": "attention", "E": "experts"}


@dataclass(frozen=True)
class SsmHybridMoEConfig:
    vocab_size: int = 131_072
    dim: int = 2688
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"  # a character a layer
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8
    conv_kernel: int = 4
    chunk: int = 128
    time_step_min: float = 1e-3
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    num_experts: int = 128
    experts_held: Tuple[int, int] = (0, 128)  # (first, count): this chip's share
    top_k: int = 6
    expert_hidden: int = 1856
    shared_hidden: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    bias_update_rate: float = 1e-3
    balance_loss_weight: float = 1e-4
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def kinds(self) -> List[str]:
        unknown = set(self.pattern) - set(KINDS)
        if unknown or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: a layer is one of {sorted(KINDS)}, not {sorted(unknown)}")
        return [KINDS[c] for c in self.pattern]

    def groups(self) -> List[Tuple[str, int]]:
        """Runs of contiguous layers of one kind: (kind, how many)."""
        return decoder.runs(self.kinds())

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """The channels the convolution runs over: ``X``, ``B`` and ``C``."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state


def ssm_hybrid_debug(**over: Any) -> SsmHybridMoEConfig:
    """Tiny widths on the published pattern's first nine layers, for tests."""
    return replace(
        SsmHybridMoEConfig(
            vocab_size=512, dim=64, pattern="MEMEM*EME", ssm_heads=4, ssm_head_dim=16, ssm_state=16,
            ssm_groups=2, chunk=16, n_heads=4, n_kv_heads=2, head_dim=16, num_experts=16,
            experts_held=(4, 4), top_k=4, expert_hidden=32, shared_hidden=48, dtype=jnp.float32,
        ),
        **over,
    )


class SsmHybridMoE:
    def __init__(self, config: SsmHybridMoEConfig, mesh: Optional[Any] = None) -> None:
        self.config = config
        self.mesh = mesh
        cfg = config
        self.groups = cfg.groups()
        if cfg.n_heads % cfg.n_kv_heads or cfg.ssm_heads % cfg.ssm_groups:
            raise ValueError("query heads divide into KV heads and state-space heads into groups")
        self.moe = RoutedExperts(
            RoutedExpertsConfig(
                dim=cfg.dim, expert_hidden=cfg.expert_hidden, num_experts=cfg.num_experts,
                experts_held=tuple(cfg.experts_held), top_k=cfg.top_k,
                routed_scaling_factor=cfg.routed_scaling_factor, norm_topk_prob=cfg.norm_topk_prob,
                shared_hidden=cfg.shared_hidden, balance_loss_weight=cfg.balance_loss_weight,
                expert_form="relu2", dtype=cfg.dtype,
            )
        )
        # set when the layers are traced: KERNEL_PATH or "plain: <why>"
        self.attention_path: Optional[str] = None

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------

    def _init_layer(self, kind: str, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        D = cfg.dim
        keys = jax.random.split(key, 6)
        # what writes into the residual stream is scaled down by the depth
        # (``rescale_prenorm_residual``, GPT-2's 1 / sqrt(2 layers))
        out_scale = 2 * cfg.n_layers

        normal = functools.partial(decoder.seeded, dtype=cfg.dtype)

        norm = jnp.ones((D,), jnp.float32)
        if kind == "experts":
            ffn = self.moe.init(keys[0])
            for name in ("w_down", "shared_down"):
                ffn[name] = (ffn[name].astype(jnp.float32) / np.sqrt(out_scale)).astype(cfg.dtype)
            return {"norm": norm, "ffn": ffn}
        if kind == "attention":
            q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
            return {
                "norm": norm,
                "wq": normal(keys[0], (D, q), D), "wk": normal(keys[1], (D, kv), D),
                "wv": normal(keys[2], (D, kv), D), "wo": normal(keys[3], (q, D), q * out_scale),
            }
        H, inner, conv, K = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_width, cfg.conv_kernel
        # the Mamba-2 reference code's: a step log-uniform between the two
        # limits with a floor, kept as the inverse of its softplus; A uniform
        # in [1, 16]; D 1
        dt = jnp.exp(
            jax.random.uniform(keys[2], (H,), jnp.float32)
            * (np.log(cfg.time_step_max) - np.log(cfg.time_step_min)) + np.log(cfg.time_step_min)
        )
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return {
            "norm": norm,
            "w_in": normal(keys[0], (D, inner + conv + H), D),
            "conv": normal(keys[1], (K, conv), K),
            "conv_bias": jnp.zeros((conv,), jnp.float32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(keys[3], (H,), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((H,), jnp.float32),
            "o_norm": jnp.ones((inner,), jnp.float32),
            "w_out": normal(keys[4], (inner, D), inner * out_scale),
        }

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        k_embed, k_out, k_layers = jax.random.split(key, 3)
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
        return decoder.kernel_refusal(seq, self.mesh, chunk=self.config.chunk)

    @part("mixer_glue")
    def _ssm(self, h: jax.Array, w: Dict[str, jax.Array], kernels: bool) -> jax.Array:
        cfg = self.config
        B, S, _ = h.shape
        H, inner, GN = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state
        zxr = decoder.proj(h, w["w_in"])
        z, xbc, r = jnp.split(zxr, [inner, inner + cfg.ssm_conv_width], axis=-1)
        xbc = decoder.short_conv_silu(xbc, w["conv"], w["conv_bias"])
        x, Bm, Cm = jnp.split(xbc, [inner, inner + GN], axis=-1)
        groups = lambda a: a.reshape(B, S, cfg.ssm_groups, cfg.ssm_state)  # noqa: E731
        dt = jax.nn.softplus(r.astype(jnp.float32) + w["dt_bias"])
        operands = (x.reshape(B, S, H, cfg.ssm_head_dim), dt, w["A_log"], groups(Bm), groups(Cm), w["D"])
        if kernels:
            y = ssd.ssd_chunked(*operands, chunk=cfg.chunk, interpret=decoder.assumed_backend() != "tpu")
        else:
            y = ssd.ssd_chunked_plain(*operands, chunk=cfg.chunk)
        # gate, then the norm over each group's channels
        y = y.reshape(B, S, inner).astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        y = decoder.rms_norm(y.reshape(B, S, cfg.ssm_groups, -1), 1.0, cfg.norm_eps).reshape(B, S, inner)
        return decoder.proj((y * w["o_norm"]).astype(h.dtype), w["w_out"])

    @part("mixer_glue")
    def _attention(self, h: jax.Array, w: Dict[str, jax.Array], kernels: bool) -> jax.Array:
        cfg = self.config
        B, S, _ = h.shape
        H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q = decoder.proj(h, w["wq"]).reshape(B, S, H, hd)
        k = decoder.proj(h, w["wk"]).reshape(B, S, KV, hd)
        v = decoder.proj(h, w["wv"]).reshape(B, S, KV, hd)
        if kernels:
            block_q, block_k = decoder.flash_blocks(S)
            o = flash.flash_attention(
                q, k, v, causal=True, block_q=block_q, block_k=block_k,
                interpret=decoder.assumed_backend() != "tpu",
            )
        else:
            grouped = q.reshape(B, S, KV, H // KV, hd)
            scores = jnp.einsum("bqgrd,bkgd->bgrqk", grouped, k).astype(jnp.float32) / np.sqrt(hd)
            scores = jnp.where(jnp.tril(jnp.ones((S, S), bool)), scores, -1e30)
            o = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, axis=-1).astype(q.dtype), v)
        return decoder.proj(o.reshape(B, S, H * hd), w["wo"])

    def _block(
        self, x: jax.Array, w: Dict[str, Any], kind: str, kernels: bool
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """One residual layer: ``(x, load [E] (zeros but for an expert
        layer), balance loss)``."""
        cfg = self.config
        with part("stream"):
            h = decoder.rms_norm(x, w["norm"], cfg.norm_eps)
        if kind == "experts":
            # the router reads the float32 norm itself
            out, load, balance = self.moe.apply(w["ffn"], h)
            with part("stream"):
                return x + out.astype(x.dtype), load, balance
        mixer = self._ssm if kind == "ssm" else self._attention
        with part("stream"):
            h = h.astype(cfg.dtype)
        out = mixer(h, w, kernels)
        with part("stream"):
            return x + out.astype(x.dtype), jnp.zeros((cfg.num_experts,), jnp.float32), jnp.zeros((), jnp.float32)

    def _trunk(self, params: Dict[str, Any], tokens: jax.Array) -> Tuple[jax.Array, List[jax.Array], jax.Array]:
        """tokens [B, S] → (the residual stream after the last layer, the
        loads [depth, E] of every stacked run of expert layers in the
        layers' order, the summed balance loss)."""
        refusal = self._kernel_refusal(tokens.shape[1])
        kernels = refusal is None
        with part("embed"):
            x = params["embed"][tokens].astype(jnp.float32)  # the residual stream
        loads, balance = [], jnp.zeros((), jnp.float32)
        # kept through a layer's rematerialisation: flash's output and row
        # statistics (136 MB at 16,384 positions).  NOT ``ssd.KEPT_NAMES``: at
        # the published widths the scan's 402 MB a layer, four layers, beside
        # a float32 stream ask 16.2 GB of a chip that gives out 16.9 and the
        # step dies allocating (PERF.md section 6, PR 35), so ``ssd_fwd``
        # (1.6 ms a layer in the cell's trace) runs again in the backward
        # pass.  ONE policy object for every run, as this model's text was
        # made (``decoder.remat``)
        keep = jax.checkpoint_policies.save_only_these_names(*flash.KEPT_NAMES)
        for (kind, depth), stacked in zip(self.groups, params["groups"]):

            def body(carry, w, kind=kind):
                y, load, bal = self._block(carry, w, kind, kernels)
                return y, (load, bal)

            # every run of the published pattern is ONE layer, where jax's
            # guard against XLA merging the two forwards stays on: it was
            # measured here (``decoder.remat``)
            x, (load, bal) = decoder.scan_run(body, x, stacked, depth, keep=keep)
            if kind == "experts":
                loads.append(load)
                with part("experts_route"):
                    balance = balance + jnp.sum(bal)
        decoder.kernel_path(self, KERNEL_PATH, refusal, self.moe.path)
        return x, loads, balance

    @part("head")
    def _logits(self, params: Dict[str, Any], x: jax.Array) -> jax.Array:
        cfg = self.config
        return decoder.head_logits(x, params["final_norm"], params["lm_head"], cfg.norm_eps, cfg.dtype)

    def apply(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """tokens [B, S] → logits [B, S, vocab] (fp32)."""
        return self._logits(params, self._trunk(params, tokens)[0])

    def _losses(
        self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]
    ) -> Tuple[jax.Array, jax.Array, List[jax.Array]]:
        tokens, targets = batch
        x, loads, balance = self._trunk(params, tokens)
        return decoder.mean_nll(self._logits(params, x), targets), balance, loads

    def loss(self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]) -> jax.Array:
        """Mean next-token cross-entropy; batch = (tokens, targets)."""
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
