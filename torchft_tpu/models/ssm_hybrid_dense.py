"""A decoder whose every layer is a mixer AND a SwiGLU, the mixer a
state-space layer (Mamba-2) or grouped-query attention by a list of layer
types, with scalar multipliers on the embedding, on both residual branches,
on the attention's scores and on the logits, and a tied head; for training on
one chip's share.

The published configuration this was built for is granite-4.0-h-micro's
(``model_type`` ``granitemoehybrid`` with no experts: the dense member of the
Granite 4.0-H family).  With ``h`` the float32 stream and ``E`` the embedding,
which is the head too:

- ``h = embedding_multiplier * E[token]``;
- a layer: ``a = RMSNorm(h)``; ``h += residual_multiplier * mixer(a)``; ``m =
  RMSNorm_post(h)``; ``h += residual_multiplier * W_down (silu(m W_gate) * (m
  W_up))``, ``W_gate | W_up`` one matrix;
- ``mamba`` (arXiv:2405.21060): ``[z | xBC | r] = a W_in``; ``xBC <-
  silu(conv(xBC) + c)``, causal and depthwise; ``xBC = [X | B | C]``, ``X``
  ``ssm_heads`` heads of ``ssm_head_dim``, ``B`` and ``C`` ``ssm_groups`` groups
  of ``ssm_state`` (ONE group in the published model: all 64 heads share them);
  ``dt = softplus(r + dt_bias)``; the scan of ``ops/ssd.py``; ``mixer = W_out (w
  * RMSNorm(y * silu(z)))``, gate first, the norm over a group's channels;
- ``attention``: ``n_heads`` query heads over ``n_kv_heads`` key and value
  heads, causal softmax of ``attention_multiplier * q k^T`` (a published
  constant, NOT ``1 / sqrt(head_dim)``), no position encoding, no bias;
- ``logits = RMSNorm_f(h) E^T / logits_scaling``.

The four multipliers are fields of the config: none is a constant here.

What is the model's and what a kernel's: the projections, the convolution,
``softplus``, the gated norm and the SwiGLU are here, plain ``jax.numpy`` over
``models/decoder.py``'s helpers; the chunked scan is ``ops/ssd.py``'s (a group
wider than a kernel's block goes through it in head blocks), the attention
``ops/flash_attention.py``'s.  ``attention_path`` is ``"ssd+flash"`` only if
every ``mamba`` layer took the scan kernels and every ``attention`` layer the
flash kernels; off the TPU the same chunk algebra runs as plain ``jax.numpy``
beside plain attention and the path is named ``"plain: <why>"``.

Contiguous layers of one type (a RUN of ``layer_types``: five, one and four in
the published period of ten) are stacked and run under one ``lax.scan``.  A
layer is rematerialised in the backward pass but for its input and what the
kernels made (``flash.KEPT_NAMES``, ``ssd.KEPT_NAMES``: a scan's output and
chunk-start states, 134 MB each a layer at 16,384 positions and a chunk of
256), so that no kernel's forward runs twice; the head and its cross-entropy
run ``head_block`` positions at a time (``decoder.blocked_nll``).  The tied
leaf's gradient is the sum of the gather's and the head's, made by jax.

The step's summary is ``decay_min``: the most negative ``-dt_t exp(A_log)`` a
token of the step's scans (0 says no scan ran), which ``summary_stats`` hands
the step's flight event.  The model has no state the optimizer does not own.
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
from torchft_tpu.parallel.moe import swiglu

KERNEL_PATH = "ssd+flash"
KEPT_NAMES = (*flash.KEPT_NAMES, *ssd.KEPT_NAMES)
KINDS = ("mamba", "attention")
SUMMARY_FIELDS = ("decay_min",)
# the published period of ten layers: attention where the index is 5 modulo 10
_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclass(frozen=True)
class SsmHybridDenseConfig:
    vocab_size: int = 100_352
    dim: int = 2048
    layer_types: Tuple[str, ...] = _PERIOD * 4  # an entry a layer
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    conv_kernel: int = 4
    chunk: int = 256
    time_step_min: float = 1e-3
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    ffn_hidden: int = 8192
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    norm_eps: float = 1e-5
    head_block: int = 4096  # positions of the head and its cross-entropy at a time
    dtype: Any = jnp.bfloat16

    def groups(self) -> List[Tuple[str, int]]:
        """Runs of contiguous layers of one type: (type, how many)."""
        unknown = set(self.layer_types) - set(KINDS)
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types {self.layer_types!r}: a layer is one of {KINDS}, not {sorted(unknown)}")
        return decoder.runs(self.layer_types)

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """The channels the convolution runs over: ``X``, ``B`` and ``C``."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state


def ssm_hybrid_dense_debug(**over: Any) -> SsmHybridDenseConfig:
    """Tiny widths on the published period of ten layers (runs of five, one
    and four), ONE group of four heads, a head in blocks shorter than the
    tests' sequences, for tests."""
    return replace(
        SsmHybridDenseConfig(
            vocab_size=96, dim=32, layer_types=_PERIOD, ssm_heads=4, ssm_head_dim=16, ssm_state=16, chunk=16,
            n_heads=4, n_kv_heads=2, head_dim=8, ffn_hidden=64, head_block=32, dtype=jnp.float32,
        ),
        **over,
    )


class SsmHybridDense:
    def __init__(self, config: SsmHybridDenseConfig, mesh: Optional[Any] = None) -> None:
        self.config = config
        self.mesh = mesh
        self.groups = config.groups()
        if config.n_heads % config.n_kv_heads or config.ssm_heads % config.ssm_groups:
            raise ValueError("query heads divide into KV heads and state-space heads into groups")
        # set when the layers are traced: KERNEL_PATH or "plain: <why>"
        self.attention_path: Optional[str] = None

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------

    def _init_layer(self, kind: str, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        D, F = cfg.dim, cfg.ffn_hidden
        k_gate_up, k_down, *keys = jax.random.split(key, 7)
        normal = lambda k, shape: decoder.seeded(k, shape, shape[0], cfg.dtype)  # noqa: E731
        if kind == "attention":
            q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
            mixer = {"wq": normal(keys[0], (D, q)), "wk": normal(keys[1], (D, kv)), "wv": normal(keys[2], (D, kv)), "wo": normal(keys[3], (q, D))}
        else:
            H, inner, conv, K = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_width, cfg.conv_kernel
            # the Mamba-2 reference code's: a step log-uniform between the two
            # limits with a floor, kept as the inverse of its softplus; A uniform
            # in [1, 16]; D 1
            dt = jnp.exp(
                jax.random.uniform(keys[2], (H,), jnp.float32)
                * (np.log(cfg.time_step_max) - np.log(cfg.time_step_min)) + np.log(cfg.time_step_min)
            )
            dt = jnp.maximum(dt, cfg.time_step_floor)
            mixer = {
                "w_in": normal(keys[0], (D, inner + conv + H)),
                "conv": normal(keys[1], (K, conv)), "conv_bias": jnp.zeros((conv,), jnp.float32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(keys[3], (H,), jnp.float32, 1.0, 16.0)),
                "D": jnp.ones((H,), jnp.float32),
                "o_norm": jnp.ones((inner,), jnp.float32),
                "w_out": normal(keys[4], (inner, D)),
            }
        return {
            "norm": jnp.ones((D,), jnp.float32), "post_norm": jnp.ones((D,), jnp.float32), "mixer": mixer,
            "ffn": {"w_gate_up": normal(k_gate_up, (D, 2 * F)), "w_down": normal(k_down, (F, D))},
        }

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        k_embed, k_layers = jax.random.split(key)
        return {
            # ONE leaf: the embedding is the head (``tie_word_embeddings``).  Rows of ``1 / sqrt(dim)``: the
            # stream starts as 12 E[token] and ends, normed, against E^T, so at rows of unit variance a token's
            # logit for ITSELF is near ``dim / logits_scaling``; at these it is of order one
            "embed": decoder.seeded(k_embed, (cfg.vocab_size, cfg.dim), cfg.dim, cfg.dtype),
            "groups": decoder.init_runs(self._init_layer, k_layers, self.groups),
            "final_norm": jnp.ones((cfg.dim,), jnp.float32),
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

    # no state the optimizer does not own
    def state_mask(self) -> Any:
        return jax.tree_util.tree_map(lambda _: False, self.param_specs())

    def advance_state(self, state: List[jax.Array], signal: List[jax.Array]) -> List[jax.Array]:
        return state

    @staticmethod
    def summary_stats(summary: np.ndarray) -> Dict[str, Any]:
        """``objective``'s summary on the host, as the flight event's detail:
        ``decay_min``, the most negative ``-dt exp(A_log)`` of the step's scans."""
        return dict(zip(SUMMARY_FIELDS, np.asarray(summary, np.float64).reshape(-1).tolist()))

    # ------------------------------------------------------------------
    # mixers
    # ------------------------------------------------------------------

    def _kernel_refusal(self, seq: int) -> Optional[str]:
        """Why the Mosaic kernels do NOT apply, or None when they do."""
        return decoder.kernel_refusal(seq, self.mesh, chunk=self.config.chunk)

    @part("mixer_glue")
    def _mamba(self, h: jax.Array, w: Dict[str, jax.Array], kernels: bool) -> Tuple[jax.Array, jax.Array]:
        """``(the mixer's output, the most negative log decay a token)``."""
        cfg = self.config
        B, S, _ = h.shape
        H, inner, GN = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state
        z, xbc, r = jnp.split(decoder.proj(h, w["w_in"]), [inner, inner + cfg.ssm_conv_width], axis=-1)
        with part("mixer_conv"):
            xbc = decoder.short_conv_silu(xbc, w["conv"], w["conv_bias"])
        x, Bm, Cm = jnp.split(xbc, [inner, inner + GN], axis=-1)
        groups = lambda a: a.reshape(B, S, cfg.ssm_groups, cfg.ssm_state)  # noqa: E731
        dt = jax.nn.softplus(r.astype(jnp.float32) + w["dt_bias"])
        decay_min = jax.lax.stop_gradient(jnp.min(-dt * jnp.exp(w["A_log"])))
        operands = (x.reshape(B, S, H, cfg.ssm_head_dim), dt, w["A_log"], groups(Bm), groups(Cm), w["D"])
        if kernels:
            y = ssd.ssd_chunked(*operands, chunk=cfg.chunk, interpret=decoder.assumed_backend() != "tpu")
        else:
            y = ssd.ssd_chunked_plain(*operands, chunk=cfg.chunk)
        with part("mixer_gate"):
            # gate, then the norm over each group's channels
            y = y.reshape(B, S, inner).astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
            y = decoder.rms_norm(y.reshape(B, S, cfg.ssm_groups, -1), 1.0, cfg.norm_eps).reshape(B, S, inner)
            y = (y * w["o_norm"]).astype(h.dtype)
        return decoder.proj(y, w["w_out"]), decay_min

    @part("mixer_glue")
    def _attention(self, h: jax.Array, w: Dict[str, jax.Array], kernels: bool) -> Tuple[jax.Array, jax.Array]:
        """``(the mixer's output, 0: no scan here)``."""
        cfg = self.config
        B, S, _ = h.shape
        H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q = decoder.proj(h, w["wq"]).reshape(B, S, H, hd)
        k = decoder.proj(h, w["wk"]).reshape(B, S, KV, hd)
        v = decoder.proj(h, w["wv"]).reshape(B, S, KV, hd)
        if kernels:
            block_q, block_k = decoder.flash_blocks(S)
            o = flash.flash_attention(
                q, k, v, causal=True, sm_scale=cfg.attention_multiplier, block_q=block_q, block_k=block_k,
                interpret=decoder.assumed_backend() != "tpu",
            )
        else:
            grouped = q.reshape(B, S, KV, H // KV, hd)
            scores = jnp.einsum("bqgrd,bkgd->bgrqk", grouped, k).astype(jnp.float32) * cfg.attention_multiplier
            scores = jnp.where(jnp.tril(jnp.ones((S, S), bool)), scores, -1e30)
            o = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, axis=-1).astype(q.dtype), v)
        return decoder.proj(o.reshape(B, S, H * hd), w["wo"]), jnp.zeros((), jnp.float32)

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------

    def _block(self, x: jax.Array, w: Dict[str, Any], kind: str, kernels: bool) -> Tuple[jax.Array, jax.Array]:
        """One layer on the float32 stream, its two residual sublayers: ``(x,
        the most negative log decay of its scan)``."""
        cfg = self.config
        with part("stream"):
            h = decoder.rms_norm(x, w["norm"], cfg.norm_eps).astype(cfg.dtype)
        mixed, decay_min = (self._mamba if kind == "mamba" else self._attention)(h, w["mixer"], kernels)
        with part("stream"):
            x = x + cfg.residual_multiplier * mixed.astype(x.dtype)
            h = decoder.rms_norm(x, w["post_norm"], cfg.norm_eps).astype(cfg.dtype)
        with part("ffn"):
            out = swiglu(*jnp.split(h @ w["ffn"]["w_gate_up"], 2, axis=-1), 0.0) @ w["ffn"]["w_down"]
        with part("stream"):
            return x + cfg.residual_multiplier * out.astype(x.dtype), decay_min

    def _trunk(self, params: Dict[str, Any], tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """tokens [B, S] → (the float32 stream after the last layer, the
        step's summary)."""
        cfg = self.config
        refusal = self._kernel_refusal(tokens.shape[1])
        kernels = refusal is None
        with part("embed"):
            x = params["embed"][tokens].astype(jnp.float32) * cfg.embedding_multiplier
        lows = []
        for (kind, depth), stacked in zip(self.groups, params["groups"]):
            x, low = decoder.scan_run(
                lambda carry, w, kind=kind: self._block(carry, w, kind, kernels), x, stacked, depth, keep=KEPT_NAMES
            )
            lows.append(low)
        decoder.kernel_path(self, KERNEL_PATH, refusal)
        # the stream as it stands before whatever head reads it: ``loss`` takes the head in blocks and
        # ``apply`` whole, two programs that the harness ties to 2e-5, and without the barrier XLA fuses
        # the last residual add into each head's norm in its own way (``models/sambay.py``; PERF.md
        # section 6, PR 63)
        x = jax.lax.optimization_barrier(x)
        with part("head"):
            return x, jnp.min(jnp.concatenate(lows), keepdims=True)

    def _head_input(self, params: Dict[str, Any], x: jax.Array) -> jax.Array:
        return decoder.rms_norm(x, params["final_norm"], self.config.norm_eps).astype(self.config.dtype)

    def _logits(self, embed: jax.Array, x: jax.Array) -> jax.Array:
        """``x E^T / logits_scaling``, the products' float32 sums as they are:
        a logit is never rounded to the model's dtype."""
        logits = jax.lax.dot_general(x, embed, (((x.ndim - 1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        return logits * (1.0 / self.config.logits_scaling)

    def apply(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """tokens [B, S] → logits [B, S, vocab] (fp32), whole."""
        x, _ = self._trunk(params, tokens)
        with part("head"):
            return self._logits(params["embed"], self._head_input(params, x))

    def _losses(self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]) -> Tuple[jax.Array, jax.Array]:
        tokens, targets = batch
        x, summary = self._trunk(params, tokens)
        with part("head"):
            nll = decoder.blocked_nll(
                functools.partial(self._logits, params["embed"]), self._head_input(params, x), targets, self.config.head_block
            )
            return jnp.mean(nll), summary

    def loss(self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]) -> jax.Array:
        """The mean next-token cross-entropy; batch = (tokens, targets)."""
        return self._losses(params, batch)[0]

    def objective(
        self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]
    ) -> Tuple[jax.Array, Tuple[List[jax.Array], jax.Array]]:
        """What a training step differentiates (the loss), no signal (the
        model has no state of its own) and the step's summary."""
        loss, summary = self._losses(params, batch)
        return loss, ([], summary)
