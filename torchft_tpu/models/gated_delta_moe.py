"""A decoder whose mixers are Gated DeltaNet layers with a gated softmax-
attention layer every few, over routed experts with a gated shared one; for
training on one chip's share.

The published configuration this was built for is Qwen3-Next-80B-A3B's
(``model_type`` ``qwen3_next``).  Layer ``i`` mixes with full attention where
``(i + 1) % full_attention_interval == 0`` and with Gated DeltaNet otherwise;
every layer's feed-forward part is ``parallel/moe.py`` ``RoutedExperts``, told
which experts are here.  Every norm of the stream and the attention's two head
norms weigh with ``1 + w``, ``w`` from 0; the DeltaNet head norm's weight is
plain, from 1.  A layer is ``x += Mixer(N(x))``, ``x += Experts(N(x))``:

- Gated DeltaNet (arXiv:2412.06464): ``[q | k | v | z] = h W_qkvz``, ``[b | a]
  = h W_ba`` (the columns in that order, a head's channels together: the
  published checkpoint groups both by key head, a permutation of columns); a
  causal depthwise convolution of ``conv_kernel`` taps over q, k and v as ONE
  array, then SiLU; ``linear_key_heads`` query and key heads under
  ``linear_value_heads`` value heads, value head ``j`` with key head ``j //
  (value heads / key heads)``; q and k of unit length a head, q times
  ``dk^-0.5``; a value head's ``beta = sigmoid(b)`` and its log decay ``g =
  -exp(A_log) * softplus(a + dt_bias)``, ONE number a head and token with no
  lower bound; the delta rule (``ops/gdn.py``); ``y = RMSNorm(o) * SiLU(z)``
  over a head's channels, norm first; out ``= y W_o``.
- Full attention: ``[q | gate] = h W_q`` (the query's columns, then the
  gate's; published: interleaved a head), ``k``, ``v`` of ``n_kv_heads``; q and
  k through an RMSNorm a head; rope over the first ``rotary_dim`` channels of a
  head, channel ``i`` paired with ``i + rotary_dim / 2``, the others pass;
  causal grouped-query attention; ``o * sigmoid(gate)``, a channel's own; out
  ``= o W_o``.
- Experts: a float32 softmax over all ``num_experts``, the ``top_k`` best,
  renormalised; the experts held; the shared expert behind ``sigmoid(h .
  w_s)``; Switch's balance loss a sequence.

What is the model's and what a kernel's: projections, the convolution, norms,
``softplus`` and the decay, rope and both gates are here, plain ``jax.numpy``;
the chunked delta rule is ``ops/gdn.py``'s (``gdn_fwd``, ``gdn_bwd``), the full
layers' attention ``ops/flash_attention.py``'s at heads of 256, the experts'
grouped products ``megablox.gmm``.  ``attention_path`` is ``"gdn+flash"`` only
if every layer took its kernels and every expert layer the grouped kernel; off
the TPU the same chunk algebra runs as plain ``jax.numpy`` beside plain
attention and ``lax.ragged_dot`` and the path is named ``"plain: <why>"``.

Contiguous layers of one kind are stacked and run under one ``lax.scan``, a
layer rematerialised in the backward pass but for its float32 input and, on a
FULL layer, what flash made (``flash.KEPT_NAMES``: 134 + 1 MB a layer at 16,384
positions and 16 heads of 256), so that ``flash_fwd`` stands once a full layer
in a step's program; a DeltaNet layer keeps nothing and ``gdn_fwd`` runs twice
(the states its backward reads are 537 MB a layer).

The residual stream is float32 whatever the matrices' dtype, and the router
reads its float32 norm, as ``IndexedSparseMoE``'s: which 10 of 512 experts a
token takes is a step function of what the router reads.

``loss`` is the next-token cross-entropy; ``objective`` (what a training step
differentiates) adds the routers' balance loss and gives ``HSDPTrainer`` the
step's summary: a layer's rows on the held experts, their largest and mean
load, the buffer's rows, and the most negative log decay a token had
(``decay_min``: 0 for a full layer).  No leaf is state the optimizer does not
own.  No multi-token-prediction module is built: the published ``config.json``
describes none.
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
from torchft_tpu.parallel.moe import ROUTE_FIELDS, RoutedExperts, RoutedExpertsConfig

KERNEL_PATH = "gdn+flash"
GDN_CHUNK = 64  # tokens a chunk of the delta rule (ops/gdn.py)
# a step's summary, one row a layer (``step_summary``, ``summary_stats``)
SUMMARY_FIELDS = (*ROUTE_FIELDS, "decay_min")


@dataclass(frozen=True)
class GatedDeltaMoEConfig:
    vocab_size: int = 151_936
    dim: int = 2048
    n_layers: int = 48
    full_attention_interval: int = 4
    n_heads: int = 16  # full attention's
    n_kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64  # ``head_dim * partial_rotary_factor``
    rope_theta: float = 10_000_000.0
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    conv_kernel: int = 4
    # ``A_log`` starts at ``log(U(0, decay_init_max))`` and ``dt_bias`` at
    # ``dt_bias_init`` (the published modelling code's; not in ``config.json``)
    decay_init_max: float = 16.0
    dt_bias_init: float = 1.0
    num_experts: int = 512
    experts_held: Tuple[int, int] = (0, 512)  # (first, count): this chip's share
    top_k: int = 10
    expert_hidden: int = 512
    shared_hidden: int = 512
    norm_topk_prob: bool = True
    balance_loss_weight: float = 1e-3
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def kinds(self) -> List[str]:
        """``"full"`` or ``"gdn"`` a layer."""
        return ["full" if (i + 1) % self.full_attention_interval == 0 else "gdn" for i in range(self.n_layers)]

    def groups(self) -> List[Tuple[str, int]]:
        """Runs of contiguous layers of one kind: (kind, how many)."""
        return decoder.runs(self.kinds())


def gated_delta_debug(**over: Any) -> GatedDeltaMoEConfig:
    """Tiny widths in the published pattern (one period), two value heads a
    key head, a quarter of a head rotated; for tests."""
    return replace(
        GatedDeltaMoEConfig(
            vocab_size=256, dim=64, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=32, rotary_dim=8,
            linear_key_heads=2, linear_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=16,
            num_experts=16, experts_held=(4, 4), top_k=4, expert_hidden=32, shared_hidden=32,
            dtype=jnp.float32,
        ),
        **over,
    )


class GatedDeltaMoE:
    def __init__(self, config: GatedDeltaMoEConfig, mesh: Optional[Any] = None) -> None:
        self.config = config
        self.mesh = mesh
        cfg = config
        if (
            cfg.n_heads % cfg.n_kv_heads or cfg.linear_value_heads % cfg.linear_key_heads
            or cfg.rotary_dim % 2 or cfg.rotary_dim > cfg.head_dim
        ):
            raise ValueError(
                "query heads divide into KV heads, value heads into key heads, rope pairs the halves of a head's first channels"
            )
        self.groups = cfg.groups()
        self.moe = RoutedExperts(
            RoutedExpertsConfig(
                dim=cfg.dim, expert_hidden=cfg.expert_hidden, num_experts=cfg.num_experts,
                experts_held=tuple(cfg.experts_held), top_k=cfg.top_k, score_func="softmax",
                selection_bias=False, norm_topk_prob=cfg.norm_topk_prob, shared_hidden=cfg.shared_hidden,
                gated_shared=True, balance_loss_weight=cfg.balance_loss_weight, dtype=cfg.dtype,
            )
        )
        # set when the layers are traced: KERNEL_PATH or "plain: <why>"
        self.attention_path: Optional[str] = None

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------

    def _init_mixer(self, kind: str, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        D = cfg.dim
        keys = jax.random.split(key, 5)

        normal = functools.partial(decoder.seeded, dtype=cfg.dtype)

        if kind == "full":
            q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
            return {
                "wq": normal(keys[0], (D, 2 * q), D),  # [query | gate], a head's channels together
                "wk": normal(keys[1], (D, kv), D), "wv": normal(keys[2], (D, kv), D),
                "wo": normal(keys[3], (q, D), q),
                # weights of the form 1 + w
                "q_norm": jnp.zeros((cfg.head_dim,), jnp.float32), "k_norm": jnp.zeros((cfg.head_dim,), jnp.float32),
            }
        keyed = cfg.linear_key_heads * cfg.linear_key_head_dim
        valued = cfg.linear_value_heads * cfg.linear_value_head_dim
        K = cfg.conv_kernel
        return {
            "w_qkvz": normal(keys[0], (D, 2 * keyed + 2 * valued), D),  # [q | k | v | z]
            "w_ba": normal(keys[1], (D, 2 * cfg.linear_value_heads), D),  # [b | a]
            "conv": normal(keys[2], (K, 2 * keyed + valued), K),  # over [q | k | v]
            "a_log": jnp.log(
                jax.random.uniform(
                    keys[3], (cfg.linear_value_heads,), jnp.float32, minval=1e-3, maxval=cfg.decay_init_max
                )
            ),
            "dt_bias": jnp.full((cfg.linear_value_heads,), cfg.dt_bias_init, jnp.float32),
            "o_norm": jnp.ones((cfg.linear_value_head_dim,), jnp.float32),  # plain, the heads share it
            "wo": normal(keys[4], (valued, D), valued),
        }

    def _init_layer(self, kind: str, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        k_mixer, k_ffn = jax.random.split(key)
        return {
            # weights of the form 1 + w
            "attn_norm": jnp.zeros((cfg.dim,), jnp.float32),
            "mlp_norm": jnp.zeros((cfg.dim,), jnp.float32),
            "mixer": self._init_mixer(kind, k_mixer),
            "ffn": self.moe.init(k_ffn),
        }

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        k_embed, k_out, k_layers = jax.random.split(key, 3)
        return {
            # rows of unit variance, as ``IndexedSparseMoE``'s and for its reason
            "embed": jax.random.normal(k_embed, (cfg.vocab_size, cfg.dim), jnp.float32).astype(cfg.dtype),
            "groups": decoder.init_runs(self._init_layer, k_layers, self.groups),
            "final_norm": jnp.zeros((cfg.dim,), jnp.float32),
            "lm_head": (
                jax.random.normal(k_out, (cfg.dim, cfg.vocab_size), jnp.float32) / np.sqrt(cfg.dim)
            ).astype(cfg.dtype),
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

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------

    def _kernel_refusal(self, seq: int) -> Optional[str]:
        """Why the Mosaic kernels do NOT apply, or None when they do."""
        return decoder.kernel_refusal(seq, self.mesh, chunk=GDN_CHUNK)

    def _normed(self, x: jax.Array, w: jax.Array) -> jax.Array:
        """The RMS norm under the weight ``1 + w``, in x's dtype."""
        return decoder.rms_norm(x, 1.0 + w, self.config.norm_eps)

    @part("mixer_glue")
    def _delta_net(self, h: jax.Array, w: Dict[str, jax.Array], kernels: bool) -> Tuple[jax.Array, jax.Array]:
        """``(the mixer's output, the most negative log decay a token had)``."""
        from torchft_tpu.ops.gdn import gdn_chunked, gdn_chunked_plain

        cfg = self.config
        B, S, _ = h.shape
        Hk, Hv = cfg.linear_key_heads, cfg.linear_value_heads
        keyed, valued = Hk * cfg.linear_key_head_dim, Hv * cfg.linear_value_head_dim
        qkvz = decoder.proj(h, w["w_qkvz"])
        ba = decoder.proj(h, w["w_ba"]).astype(jnp.float32)
        qkv = decoder.short_conv_silu(qkvz[..., : 2 * keyed + valued], w["conv"])
        q = decoder.unit(qkv[..., :keyed].reshape(B, S, Hk, -1))
        k = decoder.unit(qkv[..., keyed : 2 * keyed].reshape(B, S, Hk, -1))
        v = qkv[..., 2 * keyed :].reshape(B, S, Hv, -1)
        z = qkvz[..., 2 * keyed + valued :].reshape(B, S, Hv, -1)
        beta = jax.nn.sigmoid(ba[..., :Hv])
        # the log of the decay: one number a value head and token, unbounded below
        g = -jnp.exp(w["a_log"]) * jax.nn.softplus(ba[..., Hv:] + w["dt_bias"])
        if kernels:
            o = gdn_chunked(q, k, v, g, beta, chunk=GDN_CHUNK, interpret=decoder.assumed_backend() != "tpu")
        else:
            o = gdn_chunked_plain(q, k, v, g, beta, chunk=GDN_CHUNK)
        # norm first, then the gate
        o = decoder.rms_norm(o, w["o_norm"], cfg.norm_eps).astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        return decoder.proj(o.astype(h.dtype).reshape(B, S, valued), w["wo"]), jax.lax.stop_gradient(jnp.min(g))

    @part("mixer_glue")
    def _attention(self, h: jax.Array, w: Dict[str, jax.Array], kernels: bool) -> jax.Array:
        cfg = self.config
        B, S, _ = h.shape
        H, KV, hd, rot = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rotary_dim
        q_gate = decoder.proj(h, w["wq"])
        q = self._normed(q_gate[..., : H * hd].reshape(B, S, H, hd), w["q_norm"])
        k = self._normed(decoder.proj(h, w["wk"]).reshape(B, S, KV, hd), w["k_norm"])
        v = decoder.proj(h, w["wv"]).reshape(B, S, KV, hd)
        gate = q_gate[..., H * hd :]
        turn = lambda a: jnp.concatenate([decoder.rope_halves(a[..., :rot], cfg.rope_theta), a[..., rot:]], axis=-1)  # noqa: E731
        q, k = turn(q), turn(k)
        if kernels:
            block_q, block_k = decoder.flash_blocks(S)
            o = flash.flash_attention(
                q, k, v, causal=True, block_q=block_q, block_k=block_k,
                interpret=decoder.assumed_backend() != "tpu",
            )
        else:
            grouped = q.reshape(B, S, KV, H // KV, hd)
            scores = jnp.einsum("bqgrd,bkgd->bgrqk", grouped, k).astype(jnp.float32) / np.sqrt(hd)
            scores = jnp.where(jnp.arange(S)[None, :] <= jnp.arange(S)[:, None], scores, -1e30)
            o = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, axis=-1).astype(q.dtype), v)
        o = o.reshape(B, S, H * hd).astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))
        return decoder.proj(o.astype(h.dtype), w["wo"])

    def _block(
        self, x: jax.Array, w: Dict[str, Any], kind: str, kernels: bool
    ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array, jax.Array]]:
        """One layer: ``(x, (load [E], balance loss, decay_min))``."""
        cfg = self.config
        with part("stream"):
            h = self._normed(x, w["attn_norm"]).astype(cfg.dtype)
        if kind == "full":
            mixed, decay_min = self._attention(h, w["mixer"], kernels), jnp.zeros((), jnp.float32)
        else:
            mixed, decay_min = self._delta_net(h, w["mixer"], kernels)
        with part("stream"):
            x = x + mixed
            # the router reads the float32 norm itself
            h = self._normed(x, w["mlp_norm"])
        out, load, balance = self.moe.apply(w["ffn"], h)
        with part("stream"):
            return x + out, (load, balance, decay_min)

    def _trunk(self, params: Dict[str, Any], tokens: jax.Array) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
        """tokens [B, S] → (the residual stream after the last layer, a
        layer's (loads [L, E], balance loss [L], decay_min [L]))."""
        refusal = self._kernel_refusal(tokens.shape[1])
        kernels = refusal is None
        with part("embed"):
            x = params["embed"][tokens].astype(jnp.float32)  # the residual stream
        per_group = []
        for (kind, depth), stacked in zip(self.groups, params["groups"]):
            # kept through a layer's rematerialisation: its float32 input and,
            # on a FULL layer, flash's output and row statistics, so that the
            # dear ``flash_fwd`` stands once in a step (as ``WindowedMoE``'s)
            x, per_layer = decoder.scan_run(
                lambda carry, w, kind=kind: self._block(carry, w, kind, kernels), x, stacked, depth,
                keep=flash.KEPT_NAMES if kind == "full" else (),
            )
            per_group.append(per_layer)
        decoder.kernel_path(self, KERNEL_PATH, refusal, self.moe.path)
        return x, tuple(jnp.concatenate(field) for field in zip(*per_group))

    @part("head")
    def _logits(self, params: Dict[str, Any], x: jax.Array) -> jax.Array:
        cfg = self.config
        return decoder.head_logits(x, 1.0 + params["final_norm"], params["lm_head"], cfg.norm_eps, cfg.dtype)

    def apply(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """tokens [B, S] → logits [B, S, vocab] (fp32)."""
        return self._logits(params, self._trunk(params, tokens)[0])

    def _losses(self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
        tokens, targets = batch
        x, per_layer = self._trunk(params, tokens)
        return decoder.mean_nll(self._logits(params, x), targets), per_layer

    def loss(self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]) -> jax.Array:
        """Mean next-token cross-entropy; batch = (tokens, targets)."""
        return self._losses(params, batch)[0]

    def objective(
        self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]
    ) -> Tuple[jax.Array, Tuple[List[jax.Array], jax.Array]]:
        """What a training step differentiates (``loss`` and the routers'
        balance loss), no signal (no leaf here is the optimizer's to leave
        alone) and the step's summary."""
        loss, (load, balance, decay_min) = self._losses(params, batch)
        with part("head"):
            return loss + jnp.sum(balance), ([], self.step_summary(load, decay_min, batch[0].size))

    def step_summary(self, load: jax.Array, decay_min: jax.Array, tokens: int) -> jax.Array:
        """Of this replica's step of ``tokens`` tokens, on the device:
        ``[layers, 5]`` in the order of ``SUMMARY_FIELDS``: a layer's rows on
        the held experts, their largest and mean load, the buffer's rows
        (``RoutedExperts.route_summary``) and ``decay_min``."""
        return jnp.concatenate([self.moe.route_summary([load], tokens), decay_min[:, None]], axis=1)

    # :meth:`step_summary` on the host, as the flight event's detail
    summary_stats = staticmethod(functools.partial(moe.summary_stats, fields=SUMMARY_FIELDS))
