"""A dense decoder whose stack of layers is run several times a step with ONE
set of weights, a head and an exit gate after every pass, and the expected
loss over the exit step as what a step differentiates; for training on one
chip's share.

The published configuration this was built for is Ouro-2.6B's (``model_type``
``ouro``, ``total_ut_steps`` 4).  With ``E`` the embedding, ``T`` passes, ``L``
layers and ``N`` an RMSNorm (float32 statistics, a learned weight started at 1):

- ``x_0 = E[tokens]``; pass ``t`` runs the SAME ``L`` layers over ``x_{t-1}``,
  a layer being ``a = h + N2(Attn(N1(h)))``, ``h = a + N4(MLP(N3(a)))``: a norm
  before AND after each half, inside the residual.  ``Attn`` is causal
  multi-head attention (q, k, v, o without bias, rope over all of a head's
  channels, the halves paired, the same positions in every pass), ``MLP`` a
  SwiGLU;
- ``x_t = Nf(h)``: the model's ONE final norm, after EVERY pass, is what the
  head reads AND what pass ``t + 1`` starts from;
- ``logits_t = x_t W_head`` and ``z_t = x_t . w_g + b_g`` in float32,
  ``lambda_t = sigmoid(z_t)``.  A position's exit distribution over ``t`` is
  ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` and ``p_T = prod_{j<T} (1 -
  lambda_j)``: ``lambda_T`` is computed and unused, no gradient reaches the
  gate through it.

``apply`` gives ``logits_T`` and ``loss`` is the mean of pass ``T``'s
cross-entropy ALONE (inference never leaves early at the published
``early_exit_threshold`` of 1): it is what the mean of ``apply``'s
cross-entropy is, and the benchmark ties the two (``ftbench/harness.py``
``forward_passes``).  ``objective``, what a training step differentiates, is
``mean_i [sum_t p_t[i] nll_t[i] - beta H(p[i])]`` with ``H`` the entropy of a
position's exit distribution, differentiated whole (through ``p`` and through
``nll``).  The step's summary is every pass's mean cross-entropy, the mean
exit distribution and the mean entropy, which ``summary_stats`` hands the
step's flight event (``pass_nll``, ``exit_p``, ``exit_entropy``).  The second,
gate-only training phase of the model's public description (a frozen language
model under an adaptive exit loss) is NOT part of the step.  The model has no
state the optimizer does not own.

What is the model's and what a kernel's: projections, norms, rope, the gate
and the objective are here, plain ``jax.numpy`` over ``models/decoder.py``'s helpers; the
attention is ``ops/flash_attention.py``'s (``flash_fwd``, ``flash_dq``,
``flash_dkv``), ``L x T`` calls of each a step.  ``attention_path`` is
``"flash"`` only if the kernels ran; off the TPU the layers take plain
attention and the path is named ``"plain: <why>"``.

**One set of leaves.**  The layers are stacked ``[L, ...]`` and scanned; the
passes are a ``lax.scan`` AROUND that scan with the stacked leaves closed
over, never four copies: a leaf's gradient is the sum over its ``T`` uses, made
inside the compiled step by the pass scan's transposition, which adds a
pass's contribution to the running sum in the LEAF's dtype (bfloat16 at the
published precision: three roundings a leaf, PERF.md section 6, PR 59).  A
layer is rematerialised in the backward pass but for its input and what flash
made (``ops/flash_attention.py`` ``KEPT_NAMES``: ``o`` and one number a row),
``L x T`` of each a step, so that ``flash_fwd`` stands once a layer
application; the head and its cross-entropy run over blocks of ``head_block``
positions of all passes at once, each block's logits made again in the
backward pass and never kept, so that no ``[S, vocab]`` float32 array of a
whole pass is ever held.
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
from torchft_tpu.parallel.moe import swiglu

KERNEL_PATH = "flash"


@dataclass(frozen=True)
class LoopedConfig:
    vocab_size: int = 49_152
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 16
    head_dim: int = 128
    ffn_hidden: int = 5632
    n_passes: int = 4  # ``total_ut_steps``
    entropy_beta: float = 0.05
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-6
    head_block: int = 4096  # positions of the head and its cross-entropy at a time
    dtype: Any = jnp.bfloat16


def looped_debug(**over: Any) -> LoopedConfig:
    """Tiny widths, and a head in blocks shorter than the tests' sequences."""
    return replace(
        LoopedConfig(
            vocab_size=96, dim=64, n_layers=2, n_heads=4, head_dim=16, ffn_hidden=128, head_block=32,
            dtype=jnp.float32,
        ),
        **over,
    )


class Looped:
    def __init__(self, config: LoopedConfig, mesh: Optional[Any] = None) -> None:
        self.config = config
        self.mesh = mesh
        if config.head_dim % 2 or config.n_passes < 1 or config.head_block < 1:
            raise ValueError("rope pairs a head's halves, the stack runs once at least, a head block holds a position")
        # set when the layers are traced: KERNEL_PATH or "plain: <why>"
        self.attention_path: Optional[str] = None

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------

    def _init_layer(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        D, F, A = cfg.dim, cfg.ffn_hidden, cfg.n_heads * cfg.head_dim
        keys = jax.random.split(key, 7)

        def normal(k, shape):
            return decoder.seeded(k, shape, shape[0], cfg.dtype)

        ones = jnp.ones((D,), jnp.float32)
        return {
            # the sandwich: a norm before and after each half, inside the residual
            "norms": {"mixer_in": ones, "mixer_out": ones, "ffn_in": ones, "ffn_out": ones},
            "wq": normal(keys[0], (D, A)), "wk": normal(keys[1], (D, A)), "wv": normal(keys[2], (D, A)),
            "wo": normal(keys[3], (A, D)),
            "w_gate": normal(keys[4], (D, F)), "w_up": normal(keys[5], (D, F)), "w_down": normal(keys[6], (F, D)),
        }

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        k_embed, k_out, k_gate, k_layers = jax.random.split(key, 4)
        scale = cfg.dim ** -0.5
        embed, lm_head = decoder.embed_and_head(k_embed, k_out, cfg.vocab_size, cfg.dim, cfg.dtype, embed_std=scale)
        return {
            "embed": embed,
            # ONE stack: every pass reads these leaves
            "layers": jax.vmap(self._init_layer)(jax.random.split(k_layers, cfg.n_layers)),
            "final_norm": jnp.ones((cfg.dim,), jnp.float32),
            "lm_head": lm_head,
            # float32 as the norms are: the gate's logit is of order one at
            # the seeded start (``x_t`` leaves a norm), its bias 0
            "gate": {"w": scale * jax.random.normal(k_gate, (cfg.dim,), jnp.float32), "b": jnp.zeros((1,), jnp.float32)},
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

    @staticmethod
    def summary_stats(summary: np.ndarray) -> Dict[str, Any]:
        """``objective``'s summary on the host, as the flight event's detail:
        ``pass_nll`` (a pass's mean cross-entropy, first pass first),
        ``exit_p`` (the mean exit distribution) and ``exit_entropy`` (the mean
        entropy of a position's exit distribution, nats)."""
        flat = np.asarray(summary, np.float64).reshape(-1)
        passes = (flat.size - 1) // 2
        return dict(
            pass_nll=flat[:passes].tolist(), exit_p=flat[passes : 2 * passes].tolist(), exit_entropy=float(flat[-1]),
        )

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------

    def _kernel_refusal(self, seq: int) -> Optional[str]:
        """Why the Mosaic kernels do NOT apply, or None when they do."""
        return decoder.kernel_refusal(seq, self.mesh)

    def _attention(self, h: jax.Array, w: Dict[str, jax.Array], rope: Tuple[jax.Array, jax.Array], kernels: bool) -> jax.Array:
        cfg = self.config
        B, S, _ = h.shape
        H, hd = cfg.n_heads, cfg.head_dim
        with part("mixer_proj"):
            q, k, v = ((h @ w[name]).reshape(B, S, H, hd) for name in ("wq", "wk", "wv"))
        with part("mixer_glue"):
            q, k = decoder.apply_rope(q, *rope), decoder.apply_rope(k, *rope)
            if kernels:
                block_q, block_k = decoder.flash_blocks(S)
                o = flash.flash_attention(
                    q, k, v, causal=True, block_q=block_q, block_k=block_k,
                    interpret=decoder.assumed_backend() != "tpu",
                )
            else:
                scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * hd ** -0.5
                seen = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
                o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1).astype(q.dtype), v)
        with part("mixer_proj"):
            return o.reshape(B, S, H * hd) @ w["wo"]

    def _block(self, x: jax.Array, w: Dict[str, Any], rope: Tuple[jax.Array, jax.Array], kernels: bool) -> jax.Array:
        """One layer on the stream: both halves between two norms."""
        norm = lambda a, name: decoder.rms_norm(a, w["norms"][name], self.config.norm_eps)  # noqa: E731
        with part("stream"):
            h = norm(x, "mixer_in")
        mixed = self._attention(h, w, rope, kernels)
        with part("stream"):
            x = x + norm(mixed, "mixer_out")
            h = norm(x, "ffn_in")
        with part("ffn"):
            out = swiglu(h @ w["w_gate"], h @ w["w_up"], 0.0) @ w["w_down"]
        with part("stream"):
            return x + norm(out, "ffn_out")

    def _passes(self, params: Dict[str, Any], tokens: jax.Array, layers_by_pass: Optional[Any] = None) -> jax.Array:
        """tokens [B, S] → ``x_1 .. x_T`` [T, B, S, D]: the final norm of the
        stream after every pass.  ``layers_by_pass`` (the stacked leaves with
        one more leading axis of ``T``) gives every pass leaves of its own, so
        that a gradient with respect to it is each pass's contribution alone
        (``tests/test_looped.py``, ``ftbench/tests/loop_forward_check.py``);
        a step closes over ``params["layers"]``, one set for all passes."""
        cfg = self.config
        S = tokens.shape[1]
        refusal = self._kernel_refusal(S)
        kernels = refusal is None
        with part("embed"):
            x = params["embed"][tokens].astype(cfg.dtype)
        with part("mixer_glue"):
            rope = decoder.rope_table(S, cfg.head_dim, cfg.rope_theta)  # the same positions in every pass
        # a layer keeps its input and what flash made and runs the rest again
        # in the backward pass: L x T inputs a step (2 GiB at eight layers,
        # four passes and 16,384 positions) and as many of ``o`` and its row
        # statistics (67 + 1 MB each, 2.2 GB) beside the state.  The pass is
        # the outer loop and the stack the inner one, so the two scans are
        # written here
        layer = decoder.remat(
            lambda carry, w: (self._block(carry, w, rope, kernels), None), cfg.n_layers, keep=flash.KEPT_NAMES
        )

        def one_pass(x, own_layers):
            h, _ = jax.lax.scan(layer, x, params["layers"] if own_layers is None else own_layers)
            with part("stream"):
                x = decoder.rms_norm(h, params["final_norm"], cfg.norm_eps)
            return x, x

        with part("layers"):
            _, x_all = jax.lax.scan(one_pass, x, layers_by_pass, length=cfg.n_passes)
        decoder.kernel_path(self, KERNEL_PATH, refusal)
        return x_all

    def _logits(self, params: Dict[str, Any], x: jax.Array) -> jax.Array:
        """The products' float32 sums as they are: a logit is never rounded
        to the model's dtype."""
        return jnp.dot(x, params["lm_head"], preferred_element_type=jnp.float32)

    def _pass_nll(self, params: Dict[str, Any], x: jax.Array, targets: jax.Array) -> jax.Array:
        """The cross-entropy of every position of the passes ``x`` [t, B, S,
        D] holds, [t, B, S] float32, ``head_block`` positions at a time: a
        block's logits are made again in the backward pass, never kept, and
        no pass's ``[S, vocab]`` is ever whole."""
        return decoder.blocked_nll(functools.partial(self._logits, params), x, targets, self.config.head_block)

    @part("loop_gate")
    def _exit_log_p(self, params: Dict[str, Any], x_all: jax.Array) -> jax.Array:
        """``log p_t`` of every position, [T, B, S] float32, from ``log lambda
        = -softplus(-z)`` and ``log (1 - lambda) = -softplus(z)``.  The last
        pass's ``lambda`` is not read: what is left after ``T - 1`` refusals
        to leave exits there."""
        gate = params["gate"]
        z = jnp.einsum("tbsd,d->tbs", x_all[:-1].astype(jnp.float32), gate["w"]) + gate["b"]
        stayed = jnp.cumsum(-jax.nn.softplus(z), axis=0)  # log prod_{j <= t} (1 - lambda_j)
        zero = jnp.zeros((1, *x_all.shape[1:3]), jnp.float32)
        return jnp.concatenate([zero, stayed]) + jnp.concatenate([-jax.nn.softplus(-z), zero])

    def apply_all(self, params: Dict[str, Any], tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """tokens [B, S] → (every pass's logits [T, B, S, vocab], every
        position's exit distribution [T, B, S]), float32; whole, so for the
        tests and the chip's forward check at a size that holds them."""
        x_all = self._passes(params, tokens)
        with part("head"):
            logits = self._logits(params, x_all)
        return logits, jnp.exp(self._exit_log_p(params, x_all))

    def apply(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """tokens [B, S] → the last pass's logits [B, S, vocab] (fp32)."""
        x_all = self._passes(params, tokens)
        with part("head"):
            return self._logits(params, x_all[-1])

    def loss(self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array]) -> jax.Array:
        """The last pass's mean next-token cross-entropy, and nothing else:
        the mean of ``apply``'s cross-entropy; batch = (tokens, targets)."""
        tokens, targets = batch
        x_all = self._passes(params, tokens)
        with part("head"):
            return jnp.mean(self._pass_nll(params, x_all[-1:], targets))

    def pass_losses(
        self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array], layers_by_pass: Optional[Any] = None
    ) -> Tuple[jax.Array, jax.Array]:
        """(every pass's cross-entropy of every position, ``log p`` of every
        position), both [T, B, S] float32."""
        tokens, targets = batch
        x_all = self._passes(params, tokens, layers_by_pass)
        with part("head"):
            nll = self._pass_nll(params, x_all, targets)
        return nll, self._exit_log_p(params, x_all)

    def objective(
        self, params: Dict[str, Any], batch: Tuple[jax.Array, jax.Array], layers_by_pass: Optional[Any] = None
    ) -> Tuple[jax.Array, Tuple[List[jax.Array], jax.Array]]:
        """What a training step differentiates (the mean over the positions
        of the expected loss under the exit distribution less ``beta`` times
        its entropy), no signal (the model has no state of its own) and the
        step's summary: ``T`` mean cross-entropies, ``T`` mean exit
        probabilities, the mean entropy."""
        nll, log_p = self.pass_losses(params, batch, layers_by_pass)
        with part("loop_gate"):
            p = jnp.exp(log_p)
            entropy = -jnp.mean(jnp.sum(p * log_p, axis=0))
            summary = jnp.concatenate([jnp.mean(nll, axis=(1, 2)), jnp.mean(p, axis=(1, 2)), entropy.reshape(1)])
            return jnp.mean(jnp.sum(p * nll, axis=0)) - self.config.entropy_beta * entropy, ([], summary)
