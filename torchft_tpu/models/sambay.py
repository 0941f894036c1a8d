"""A decoder-hybrid-decoder (SambaY, arXiv:2507.06607): a first half of
selective-scan and differential-attention layers, and a second half whose
layers read ONE layer's keys and values and ONE scan's output instead of
making their own; for training on one chip's share.

The published configuration this was built for is Phi-4-mini-flash-reasoning's
(``model_type`` ``phi4flash``).  With ``E`` the embedding (tied: it is the head
too) and ``LN`` a LayerNorm with weight and bias (float32 statistics):

- ``x_0 = E[tokens]``, no scaling and no position encoding anywhere; every
  layer ``a = x + Mixer(LN1(x))``, ``x' = a + W_down(silu(g) * u)`` with ``[g,
  u] = LN2(a) W_gate_up``; after the last layer ``logits = LN_f(x) E^T`` in
  float32, never rounded.
- **M**, a selective scan (Mamba's, arXiv:2312.00752): ``[u, z] = h W_in``;
  ``u = silu(conv(u) + b_c)``; ``[r, B_t, C_t] = u W_x``; ``dt = softplus(r
  W_dt + b_dt)``; ``A = -exp(A_log)``, a decay for every (channel, state)
  pair; the recurrence of ``ops/selscan.py``; ``Mixer = (y * silu(z)) W_out``.
  The LAST M of the first half also hands on ``m = y``, before the gate.
- **S** and **F**, differential attention (arXiv:2410.05258) under a window
  and whole: the query heads are pairs ``(q1, q2)_j`` = heads ``(2j, 2j + 1)``,
  the key heads pairs ``(k1, k2)_p``, the value heads pairs ``V_p = [v1; v2]``;
  query pair ``j`` reads pair ``p = j // (pairs of queries a pair of keys)``:
  ``O = softmax(q1 k1^T / sqrt(d) + mask) V - lambda softmax(q2 k2^T / sqrt(d)
  + mask) V``, ``lambda = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_0``,
  ``lambda_0 = 0.8 - 0.6 exp(-0.3 l)`` with ``l`` the layer's PUBLISHED index,
  then an RMSNorm over the pair's channels times ``1 - lambda_0`` and ``W_o``.
  F's ``K`` and ``V``, as projected, are handed on.
- **G**, a gated memory unit: ``Mixer = (m * silu(h W_1)) W_2``, ``m`` the
  first half's, the same for every G.
- **C**, cross-attention: ``q = h W_q + b``, differential attention as above
  with its own ``lambda`` vectors, pair norm and ``W_o`` over F's ``K`` and
  ``V``; in training a whole causal launch.

``pattern`` spells the layers, ``(M S) x a, M F, (G C) x b``.  The step's
summary is ``decay_min`` (the most negative ``dt_t[c] A[c, n]`` a step: 0 says
no scan ran) and ``lambda`` (the mean of the layers' ``lambda``), which
``summary_stats`` hands the step's flight event.  The model has no state the
optimizer does not own.

What is the model's and what a kernel's: projections, norms, the convolution,
softplus, gates and the combination of the two softmaxes are here, plain
``jax.numpy`` over ``models/decoder.py``'s helpers; the scan is
``ops/selscan.py``'s (``selscan_fwd``, ``selscan_bwd``) and BOTH softmaxes of a
layer are ONE launch of ``ops/flash_attention.py`` over all query heads at
heads of ``d`` for q and k and ``2 d`` for v (``flash_win_*`` under the window,
``flash_*`` whole): the heads are put in the order in which a group of the
launch is the queries that read one key head, and ``V_p`` stands once for
``k1`` and once for ``k2``.  ``attention_path`` is ``"selscan+flash"`` only if
the kernels ran; off the TPU the layers take ``selscan_plain`` and a dense
masked softmax and the path is named ``"plain: <why>"``.

**Three runs.**  The (M S) pairs are stacked and scanned, (M F) runs once, the
(G C) pairs are stacked and scanned with ``m``, ``K`` and ``V`` closed over, so
that the scan's transposition sums their cotangents over their readers.  They
are handed over in FLOAT32 (a reader rounds them back to the model's dtype
where it uses them), so that sum is a float32 sum.  A layer is rematerialised
in the backward pass but for its input and what the kernels made
(``flash.KEPT_NAMES``, ``selscan.KEPT_NAMES``); the head and its cross-entropy
run ``head_block`` positions at a time, a block's logits made again in the
backward pass (``decoder.blocked_nll``).  The tied leaf's gradient is the sum
of the gather's and the head's, made by jax.
"""

from __future__ import annotations

import functools
import re
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.models import decoder
from torchft_tpu.obs.spans import part
from torchft_tpu.ops import flash_attention as flash
from torchft_tpu.ops import selscan
from torchft_tpu.parallel.moe import swiglu

KERNEL_PATH = "selscan+flash"
KEPT_NAMES = (*flash.KEPT_NAMES, *selscan.KEPT_NAMES)
SUMMARY_FIELDS = ("decay_min", "lambda")
# the Mamba reference code's start of a scan's step: log-uniform between the
# first two, never under the third
_DT_MIN, _DT_MAX, _DT_FLOOR = 1e-3, 1e-1, 1e-4
_PATTERN = re.compile(r"^((?:MS)*)MF((?:GC)+)$")


@dataclass(frozen=True)
class SambaYConfig:
    vocab_size: int = 200_064
    dim: int = 2560
    n_heads: int = 40
    n_kv_heads: int = 20
    head_dim: int = 64
    ffn_hidden: int = 10_240
    window: int = 512
    pattern: str = "MS" * 8 + "MF" + "GC" * 7
    # the PUBLISHED index of every layer of ``pattern`` (``lambda_0`` reads
    # it); None: the pattern is the whole model's
    published_index: Optional[Tuple[int, ...]] = None
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    norm_eps: float = 1e-5
    scan_chunk: int = 128
    head_block: int = 4096  # positions of the head and its cross-entropy at a time
    dtype: Any = jnp.bfloat16

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def runs(self) -> Tuple[int, int]:
        """(how many (M S) pairs, how many (G C) pairs)."""
        found = _PATTERN.match(self.pattern)
        if not found:
            raise ValueError(f"pattern {self.pattern!r} is not (MS) x a, MF, (GC) x b")
        return len(found.group(1)) // 2, len(found.group(2)) // 2

    def lambda_0(self) -> np.ndarray:
        """``0.8 - 0.6 exp(-0.3 l)`` of every layer of ``pattern``."""
        index = self.published_index or tuple(range(len(self.pattern)))
        if len(index) != len(self.pattern):
            raise ValueError(f"{len(index)} published indices for {len(self.pattern)} layers")
        return (0.8 - 0.6 * np.exp(-0.3 * np.asarray(index, np.float64))).astype(np.float32)


def sambay_debug(**over: Any) -> SambaYConfig:
    """Tiny widths, two (G C) pairs (a summed cotangent), a window shorter
    than the tests' sequences and a head in blocks shorter than them."""
    return replace(
        SambaYConfig(
            vocab_size=96, dim=64, n_heads=8, n_kv_heads=4, head_dim=8, ffn_hidden=128, window=16,
            pattern="MSMFGCGC", published_index=(0, 1, 16, 17, 18, 19, 20, 21), d_state=8, dt_rank=8, scan_chunk=16,
            head_block=32, dtype=jnp.float32,
        ),
        **over,
    )


class SambaY:
    def __init__(self, config: SambaYConfig, mesh: Optional[Any] = None) -> None:
        self.config = config
        self.mesh = mesh
        config.runs, config.lambda_0()  # a pattern or an index that does not fit raises here
        if config.n_heads * config.head_dim != config.dim:
            raise ValueError("the query heads fill the stream's width")
        if config.n_heads % config.n_kv_heads or config.n_kv_heads % 2:
            raise ValueError("key heads come in pairs and a pair of them serves whole pairs of query heads")
        # set when the layers are traced: KERNEL_PATH or "plain: <why>"
        self.attention_path: Optional[str] = None

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------

    def _init_ffn(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        D, F = cfg.dim, cfg.ffn_hidden
        k_in, k_out = jax.random.split(key)
        norm = lambda: {"w": jnp.ones((D,), jnp.float32), "b": jnp.zeros((D,), jnp.float32)}  # noqa: E731
        return {
            "norms": {"mixer": norm(), "ffn": norm()},
            "w_gate_up": decoder.seeded(k_in, (D, 2 * F), D, cfg.dtype),
            "w_down": decoder.seeded(k_out, (F, D), F, cfg.dtype),
        }

    def _init_diff(self, key: jax.Array) -> Dict[str, Any]:
        """What every differential attention has whoever makes its keys: four
        ``lambda`` vectors, the pair norm, ``W_o`` and its bias."""
        cfg = self.config
        D, hd = cfg.dim, cfg.head_dim
        k_lam, k_o = jax.random.split(key)
        lam = 0.1 * jax.random.normal(k_lam, (4, hd), jnp.float32)
        return {
            "lambda": {"q1": lam[0], "k1": lam[1], "q2": lam[2], "k2": lam[3]},
            "pair_norm": jnp.ones((2 * hd,), jnp.float32),
            "wo": decoder.seeded(k_o, (D, D), D, cfg.dtype), "bo": jnp.zeros((D,), jnp.float32),
        }

    def _init_layer(self, kind: str, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        D, I, N, R, K = cfg.dim, cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.d_conv
        k_ffn, *keys = jax.random.split(key, 6)
        normal = lambda k, shape: decoder.seeded(k, shape, shape[0], cfg.dtype)  # noqa: E731
        if kind == "M":
            # the Mamba reference code's: A = -(1..N) a channel, D 1, a step
            # log-uniform between the two limits with a floor, kept as the
            # inverse of its softplus, W_dt uniform in +-R^-1/2
            dt = jnp.exp(
                jax.random.uniform(keys[4], (I,), jnp.float32) * (np.log(_DT_MAX) - np.log(_DT_MIN)) + np.log(_DT_MIN)
            )
            dt = jnp.maximum(dt, _DT_FLOOR)
            mixer = {
                "w_in": normal(keys[0], (D, 2 * I)),
                "conv": normal(keys[1], (K, I)), "conv_bias": jnp.zeros((I,), jnp.float32),
                "w_x": normal(keys[2], (I, R + 2 * N)),
                "w_dt": jax.random.uniform(keys[3], (R, I), jnp.float32, -(R ** -0.5), R ** -0.5).astype(cfg.dtype),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (I, N)),
                "D": jnp.ones((I,), jnp.float32),
                "w_out": normal(jax.random.fold_in(keys[0], 1), (I, D)),
            }
        elif kind in "SF":
            qkv = D + 2 * cfg.n_kv_heads * cfg.head_dim
            mixer = {"w_qkv": normal(keys[0], (D, qkv)), "b_qkv": jnp.zeros((qkv,), jnp.float32), **self._init_diff(keys[1])}
        elif kind == "G":
            mixer = {"w_1": normal(keys[0], (D, I)), "w_2": normal(keys[1], (I, D))}
        else:
            mixer = {"w_q": normal(keys[0], (D, D)), "b_q": jnp.zeros((D,), jnp.float32), **self._init_diff(keys[1])}
        return {**self._init_ffn(k_ffn), "mixer": mixer}

    def _init_pair(self, kinds: str, key: jax.Array) -> Dict[str, Any]:
        first, second = jax.random.split(key)
        return {kinds[0]: self._init_layer(kinds[0], first), kinds[1]: self._init_layer(kinds[1], second)}

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        k_embed, k_layers = jax.random.split(key)
        pairs, readers = cfg.runs
        first, middle, second = decoder.init_runs(self._init_pair, k_layers, (("MS", pairs), ("MF", 1), ("GC", readers)))
        return {
            # ONE leaf: the embedding is the head (``tie_word_embeddings``)
            "embed": decoder.seeded(k_embed, (cfg.vocab_size, cfg.dim), cfg.dim, cfg.dtype),
            "first": first, "middle": middle, "second": second,
            "final_norm": {"w": jnp.ones((cfg.dim,), jnp.float32), "b": jnp.zeros((cfg.dim,), jnp.float32)},
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
        ``decay_min`` (the most negative ``dt A`` of the step's scans) and
        ``lambda`` (the mean of the attention layers' ``lambda``)."""
        return dict(zip(SUMMARY_FIELDS, np.asarray(summary, np.float64).reshape(-1).tolist()))

    # ------------------------------------------------------------------
    # mixers
    # ------------------------------------------------------------------

    def _kernel_refusal(self, seq: int) -> Optional[str]:
        """Why the Mosaic kernels do NOT apply, or None when they do."""
        return decoder.kernel_refusal(seq, self.mesh, chunk=self.config.scan_chunk)

    @part("mixer_glue")
    def _scan(self, h: jax.Array, w: Dict[str, jax.Array], *, kernels: bool) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
        """``(the mixer's output, (m = y before the gate, the most negative dt
        A))``."""
        cfg = self.config
        N, R = cfg.d_state, cfg.dt_rank
        u, z = jnp.split(decoder.proj(h, w["w_in"]), 2, axis=-1)
        u = decoder.short_conv_silu(u, w["conv"], w["conv_bias"])
        r, Bm, Cm = jnp.split(decoder.proj(u, w["w_x"]), [R, R + N], axis=-1)
        with part("mixer_proj"):
            dt = jnp.dot(r, w["w_dt"], preferred_element_type=jnp.float32)
        dt = jax.nn.softplus(dt + w["dt_bias"])
        A = -jnp.exp(w["A_log"])
        decay_min = jax.lax.stop_gradient(jnp.min(dt * jnp.min(A, axis=1)))
        if kernels:
            y = selscan.selscan(u, dt, A, Bm, Cm, w["D"], chunk=cfg.scan_chunk, interpret=decoder.assumed_backend() != "tpu")
        else:
            y = selscan.selscan_plain(u, dt, A, Bm, Cm, w["D"], chunk=cfg.scan_chunk)
        gated = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(h.dtype)
        return decoder.proj(gated, w["w_out"]), (y, decay_min)

    def _softmaxes(self, q: jax.Array, k: jax.Array, v: jax.Array, window: Optional[int], kernels: bool) -> jax.Array:
        """Both softmaxes of every pair: q ``[B, S, H, d]``, k and v ``[B, S,
        KV, d]`` → ``[B, S, KV / 2, H / KV, 2, 2 d]``: for every pair of key
        heads and every pair of query heads that reads it, ``softmax(q1 k1^T)
        V`` and ``softmax(q2 k2^T) V``."""
        cfg = self.config
        B, S, H, hd = q.shape
        P, G = cfg.n_kv_heads // 2, H // cfg.n_kv_heads
        # query head 2 (p G + r) + s: q_{s+1} of the r-th query pair on key pair p
        q = q.reshape(B, S, P, G, 2, hd)
        pairs_v = v.reshape(B, S, P, 2 * hd)
        if kernels:
            block_q, block_k = decoder.flash_blocks(S)
            # ONE launch: the launch's key head 2 p + s is k_{s+1} of pair p
            # with V_p, and its group the G queries q_{s+1} that read it
            o = flash.flash_attention(
                q.transpose(0, 1, 2, 4, 3, 5).reshape(B, S, H, hd), k, jnp.repeat(pairs_v, 2, axis=2),
                causal=True, window=window, block_q=block_q, block_k=block_k,
                interpret=decoder.assumed_backend() != "tpu",
            )
            return o.reshape(B, S, P, 2, G, 2 * hd).transpose(0, 1, 2, 4, 3, 5)
        scores = jnp.einsum("bqpgsd,bkpsd->bpgsqk", q, k.reshape(B, S, P, 2, hd)).astype(jnp.float32) * hd ** -0.5
        behind = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
        seen = (behind >= 0) if window is None else (behind >= 0) & (behind < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1).astype(q.dtype)
        return jnp.einsum("bpgsqk,bkpe->bqpgse", probs, pairs_v)

    @part("mixer_diff")
    def _combine(self, o: jax.Array, w: Dict[str, Any], lambda_0: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """``(O1 - lambda O2`` under the pair norm times ``1 - lambda_0`` [B,
        S, D], ``lambda)``."""
        B, S = o.shape[:2]
        l = w["lambda"]
        lam = jnp.exp(jnp.sum(l["q1"] * l["k1"])) - jnp.exp(jnp.sum(l["q2"] * l["k2"])) + lambda_0
        o = o.astype(jnp.float32)
        diff = o[..., 0, :] - lam * o[..., 1, :]
        normed = decoder.rms_norm(diff, w["pair_norm"], self.config.norm_eps) * (1.0 - lambda_0)
        return normed.reshape(B, S, -1).astype(self.config.dtype), lam

    @part("mixer_glue")
    def _attention(
        self, h: jax.Array, w: Dict[str, Any], lambda_0: jax.Array, *, window: Optional[int], kernels: bool
    ) -> Tuple[jax.Array, Tuple[jax.Array, Tuple[jax.Array, jax.Array]]]:
        """``(the mixer's output, (lambda, (K, V) as projected))``."""
        cfg = self.config
        B, S, D = h.shape
        kv = cfg.n_kv_heads * cfg.head_dim
        qkv = decoder.proj(h, w["w_qkv"]) + w["b_qkv"].astype(h.dtype)
        q, k, v = (a.reshape(B, S, -1, cfg.head_dim) for a in jnp.split(qkv, [D, D + kv], axis=-1))
        o, lam = self._combine(self._softmaxes(q, k, v, window, kernels), w, lambda_0)
        return decoder.proj(o, w["wo"]) + w["bo"].astype(h.dtype), (lam, (k, v))

    @part("mixer_glue")
    def _cross(
        self, h: jax.Array, w: Dict[str, Any], lambda_0: jax.Array, *, k: jax.Array, v: jax.Array, kernels: bool
    ) -> Tuple[jax.Array, jax.Array]:
        """``(the mixer's output, lambda)`` over an earlier layer's ``k`` and
        ``v``, which come as the float32 copies the second half closes over."""
        B, S, _ = h.shape
        q = (decoder.proj(h, w["w_q"]) + w["b_q"].astype(h.dtype)).reshape(B, S, -1, self.config.head_dim)
        o, lam = self._combine(self._softmaxes(q, k.astype(h.dtype), v.astype(h.dtype), None, kernels), w, lambda_0)
        return decoder.proj(o, w["wo"]) + w["bo"].astype(h.dtype), lam

    @part("mixer_glue")
    def _memory(self, h: jax.Array, w: Dict[str, Any], *, m: jax.Array) -> Tuple[jax.Array, None]:
        """``(the mixer's output, nothing to hand on)`` over an earlier scan's
        ``m``, the float32 copy the second half closes over."""
        gate = decoder.proj(h, w["w_1"])
        gated = (m.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))).astype(h.dtype)
        return decoder.proj(gated, w["w_2"]), None

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------

    def _layer(self, x: jax.Array, w: Dict[str, Any], mixer: Any) -> Tuple[jax.Array, Any]:
        """One layer on the stream: ``mixer(h, its leaves) -> (out, what it
        hands on)`` and the SwiGLU, a LayerNorm before each."""
        cfg = self.config
        norm = lambda a, n: decoder.layer_norm(a, n["w"], n["b"], cfg.norm_eps)  # noqa: E731
        with part("stream"):
            h = norm(x, w["norms"]["mixer"])
        mixed, handed = mixer(h, w["mixer"])
        with part("stream"):
            x = x + mixed
            h = norm(x, w["norms"]["ffn"])
        with part("ffn"):
            out = swiglu(*jnp.split(h @ w["w_gate_up"], 2, axis=-1), 0.0) @ w["w_down"]
        with part("stream"):
            return x + out, handed

    def _trunk(self, params: Dict[str, Any], tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """tokens [B, S] → (the stream after the last layer, the step's
        summary)."""
        cfg = self.config
        refusal = self._kernel_refusal(tokens.shape[1])
        kernels = refusal is None
        pairs, readers = cfg.runs
        lambda_0 = cfg.lambda_0()  # numpy: a run's share is cut here, not by a gather in the step
        with part("embed"):
            x = params["embed"][tokens].astype(cfg.dtype)

        def layer(depth, mixer):
            """``mixer(h, its leaves, *more) -> (out, handed)`` as one
            rematerialised layer ``(x, its leaves, *more) -> (x, handed)``."""
            return decoder.remat(
                lambda x, w, *more: self._layer(x, w, lambda h, leaves: mixer(h, leaves, *more)), depth, KEPT_NAMES
            )

        def first_half(kind, window, depth):
            scan = layer(depth, functools.partial(self._scan, kernels=kernels))
            attention = layer(depth, functools.partial(self._attention, window=window, kernels=kernels))

            def pair(x, xs):
                w, lam_0 = xs
                x, (m, decay_min) = scan(x, w["M"])
                x, (lam, kv) = attention(x, w[kind], lam_0)
                return x, (decay_min, lam, m, kv)

            return pair

        windowed = first_half("S", cfg.window, pairs)

        def numbers_alone(x, xs):
            """A stacked pair reports its two numbers; what it would hand on
            stays inside the layer."""
            x, out = windowed(x, xs)
            return x, out[:2]

        x, (decay_first, lam_first) = decoder.scan_run(
            numbers_alone, x, (params["first"], jnp.asarray(lambda_0[1 : 2 * pairs : 2])), pairs, keep=None
        )
        at = 2 * pairs
        x, (decay_middle, lam_middle, m, (k, v)) = decoder.scan_run(
            first_half("F", None, 1), x, (params["middle"], jnp.asarray(lambda_0[at + 1 : at + 2])), 1, keep=None
        )
        # handed over in float32: the scan over the readers adds their
        # cotangents in the dtype of what it closes over
        m, k, v = (a[0].astype(jnp.float32) for a in (m, k, v))
        memory = layer(readers, functools.partial(self._memory, m=m))
        cross = layer(readers, functools.partial(self._cross, k=k, v=v, kernels=kernels))

        def second_half(x, xs):
            w, lam_0 = xs
            x, _ = memory(x, w["G"])
            return cross(x, w["C"], lam_0)

        x, lam_second = decoder.scan_run(
            second_half, x, (params["second"], jnp.asarray(lambda_0[at + 3 :: 2])), readers, keep=None
        )
        decoder.kernel_path(self, KERNEL_PATH, refusal)
        # the stream as it stands, in its own dtype, before whatever head reads it, whatever the
        # pattern: without the barrier XLA fuses the last layer's residual add into the head's norm
        # at whatever precision it has at hand, and ``loss`` (the head in blocks) and ``apply``
        # (whole) see two streams (PERF.md section 6, PR 63: the harness's tie of the two failed by
        # 4e-5 to 7e-5; 4.6 ms a step on the chip)
        x = jax.lax.optimization_barrier(x)
        with part("head"):
            summary = jnp.stack([
                jnp.minimum(jnp.min(decay_first, initial=0.0), decay_middle[0]),
                jnp.mean(jnp.concatenate([lam_first, lam_middle, lam_second])),
            ])
        return x, summary

    def _head_input(self, params: Dict[str, Any], x: jax.Array) -> jax.Array:
        n = params["final_norm"]
        return decoder.layer_norm(x, n["w"], n["b"], self.config.norm_eps).astype(self.config.dtype)

    @staticmethod
    def _logits(embed: jax.Array, x: jax.Array) -> jax.Array:
        """``x E^T``, the products' float32 sums as they are: a logit is never
        rounded to the model's dtype."""
        return jax.lax.dot_general(x, embed, (((x.ndim - 1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

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
