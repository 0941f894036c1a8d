"""The plain reference of the architecture ``eva`` (EvaByte, ``model_type``
``evabyte``, ``attention_class`` ``eva``): forward pass, the losses and,
through ``jax.grad``, gradients.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no walk over blocks,
no scan, nothing of ``torchft_tpu``.  One layer's float32 copy at a time; the
two key sources are two EXPLICIT score matrices of a window's query rows (at
most ``window_size`` rows at a time, so that 32,768 positions fit: ``[H,
2,048, 2,048]`` against the window's own tokens and ``[H, 2,048, 128 w]``
against the summaries of the ``w`` windows before it), joined and put through
ONE softmax.  It reads a configuration's own keys and the parameters in the
layout ``models/eva.py`` keeps them in.

The equations (stream ``x`` of width ``hidden_size``, float32; ``H`` heads of
``d = hidden_size / num_attention_heads``, ``W = window_size``, ``C =
chunk_size``, ``s = d ** -0.5``); what ``config.json`` does not state is listed
under ``assumed`` in ``configs/evabyte-6.5b-1x1.json``:

- ``x = E[byte]``.
- Mixer: ``h = RMSNorm(x)`` with the weight ``1 + g``
  (``norm_add_unit_offset``), eps ``rms_norm_eps``; ``q, k, v = h W_q, h W_k, h
  W_v``, no bias, ``H`` heads each; rope on all ``d`` channels of ``q`` and
  ``k``, ``rope_theta``, channel ``i`` paired with ``i + d / 2``, no scaling.
- Pooling, a head's learned ``phi`` and ``mu`` in ``R^d``: position ``j`` lies
  in chunk ``j // C``; ``pi = softmax`` over a chunk's ``C`` positions of ``s
  (k_j . phi)``; ``k~_c = sum_j pi_j k_j + mu``, ``v~_c = sum_j pi_j v_j``.
- Attention: query ``i`` of window ``w(i) = i // W`` sees ``T(i) = {j : w(j) =
  w(i), j <= i}`` and ``R(i) = {c : c < (W / C) w(i)}``; ``o_i = (sum_T exp(s
  q_i . k_j) v_j + sum_R exp(s q_i . k~_c) v~_c) / Z_i`` with ``Z_i`` the sum
  of both kinds of weight.  ``x += concat_heads(o) W_o``.
- ``x += W_down (silu(W_gate h') * (W_up h'))``, ``h' = RMSNorm(x)``.
- ``RMSNorm(x) W_head``: ``num_pred_heads`` slices of ``vocab_size`` columns
  each; slice ``m`` at ``t`` is of the byte at ``t + 1 + m``.

Where this departs from the published description (``config.json``; nothing
was fetched, and the config names ``attention_class``, ``chunk_size``,
``window_size`` and ``num_pred_heads`` and nothing of how they act): every
entry of ``assumed`` in the configuration file, as ISSUE 52 states them; EVA's
random features and control variates (arXiv:2302.04542) are NOT here, the
pooling being deterministic.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

FFN_BLOCK = 4096  # positions of the feed-forward part at a time


def _f32(a):
    import jax.numpy as jnp

    return jnp.asarray(a, jnp.float32)


def rms_norm(x, g, eps):
    """The weight is ``1 + g`` (``norm_add_unit_offset``)."""
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def rope_halves(x, theta):
    """x [B, S, H, R]: channel ``i`` turns with ``i + R / 2`` by the angle
    ``position * theta^(-2 i / R)``."""
    import jax.numpy as jnp

    S, R = x.shape[1], x.shape[-1]
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(R // 2, dtype=jnp.float32) * 2 / R)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : R // 2], x[..., R // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def pool(k, v, phi, mu, chunk):
    """k, v [B, S, H, d] → a summary key and value a chunk, [B, S / chunk, H, d]."""
    import jax

    B, S, H, d = k.shape
    k, v = k.reshape(B, S // chunk, chunk, H, d), v.reshape(B, S // chunk, chunk, H, d)
    pi = jax.nn.softmax((k * phi).sum(-1) / np.sqrt(d), axis=2)[..., None]
    return (pi * k).sum(2) + mu, (pi * v).sum(2)


def mixer(h, w, cfg):
    import jax
    import jax.numpy as jnp

    B, S, D = h.shape
    H, W, C = cfg["num_attention_heads"], cfg["window_size"], cfg["chunk_size"]
    d = D // H
    q = rope_halves((h @ w["wq"]).reshape(B, S, H, d), cfg["rope_theta"])
    k = rope_halves((h @ w["wk"]).reshape(B, S, H, d), cfg["rope_theta"])
    v = (h @ w["wv"]).reshape(B, S, H, d)
    k_sum, v_sum = pool(k, v, w["phi"], w["mu"], C)
    outs = []
    for lo in range(0, S, W):  # a window's rows: its own tokens, and the summaries before it
        hi = min(lo + W, S)
        rows = jnp.arange(lo, hi)
        before = lo // C
        own = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, lo:hi]) / np.sqrt(d)
        own = jnp.where(rows[None, :] <= rows[:, None], own, -jnp.inf)
        earlier = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k_sum[:, :before]) / np.sqrt(d)
        probs = jax.nn.softmax(jnp.concatenate([earlier, own], axis=-1), axis=-1)  # ONE softmax over both
        values = jnp.concatenate([v_sum[:, :before], v[:, lo:hi]], axis=1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", probs, values).reshape(B, hi - lo, D))
    return jnp.concatenate(outs, axis=1) @ w["wo"]


def swiglu(x, gate, up, down):
    import jax
    import jax.numpy as jnp

    return jnp.concatenate(
        [
            (jax.nn.silu(x[:, lo : lo + FFN_BLOCK] @ gate) * (x[:, lo : lo + FFN_BLOCK] @ up)) @ down
            for lo in range(0, x.shape[1], FFN_BLOCK)
        ],
        axis=1,
    )


def block(x, w, cfg):
    eps = cfg["rms_norm_eps"]
    x = x + mixer(rms_norm(x, w["attn_norm"], eps), w, cfg)
    return x + swiglu(rms_norm(x, w["mlp_norm"], eps), w["w_gate"], w["w_up"], w["w_down"])


def hidden(params: Dict[str, Any], tokens, cfg: Dict[str, Any]):
    """The final norm of the stream after the last layer, [B, S, D]; under
    the caller's matmul precision."""
    import jax
    import jax.numpy as jnp

    x = _f32(params["embed"])[jnp.asarray(tokens)]
    layers = params["layers"]
    for i in range(jax.tree_util.tree_leaves(layers)[0].shape[0]):
        x = block(x, jax.tree_util.tree_map(lambda a: _f32(a[i]), layers), cfg)
    return rms_norm(x, _f32(params["final_norm"]), cfg["rms_norm_eps"])


def slice_logits(params: Dict[str, Any], tokens, cfg: Dict[str, Any]):
    """Every slice's logits, [B, S, num_pred_heads, vocab_size]."""
    import jax

    with jax.default_matmul_precision("highest"):
        final = hidden(params, tokens, cfg)
        return (final @ _f32(params["lm_head"])).reshape(*final.shape[:2], cfg["num_pred_heads"], cfg["vocab_size"])


def slice_means(params: Dict[str, Any], batch, cfg: Dict[str, Any]):
    """The mean cross-entropy of every slice, [num_pred_heads]: slice ``m`` at
    ``t`` against the byte at ``t + 1 + m``, which is ``targets[t + m]``, over
    the positions ``t <= S - 1 - m`` that have it (left out, not wrapped)."""
    import jax
    import jax.numpy as jnp

    tokens, targets = batch
    logp = jax.nn.log_softmax(slice_logits(params, tokens, cfg), axis=-1)
    S = logp.shape[1]
    means = []
    for m in range(cfg["num_pred_heads"]):
        labels = jnp.asarray(targets)[:, m:]
        means.append(-jnp.mean(jnp.take_along_axis(logp[:, : S - m, m], labels[..., None], axis=-1)))
    return jnp.stack(means)


def loss(params: Dict[str, Any], batch, cfg: Dict[str, Any]):
    """The next byte's mean cross-entropy ALONE: slice 0's."""
    return slice_means(params, batch, cfg)[0]


def objective(params: Dict[str, Any], batch, cfg: Dict[str, Any]):
    """What a training step differentiates: the plain mean of the slices' means."""
    import jax.numpy as jnp

    return jnp.mean(slice_means(params, batch, cfg))


def token_nll(params: Dict[str, Any], tokens, targets, cfg: Dict[str, Any]):
    """Next-byte cross-entropy of every position, [B, S] float32: slice 0."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        final = hidden(params, tokens, cfg)
        logp = jax.nn.log_softmax(final @ _f32(params["lm_head"])[:, : cfg["vocab_size"]], axis=-1)
        return -jnp.take_along_axis(logp, jnp.asarray(targets)[..., None], axis=-1)[..., 0]
