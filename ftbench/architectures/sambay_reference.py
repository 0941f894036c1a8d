"""The plain reference of the architecture ``sambay`` (Phi-4-mini-flash-reasoning,
``model_type`` ``phi4flash``; the decoder-hybrid-decoder of arXiv:2507.06607):
the forward pass, the loss and, through ``jax.grad``, gradients.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no chunks, no
rematerialisation, nothing of ``torchft_tpu``.  A Python loop over the layers,
one layer's float32 copy at a time; the selective scan is the TOKEN-BY-TOKEN
recurrence (a ``lax.scan`` over the tokens whose carry is one ``[channels,
states]`` state), attention a dense masked softmax of ``ROWS`` query rows at a
time against every key (under a window: from the window's first stretch of
rows on), the SwiGLU and the head's logits ``HEAD_ROWS`` positions at a time,
so that 16,384 positions at the published widths fit.  It reads a configuration's own keys and the parameters in the
layout ``models/sambay.py`` keeps them in.

The equations (stream of width ``hidden_size``; ``LN`` a LayerNorm with weight,
bias and ``layer_norm_eps``; ``layer_pattern`` spells the layers, ``(M S) x a, M
F, (G C) x b``).  What ``config.json`` does not state is listed under
``assumed`` in ``configs/phi-4-mini-flash-reasoning-vp8-1x1.json``; the
departures from the published description are marked DEPARTURE below:

- ``x_0 = E[token]``: no scaling, no position encoding anywhere (assumed: the
  family's stated design, the scans carry position).
- Every layer: ``a = x + Mixer(LN1(x))``, ``x' = a + W_down(silu(g) * u)``, ``[g,
  u] = LN2(a) W_gate_up``.  After the last ``logits = LN_f(x) E^T`` (tied).
- ``M``: ``[u, z] = h W_in``; ``u = silu(conv(u) + b_c)`` (causal, depthwise,
  ``K`` taps, the last the current token's); ``[r, B_t, C_t] = u W_x``; ``dt =
  softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t[c, n] = exp(dt_t[c] A[c,
  n]) h_{t-1}[c, n] + dt_t[c] B_t[n] u_t[c]``; ``y_t[c] = sum_n C_t[n] h_t[c, n]
  + D[c] u_t[c]``; ``Mixer = (y * silu(z)) W_out``.  The last M of the first
  half hands on ``m = y`` (with ``D u``, before the gate: assumed).
- ``S`` (mask ``i - sliding_window < j <= i``) and ``F`` (``j <= i``),
  differential attention in the form a fused kernel takes (DEPARTURE from
  arXiv:2410.05258's one softmax difference over shared values: the same
  mathematics, the pairing of heads assumed): ``[q, k, v] = h W_qkv + b``; query
  heads ``(2j, 2j + 1)`` are the pair ``(q1, q2)_j``, key heads ``(2p, 2p + 1)``
  the pair ``(k1, k2)_p``, value heads ``(2p, 2p + 1)`` ``V_p = [v1; v2]``; query
  pair ``j`` reads ``p = j // (query pairs a key pair)``; ``O = softmax(q1 k1^T
  / sqrt(d) + mask) V - lambda softmax(q2 k2^T / sqrt(d) + mask) V``; ``lambda =
  exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_0``, ``lambda_0 = 0.8 - 0.6
  exp(-0.3 l)``, ``l`` the layer's PUBLISHED index (``layer_index``); an RMSNorm
  over the pair's ``2 d`` channels (weight, ``layer_norm_eps``) times ``1 -
  lambda_0``; ``W_o`` with a bias.  F hands on its ``K`` and ``V`` as projected.
- ``G``: ``Mixer = (m * silu(h W_1)) W_2``.
- ``C``: ``q = h W_q + b``; differential attention as above with its own
  ``lambda`` vectors, pair norm and ``W_o`` over F's ``K`` and ``V``, mask ``j <=
  i``.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

ROWS = 512  # query rows of the scores at a time
HEAD_ROWS = 2048  # positions of the SwiGLU and of the head's logits at a time


def _f32(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def layer_norm(x, n, eps):
    import jax.numpy as jnp

    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred / jnp.sqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps) * n["w"] + n["b"]


def conv_silu(x, w, bias):
    """Causal depthwise convolution: tap ``K - 1`` is the current token's."""
    import jax
    import jax.numpy as jnp

    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j : j + S] * w[j] for j in range(K)) + bias)


def recurrence(u, dt, A, Bm, Cm, D):
    """``y`` [B, S, C] of the selective scan, token by token from a zero
    state: the carry is ``h`` [B, C, N]."""
    import jax
    import jax.numpy as jnp

    def token(h, now):
        u, dt, b, c = now
        h = jnp.exp(dt[:, :, None] * A) * h + (dt * u)[:, :, None] * b[:, None, :]
        return h, jnp.sum(h * c[:, None, :], axis=-1) + D * u

    start = jnp.zeros((*u.shape[::2], A.shape[1]), jnp.float32)
    _, y = jax.lax.scan(token, start, tuple(jnp.moveaxis(a, 1, 0) for a in (u, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


def scan_mixer(h, w):
    """``(Mixer(h), m)``."""
    import jax
    import jax.numpy as jnp

    R, N = w["w_dt"].shape[0], w["A_log"].shape[1]
    u, z = jnp.split(h @ w["w_in"], 2, axis=-1)
    u = conv_silu(u, w["conv"], w["conv_bias"])
    r, Bm, Cm = jnp.split(u @ w["w_x"], [R, R + N], axis=-1)
    dt = jax.nn.softplus(r @ w["w_dt"] + w["dt_bias"])
    y = recurrence(u, dt, -jnp.exp(w["A_log"]), Bm, Cm, w["D"])
    return (y * jax.nn.silu(z)) @ w["w_out"], y


def differential(q, k, v, w, lambda_0, window, eps):
    """q ``[B, S, H, d]``, k and v ``[B, S, KV, d]`` → ``[B, S, H d]`` before
    ``W_o``.  ``window`` None: every earlier key."""
    import jax
    import jax.numpy as jnp

    B, S, H, d = q.shape
    P = k.shape[2] // 2
    G = H // 2 // P  # query pairs a key pair
    q = q.reshape(B, S, P, G, 2, d)  # head 2 (p G + g) + s
    k = k.reshape(B, S, P, 2, d)
    V = v.reshape(B, S, P, 2 * d)
    l = w["lambda"]
    lam = jnp.exp(jnp.dot(l["q1"], l["k1"])) - jnp.exp(jnp.dot(l["q2"], l["k2"])) + lambda_0
    outs = []
    for lo in range(0, S, ROWS):
        hi = min(lo + ROWS, S)
        # the keys a stretch is set against: all of them (the mask hides the later ones) or, under a
        # window, from the stretch of rows that holds the first key its first row sees; so that the
        # stretches of a layer have ONE shape (two under a window) and are compiled once
        first, last = (0, S) if window is None else (max(0, (lo - window + 1) // ROWS * ROWS), hi)
        scores = jnp.einsum("bqpgsd,bkpsd->bpgsqk", q[:, lo:hi], k[:, first:last]) / np.sqrt(d)
        behind = jnp.arange(lo, hi)[:, None] - jnp.arange(first, last)[None, :]
        seen = (behind >= 0) if window is None else (behind >= 0) & (behind < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        o = jnp.einsum("bpgsqk,bkpe->bqpgse", probs, V[:, first:last])
        diff = o[..., 0, :] - lam * o[..., 1, :]
        normed = diff / jnp.sqrt(jnp.mean(diff * diff, axis=-1, keepdims=True) + eps) * w["pair_norm"]
        outs.append((normed * (1.0 - lambda_0)).reshape(B, hi - lo, H * d))
    return jnp.concatenate(outs, axis=1)


def attention_mixer(h, w, lambda_0, window, cfg):
    """``(Mixer(h), (K, V) as projected)``."""
    import jax.numpy as jnp

    B, S, D = h.shape
    d = D // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * d
    q, k, v = (a.reshape(B, S, -1, d) for a in jnp.split(h @ w["w_qkv"] + w["b_qkv"], [D, D + kv], axis=-1))
    return differential(q, k, v, w, lambda_0, window, cfg["layer_norm_eps"]) @ w["wo"] + w["bo"], (k, v)


def cross_mixer(h, w, lambda_0, k, v, cfg):
    B, S, D = h.shape
    q = (h @ w["w_q"] + w["b_q"]).reshape(B, S, cfg["num_attention_heads"], -1)
    return differential(q, k, v, w, lambda_0, None, cfg["layer_norm_eps"]) @ w["wo"] + w["bo"]


def memory_mixer(h, w, m):
    import jax

    return (m * jax.nn.silu(h @ w["w_1"])) @ w["w_2"]


def mlp(h, w):
    import jax
    import jax.numpy as jnp

    def rows(h):
        g, u = jnp.split(h @ w["w_gate_up"], 2, axis=-1)
        return (jax.nn.silu(g) * u) @ w["w_down"]

    return jnp.concatenate([rows(h[:, lo : lo + HEAD_ROWS]) for lo in range(0, h.shape[1], HEAD_ROWS)], axis=1)


def layer(x, w, mixer, cfg):
    """``(x', what the mixer hands on)``."""
    eps = cfg["layer_norm_eps"]
    mixed, handed = mixer(layer_norm(x, w["norms"]["mixer"], eps), w["mixer"])
    a = x + mixed
    return a + mlp(layer_norm(a, w["norms"]["ffn"], eps), w), handed


def lambda_0(cfg: Dict[str, Any]):
    return [0.8 - 0.6 * float(np.exp(-0.3 * l)) for l in cfg["layer_index"]]


def hidden(params: Dict[str, Any], tokens, cfg: Dict[str, Any]):
    """``LN_f`` of the stream after the last layer, [B, S, D]; under the
    caller's matmul precision."""
    import jax
    import jax.numpy as jnp

    pattern, lam_0 = cfg["layer_pattern"], lambda_0(cfg)
    x = jnp.asarray(params["embed"], jnp.float32)[jnp.asarray(tokens)]
    # the stacked runs in the pattern's order, a pair of layers a step
    stacked = [(params[run], i) for run in ("first", "middle", "second")
               for i in range(jax.tree_util.tree_leaves(params[run])[0].shape[0])]
    m = kv = None
    for at, (kind, (run, i)) in enumerate(zip(pattern, [s for s in stacked for _ in range(2)])):
        w = _f32(jax.tree_util.tree_map(lambda a: a[i], run[kind]))
        if kind == "M":
            x, m_here = layer(x, w, scan_mixer, cfg)
            m = m_here if pattern[at + 1] == "F" else m  # the last M of the first half's
        elif kind in "SF":
            window = cfg["sliding_window"] if kind == "S" else None
            x, kv_here = layer(x, w, lambda h, w: attention_mixer(h, w, lam_0[at], window, cfg), cfg)
            kv = kv_here if kind == "F" else kv
        elif kind == "G":
            x, _ = layer(x, w, lambda h, w: (memory_mixer(h, w, m), None), cfg)
        else:
            x, _ = layer(x, w, lambda h, w: (cross_mixer(h, w, lam_0[at], *kv, cfg), None), cfg)
    return layer_norm(x, _f32(params["final_norm"]), cfg["layer_norm_eps"])


def head_nll(params: Dict[str, Any], x, targets):
    """The cross-entropy of every position under ``x E^T``, [B, S]."""
    import jax
    import jax.numpy as jnp

    head, targets = jnp.asarray(params["embed"], jnp.float32).T, jnp.asarray(targets)
    out = []
    for lo in range(0, x.shape[1], HEAD_ROWS):
        logp = jax.nn.log_softmax(x[:, lo : lo + HEAD_ROWS] @ head, axis=-1)
        out.append(-jnp.take_along_axis(logp, targets[:, lo : lo + HEAD_ROWS, None], axis=-1)[..., 0])
    return jnp.concatenate(out, axis=1)


def logits(params: Dict[str, Any], tokens, cfg: Dict[str, Any]):
    """Logits [B, S, vocab], whole: for small sizes."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return hidden(params, tokens, cfg) @ jnp.asarray(params["embed"], jnp.float32).T


def token_nll(params: Dict[str, Any], tokens, targets, cfg: Dict[str, Any]):
    """Next-token cross-entropy of every position, [B, S] float32."""
    import jax

    with jax.default_matmul_precision("highest"):
        return head_nll(params, hidden(params, tokens, cfg), targets)


def loss(params: Dict[str, Any], batch, cfg: Dict[str, Any]):
    import jax.numpy as jnp

    return jnp.mean(token_nll(params, *batch, cfg))
