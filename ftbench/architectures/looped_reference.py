"""The plain reference of the architecture ``looped`` (Ouro-2.6B, ``model_type``
``ouro``): the forward pass of every pass, the exit distribution, the
objective and, through ``jax.grad``, gradients.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no scan, no
rematerialisation, nothing of ``torchft_tpu``.  A Python loop over the passes
around a Python loop over the layers, one layer's float32 copy at a time; the
attention's scores of ``ROWS`` query rows at a time and the head's logits of
``ROWS`` positions at a time, so that 16,384 positions at a vocabulary of
49,152 fit.  It reads a configuration's own keys and the parameters in the
layout ``models/looped.py`` keeps them in.

The equations (stream ``x`` of width ``hidden_size``; ``H`` heads of ``d =
hidden_size / num_attention_heads``; ``T = total_ut_steps``; ``N`` an RMSNorm
with eps ``rms_norm_eps`` and a learned weight); what ``config.json`` does not
state is listed under ``assumed`` in ``configs/ouro-2.6b-1x1.json``:

- ``x_0 = E[token]``.
- For ``t = 1..T``, the SAME layers every pass: ``h = x_{t-1}``; for every
  layer ``a = h + N2(Attn(N1(h)))``, ``h = a + N4(MLP(N3(a)))``; then ``x_t =
  Nf(h)``, the ONE final norm, which the head reads and pass ``t + 1`` starts
  from.
- ``Attn``: ``q, k, v = h W_q, h W_k, h W_v``, no bias, ``H`` heads each; rope
  on all ``d`` channels of ``q`` and ``k``, ``rope_theta``, channel ``i``
  paired with ``i + d / 2``, positions ``0..S-1`` in every pass; causal softmax
  attention at scale ``d ** -0.5``; ``concat_heads(o) W_o``.
- ``MLP``: ``W_down (silu(W_gate h) * (W_up h))``.
- ``logits_t = x_t W_head``; ``z_t = x_t . w_g + b_g``, ``lambda_t =
  sigmoid(z_t)``; ``p_1 = lambda_1``, ``p_t = lambda_t prod_{j<t} (1 -
  lambda_j)`` for ``1 < t < T``, ``p_T = prod_{j<T} (1 - lambda_j)``
  (``lambda_T`` is computed and unused).
- ``objective = mean_i [sum_t p_t[i] nll_t[i] - beta H(p[i])]``, ``H(p) =
  -sum_t p_t log p_t``, ``beta`` the configuration's ``assumed.entropy_beta``.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

ROWS = 2048  # query rows of the scores, and positions of the head's logits, at a time


def _f32(a):
    import jax.numpy as jnp

    return jnp.asarray(a, jnp.float32)


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope_halves(x, theta):
    """x [B, S, H, d]: channel ``i`` turns with ``i + d / 2`` by the angle
    ``position * theta^(-2 i / d)``."""
    import jax.numpy as jnp

    S, d = x.shape[1], x.shape[-1]
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2 / d)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(h, w, cfg):
    import jax
    import jax.numpy as jnp

    B, S, D = h.shape
    H = cfg["num_attention_heads"]
    d = D // H
    q = rope_halves((h @ w["wq"]).reshape(B, S, H, d), cfg["rope_theta"])
    k = rope_halves((h @ w["wk"]).reshape(B, S, H, d), cfg["rope_theta"])
    v = (h @ w["wv"]).reshape(B, S, H, d)
    outs = []
    for lo in range(0, S, ROWS):  # a stretch of query rows against every key up to its last
        hi = min(lo + ROWS, S)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi]) / np.sqrt(d)
        seen = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v[:, :hi]).reshape(B, hi - lo, D))
    return jnp.concatenate(outs, axis=1) @ w["wo"]


def mlp(h, w):
    import jax
    import jax.numpy as jnp

    return jnp.concatenate(
        [
            (jax.nn.silu(h[:, lo : lo + ROWS] @ w["w_gate"]) * (h[:, lo : lo + ROWS] @ w["w_up"])) @ w["w_down"]
            for lo in range(0, h.shape[1], ROWS)
        ],
        axis=1,
    )


def block(h, w, cfg):
    eps, n = cfg["rms_norm_eps"], w["norms"]
    a = h + rms_norm(attention(rms_norm(h, n["mixer_in"], eps), w, cfg), n["mixer_out"], eps)
    return a + rms_norm(mlp(rms_norm(a, n["ffn_in"], eps), w), n["ffn_out"], eps)


def hidden(params: Dict[str, Any], tokens, cfg: Dict[str, Any]):
    """``x_1 .. x_T``, a list of [B, S, D]: the final norm of the stream
    after every pass; under the caller's matmul precision."""
    import jax
    import jax.numpy as jnp

    x = _f32(params["embed"])[jnp.asarray(tokens)]
    layers = params["layers"]
    depth = jax.tree_util.tree_leaves(layers)[0].shape[0]
    out = []
    for _ in range(cfg["total_ut_steps"]):
        for i in range(depth):
            x = block(x, jax.tree_util.tree_map(lambda a: _f32(a[i]), layers), cfg)
        x = rms_norm(x, _f32(params["final_norm"]), cfg["rms_norm_eps"])
        out.append(x)
    return out


def head_nll(params: Dict[str, Any], x, targets):
    """The cross-entropy of every position under ``x W_head``, [B, S]."""
    import jax
    import jax.numpy as jnp

    head, targets = _f32(params["lm_head"]), jnp.asarray(targets)
    out = []
    for lo in range(0, x.shape[1], ROWS):
        logp = jax.nn.log_softmax(x[:, lo : lo + ROWS] @ head, axis=-1)
        out.append(-jnp.take_along_axis(logp, targets[:, lo : lo + ROWS, None], axis=-1)[..., 0])
    return jnp.concatenate(out, axis=1)


def exit_distribution(params: Dict[str, Any], xs):
    """``p`` [T, B, S] from every pass's ``x_t``, by the products as written:
    ``lambda_T`` is computed and nothing reads it."""
    import jax
    import jax.numpy as jnp

    gate = params["gate"]
    lam = [jax.nn.sigmoid(x @ _f32(gate["w"]) + _f32(gate["b"])) for x in xs]
    p, stayed = [], jnp.ones_like(lam[0])
    for t in range(len(xs) - 1):
        p.append(lam[t] * stayed)
        stayed = stayed * (1.0 - lam[t])
    return jnp.stack(p + [stayed])


def passes(params: Dict[str, Any], batch, cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``nll`` [T, B, S] (every pass's cross-entropy of every position), ``p``
    [T, B, S], ``objective`` (the scalar a step differentiates)."""
    import jax
    import jax.numpy as jnp

    tokens, targets = batch
    with jax.default_matmul_precision("highest"):
        xs = hidden(params, tokens, cfg)
        nll = jnp.stack([head_nll(params, x, targets) for x in xs])
        p = exit_distribution(params, xs)
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0), axis=0)
    objective = jnp.mean(jnp.sum(p * nll, axis=0) - cfg["assumed"]["entropy_beta"] * entropy)
    return dict(nll=nll, p=p, objective=objective)


def logits(params: Dict[str, Any], tokens, cfg: Dict[str, Any]):
    """Every pass's logits [T, B, S, vocab], whole: for small sizes."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return jnp.stack([x @ _f32(params["lm_head"]) for x in hidden(params, tokens, cfg)])


def objective(params: Dict[str, Any], batch, cfg: Dict[str, Any]):
    return passes(params, batch, cfg)["objective"]


def token_nll(params: Dict[str, Any], tokens, targets, cfg: Dict[str, Any]):
    """Next-token cross-entropy of every position, [B, S] float32: pass T's."""
    import jax

    with jax.default_matmul_precision("highest"):
        return head_nll(params, hidden(params, tokens, cfg)[-1], targets)
