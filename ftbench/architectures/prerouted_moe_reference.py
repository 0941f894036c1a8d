"""The plain reference of the architecture ``prerouted_moe``
(SmallThinker-21BA3B-Instruct, ``model_name`` ``smallthinker_21b_instruct``,
arXiv:2507.20984): forward pass, the loss and, through ``jax.grad``, gradients.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no walk over blocks,
no sorting of tokens, nothing of ``torchft_tpu``.  One layer's float32 copy at
a time; the window is an EXPLICIT MASK over a row block's scores against every
key (``ROW_BLOCK`` query rows at a time so that 16,384 positions fit); the
experts held are a plain loop of dense products; ``lax.top_k`` on the router's
full row.  It reads a configuration's own keys and the parameters in the
layout ``models/prerouted_moe.py`` keeps them in.

The equations (stream ``h`` of width ``hidden_size``, float32); what
``config.json`` does not state is listed under ``assumed`` in
``configs/smallthinker-21b-a3b-ep2-1x1.json``:

- ``h = E[token]`` (no scale).
- Layer ``l``: ``a = RMSNorm_in(h)``; ``r = a W_r``, the router's
  ``router_experts`` logits, read off the ATTENTION'S input.
- ``q = a Wq`` of ``num_attention_heads`` heads of ``head_dim``, ``k = a Wk``
  and ``v = a Wv`` of ``num_key_value_heads``; no bias, no head norm, no gate.
  ``rope_layout[l] == 1``: rope on all channels, ``rope_theta``, channel ``i``
  paired with ``i + head_dim / 2``; 0: NO position encoding.
  ``sliding_window_layout[l] == 1``: query ``i`` sees keys ``j`` with ``i -
  sliding_window_size < j <= i``; 0: every ``j <= i``.  ``o = softmax(q k^T /
  sqrt(head_dim)) v``; ``h += o Wo``.
- ``m = RMSNorm_post(h)``; the ``moe_num_active_primary_experts`` largest of
  ``r`` are the token's experts; ``w = softmax`` over the chosen logits alone
  (``moe_primary_router_apply_softmax`` with ``norm_topk_prob``: the softmax
  over all, taken at the chosen and divided by their sum, is the same numbers,
  and this is the form that does not pass through it); ``h += sum_{e chosen
  and held} w_e W_down,e (relu(m W_gate,e) * (m W_up,e))``.  Only the experts
  ``experts_held`` names are HERE; theirs is the part computed.  No shared
  expert, no dense layer.
- ``RMSNorm(h) W_head``, the cross-entropy of every position.  No auxiliary
  loss, no selection bias.

Where this departs from the published description (``config.json`` and the
catalog's ``described_as``: "sparse ReGLU; router placed before attention";
nothing was fetched):

1. That the router reads the NORMALISED input ``a`` and not ``h`` itself is
   the published modelling code's ``router_input`` as ISSUE 67 states it, and
   not in a file here.
2. The residual stream is float32; the released weights are bfloat16 and the
   family's code adds in the weights' dtype.
3. The window counts the query's own position (``i - W < j <= i``): the
   reading every windowed model of this repository takes.
4. The 32 experts that the other chip holds add nothing here; a token's
   weights are still normalised over all 6 it chose.
5. The "secondary" experts the family's description names have no key in this
   model's ``config.json`` and none is written.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

ROW_BLOCK = 128  # query rows of scores at a time
LOGIT_BLOCK = 1024  # positions of logits at a time


def _f32(a):
    import jax.numpy as jnp

    return jnp.asarray(a, jnp.float32)


def layer_kinds(cfg: Dict[str, Any]) -> List[Tuple[bool, bool]]:
    """(under the window, with rope) of every layer."""
    return [(bool(w), bool(r)) for w, r in zip(cfg["sliding_window_layout"], cfg["rope_layout"], strict=True)]


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope_halves(x, theta):
    """x [B, S, H, R]: channel ``i`` turns with ``i + R / 2`` by the angle
    ``position * theta^(-2 i / R)``."""
    import jax.numpy as jnp

    S, R = x.shape[1], x.shape[-1]
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(R // 2, dtype=jnp.float32) * 2 / R)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : R // 2], x[..., R // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def seen(rows, keys, window):
    """The mask [rows, keys]: a key no later than the query and, under a
    window, fewer than ``window`` positions back (the query's own counts)."""
    mask = keys[None, :] <= rows[:, None]
    if window is not None:
        mask = mask & (keys[None, :] > rows[:, None] - window)
    return mask


def attention(a, w, kind, cfg):
    """``softmax(q k^T / sqrt(d)) v Wo`` of one layer, ``kind`` = (under the
    window, with rope)."""
    import jax
    import jax.numpy as jnp

    B, S, _ = a.shape
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (a @ w["wq"]).reshape(B, S, H, hd)
    k = (a @ w["wk"]).reshape(B, S, KV, hd)
    v = (a @ w["wv"]).reshape(B, S, KV, hd)
    windowed, roped = kind
    if roped:
        q, k = rope_halves(q, cfg["rope_theta"]), rope_halves(k, cfg["rope_theta"])
    window = cfg["sliding_window_size"] if windowed else None
    q = q.reshape(B, S, KV, H // KV, hd)
    outs = []
    block = min(ROW_BLOCK, S)
    for lo in range(0, S, block):
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", q[:, lo : lo + block], k) / np.sqrt(hd)
        mask = seen(lo + jnp.arange(block), jnp.arange(S), window)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bgrqk,bkgd->bqgrd", probs, v).reshape(B, block, H * hd))
    return jnp.concatenate(outs, axis=1) @ w["wo"]


def reglu(x, gate, up, down):
    import jax

    return (jax.nn.relu(x @ gate) * (x @ up)) @ down


def route(a, router, cfg):
    """a [T, D] -> (weights [T, E] that are zero off the chosen experts,
    chosen [T, E] bool): the softmax over the chosen logits alone."""
    import jax
    import jax.numpy as jnp

    logits = a @ router
    _, best = jax.lax.top_k(logits, cfg["moe_num_active_primary_experts"])
    chosen = jnp.zeros_like(logits, bool).at[jnp.arange(a.shape[0])[:, None], best].set(True)
    return jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), axis=-1), chosen


def moe_layer(a, m, w, cfg, held: Tuple[int, int]):
    """The part of an expert layer that the experts ``held`` (first, count)
    give: ``(out [B, S, D], load [E])``.  The router reads ``a``, the experts
    ``m``.  ``w['w_gate']``, ``w['w_up']`` and ``w['w_down']`` hold the held
    experts only."""
    import jax.numpy as jnp

    B, S, D = m.shape
    x = m.reshape(B * S, D)
    weights, chosen = route(a.reshape(B * S, D), w["router"], cfg)
    out = jnp.zeros_like(x)
    first, count = held
    for e in range(count):
        out = out + weights[:, first + e, None] * reglu(x, w["w_gate"][e], w["w_up"][e], w["w_down"][e])
    return out.reshape(B, S, D), chosen.sum(axis=0).astype(jnp.float32)


def block(h, w, kind, cfg):
    """One layer: ``(h, load)``."""
    eps, norms = cfg["rms_norm_eps"], w["norms"]
    a = rms_norm(h, norms["mixer_in"], eps)
    h = h + attention(a, w, kind, cfg)
    y, load = moe_layer(a, rms_norm(h, norms["ffn_in"], eps), w["ffn"], cfg, tuple(cfg["experts_held"]))
    return h + y, load


def _nll_blocks(x, head, targets):
    import jax
    import jax.numpy as jnp

    S = x.shape[1]
    out = []
    size = min(LOGIT_BLOCK, S)
    for lo in range(0, S, size):
        logp = jax.nn.log_softmax(x[:, lo : lo + size] @ head, axis=-1)
        out.append(-jnp.take_along_axis(logp, targets[:, lo : lo + size, None], axis=-1)[..., 0])
    return jnp.concatenate(out, axis=1)


def _layers(params: Dict[str, Any]):
    """Every layer's float32 weights, one at a time, from the stacked runs
    the program keeps them in."""
    import jax

    for group in params["groups"]:
        for j in range(jax.tree_util.tree_leaves(group)[0].shape[0]):
            yield jax.tree_util.tree_map(lambda a: _f32(a[j]), group)


def forward(params: Dict[str, Any], tokens, targets, cfg: Dict[str, Any], logits: bool = False):
    """``dict(nll [B, S], loads [one [E] a layer], logits [B, S, V] if
    asked)``."""
    import jax
    import jax.numpy as jnp

    tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"])[tokens]
        loads = []
        for kind, layer in zip(layer_kinds(cfg), _layers(params), strict=True):
            h, load = block(h, layer, kind, cfg)
            loads.append(load)
        head = _f32(params["lm_head"])
        final = rms_norm(h, _f32(params["final_norm"]), cfg["rms_norm_eps"])
        out = dict(nll=_nll_blocks(final, head, targets), loads=loads)
        if logits:
            out["logits"] = final @ head
        return out


def loss(params: Dict[str, Any], batch, cfg: Dict[str, Any]):
    """What a training step differentiates: the mean cross-entropy."""
    import jax.numpy as jnp

    return jnp.mean(forward(params, batch[0], batch[1], cfg)["nll"])


def token_nll(params: Dict[str, Any], tokens, targets, cfg: Dict[str, Any]):
    """Next-token cross-entropy of every position, [B, S] float32."""
    return forward(params, tokens, targets, cfg)["nll"]
