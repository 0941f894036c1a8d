"""The plain reference of the architecture ``windowed_moe`` (Trinity-Mini,
``model_type`` ``afmoe``): forward pass, the loss and, through ``jax.grad``,
gradients.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no walk over blocks,
no sorting of tokens, nothing of ``torchft_tpu``.  One layer's float32 copy at
a time; the window is an EXPLICIT MASK over a row block's scores against every
key (``ROW_BLOCK`` query rows at a time so that 16,384 positions fit); the
experts held are a plain loop; ``lax.top_k`` on the router's full row.  It
reads a configuration's own keys and the parameters in the layout
``models/windowed_moe.py`` keeps them in.

The equations (stream ``h`` of width ``hidden_size``, float32); what
``config.json`` does not state is listed under ``assumed`` in
``configs/trinity-mini-ep8-1x1.json``:

- ``h = E[token] * sqrt(hidden_size)`` (``mup_enabled``).
- Mixer of layer ``l``: ``a = RMSNorm(h)``; ``q = a Wq`` of
  ``num_attention_heads`` heads of ``head_dim``, ``k = a Wk`` and ``v = a Wv``
  of ``num_key_value_heads``, ``g = a Wg`` of the width of ``q``; ``q`` and
  ``k`` through an RMSNorm over a head's channels, one weight shared by the
  heads.  ``layer_types[l] == "sliding_attention"``: rope on all channels,
  ``rope_theta``, channel ``i`` paired with ``i + head_dim / 2``, and query
  ``i`` sees keys ``j`` with ``i - sliding_window < j <= i``.
  ``"full_attention"``: NO position encoding and every ``j <= i``.
  ``o = softmax(q k^T / sqrt(head_dim)) v``; ``h += RMSNorm((o * sigmoid(g))
  Wo)``.
- Feed-forward part: ``m = RMSNorm(h)``.  Layers below ``num_dense_layers``:
  ``W_down (silu(W_gate m) * (W_up m))`` of width ``intermediate_size``.
  Others: ``s = sigmoid(W_r m)`` over the router's full width; the
  ``num_experts_per_tok`` largest of ``s + bias``; ``w = route_scale s_e /
  (sum_chosen s + 1e-20)``; ``y = shared(m) + sum_{e chosen and held} w_e
  expert_e(m)``, all SwiGLU of ``moe_intermediate_size``.  Only the experts
  ``experts_held`` names are HERE; theirs is the part computed.
  ``h += RMSNorm(y)``.
- ``RMSNorm(h) W_head``, the cross-entropy of every position.  There is no
  auxiliary loss: the selection bias is moved by the load after a step.

Where this departs from the published description (``config.json`` and the
``afmoe`` family's modelling code as ISSUE 41 states it; nothing was fetched):

1. Every statement marked (afmoe) in the configuration's ``assumed`` is taken
   from ISSUE 41 and not from a file here: the gate, the q/k norms, rope's
   pairing and its absence on the full layers, the window's count, the four
   norms, the embedding's scale, the bias update's rate.
2. The residual stream is float32; the released weights are bfloat16 and the
   family's code adds in the weights' dtype.
3. The router's selection bias is moved by ``sign(mean(load) - load)`` at
   ``load_balance_coeff``, DeepSeek-V3's rule (arXiv:2412.19437 section
   2.1.2); the family's code may scale or clip the update differently.
4. ``num_expert_groups``, ``num_limited_groups``, ``n_group`` and
   ``topk_group`` are all 1: there is no group step, and none is written.
5. The 112 experts that other chips hold add nothing here; a token's
   weights are still normalised over all 8 it chose.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

ROW_BLOCK = 128  # query rows of scores at a time
LOGIT_BLOCK = 1024  # positions of logits at a time


def _f32(a):
    import jax.numpy as jnp

    return jnp.asarray(a, jnp.float32)


def layer_kinds(cfg: Dict[str, Any]) -> List[Tuple[str, str]]:
    """(attention kind, ``"dense"`` or ``"moe"``) of every layer."""
    return [
        (kind, "dense" if i < cfg["num_dense_layers"] else "moe") for i, kind in enumerate(cfg["layer_types"])
    ]


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


def attention_mixer(a, w, kind, cfg):
    import jax
    import jax.numpy as jnp

    B, S, _ = a.shape
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q = rms_norm((a @ w["wq"]).reshape(B, S, H, hd), w["q_norm"], eps)
    k = rms_norm((a @ w["wk"]).reshape(B, S, KV, hd), w["k_norm"], eps)
    v = (a @ w["wv"]).reshape(B, S, KV, hd)
    window = None
    if kind == "sliding_attention":
        q, k = rope_halves(q, cfg["rope_theta"]), rope_halves(k, cfg["rope_theta"])
        window = cfg["sliding_window"]
    q = q.reshape(B, S, KV, H // KV, hd)
    outs = []
    block = min(ROW_BLOCK, S)
    for lo in range(0, S, block):
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", q[:, lo : lo + block], k) / np.sqrt(hd)
        mask = seen(lo + jnp.arange(block), jnp.arange(S), window)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bgrqk,bkgd->bqgrd", probs, v).reshape(B, block, H * hd))
    o = jnp.concatenate(outs, axis=1)
    return (o * jax.nn.sigmoid(a @ w["wg"])) @ w["wo"]


def swiglu(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(x, router, bias, cfg):
    """x [T, D] -> (weights [T, E] that are zero off the chosen experts,
    chosen [T, E] bool)."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(x @ router)
    _, best = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    chosen = jnp.zeros_like(scores, bool).at[jnp.arange(x.shape[0])[:, None], best].set(True)
    weights = jnp.where(chosen, scores, 0.0)
    if cfg["route_norm"]:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return weights * cfg["route_scale"], chosen


def moe_layer(m, w, cfg, held: Tuple[int, int], shared: bool = True):
    """The part of an expert layer that the experts ``held`` (first, count)
    give, with the shared expert's if ``shared``: ``(out [B, S, D], load
    [E])``.  ``w['w_gate']``, ``w['w_up']`` and ``w['w_down']`` hold the held
    experts only."""
    import jax.numpy as jnp

    B, S, D = m.shape
    x = m.reshape(B * S, D)
    weights, chosen = route(x, w["router"], w["bias"], cfg)
    out = jnp.zeros_like(x)
    first, count = held
    for e in range(count):
        out = out + weights[:, first + e, None] * swiglu(x, w["w_gate"][e], w["w_up"][e], w["w_down"][e])
    if shared:
        out = out + swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"])
    return out.reshape(B, S, D), chosen.sum(axis=0).astype(jnp.float32)


def block(h, w, kind, cfg):
    """One layer: ``(h, load or None)``."""
    eps, norms = cfg["rms_norm_eps"], w["norms"]
    mixed = attention_mixer(rms_norm(h, norms["mixer_in"], eps), w, kind[0], cfg)
    h = h + rms_norm(mixed, norms["mixer_out"], eps)
    m = rms_norm(h, norms["ffn_in"], eps)
    if kind[1] == "dense":
        f = w["ffn"]
        y, load = swiglu(m, f["w_gate"], f["w_up"], f["w_down"]), None
    else:
        y, load = moe_layer(m, w["ffn"], cfg, tuple(cfg["experts_held"]))
    return h + rms_norm(y, norms["ffn_out"], eps), load


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
    """``dict(nll [B, S], loads [one [E] an expert layer], logits [B, S, V]
    if asked)``."""
    import jax
    import jax.numpy as jnp

    tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"])[tokens]
        if cfg["mup_enabled"]:
            h = h * np.float32(np.sqrt(cfg["hidden_size"]))
        loads = []
        for kind, layer in zip(layer_kinds(cfg), _layers(params), strict=True):
            h, load = block(h, layer, kind, cfg)
            if load is not None:
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
