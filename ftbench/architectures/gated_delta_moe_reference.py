"""The plain reference of the architecture ``gated_delta_moe`` (Qwen3-Next-80B-A3B,
``model_type`` ``qwen3_next``): forward pass, the loss and, through
``jax.grad``, gradients.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no chunks, no sorting
of tokens, nothing of ``torchft_tpu``.  One layer's float32 copy at a time; the
delta rule is the RECURRENCE, token by token under a ``lax.scan`` over
positions, never the chunked form; attention's scores ``ROW_BLOCK`` query rows
at a time so that 16,384 positions fit; the experts held are a plain loop;
``lax.top_k`` on the router's full row.  It reads a configuration's own keys
and the parameters in the layout ``models/gated_delta_moe.py`` keeps them in.

The equations (stream ``x`` of width ``hidden_size``, float32); what
``config.json`` does not state is listed under ``assumed`` in
``configs/qwen3-next-80b-a3b-ep16-1x1.json``.  ``N(x; w) = x * rsqrt(mean(x^2)
+ eps) * (1 + w)``.  Layer ``i`` (from 0): ``x += Mixer_i(N(x; w1))``, then ``x
+= Experts(N(x; w2))``; ``Mixer_i`` is full attention where ``(i + 1) %
full_attention_interval == 0``, else Gated DeltaNet.  Logits ``N(x; w_f)
W_head``, untied.

- Gated DeltaNet (arXiv:2412.06464), ``h = N(x)``:
  1. ``[q | k | v | z] = h W_qkvz``, ``[b | a] = h W_ba``.
  2. ``[q | k | v] <- SiLU(conv([q | k | v]))``: causal, depthwise,
     ``linear_conv_kernel_dim`` taps, no bias.
  3. ``linear_num_key_heads`` heads of q and k, ``linear_num_value_heads`` of v
     and z; value head ``j`` goes with key head ``j // (value heads / key
     heads)``.
  4. ``q <- q * rsqrt(sum(q^2) + 1e-6) * dk^-0.5``, ``k <- k * rsqrt(sum(k^2) +
     1e-6)`` over a head.
  5. A value head and token: ``beta = sigmoid(b)``, ``g = -exp(A_log) *
     softplus(a + dt_bias)``.
  6. From ``S_0 = 0``, ``S`` ``[dk, dv]`` a value head: ``S' = exp(g_t)
     S_{t-1}``; ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``; ``o_t = S_t^T
     q_t``.
  7. ``y = o * rsqrt(mean(o^2) + eps) * w_n * SiLU(z)`` over a head (``w_n``
     plain, the heads share it; norm first, then the gate); out ``= y W_o``.
- Full attention, ``h = N(x)``: ``[q | gate] = h W_q``, ``k = h W_k``, ``v = h
  W_v``; ``q <- N(q; w_q)``, ``k <- N(k; w_k)`` over a head; rope
  (``rope_theta``, no scaling) turns the first ``head_dim *
  partial_rotary_factor`` channels of a head, channel ``i`` paired with ``i +``
  half of them, the others pass; causal ``softmax(q k^T / sqrt(head_dim)) v``,
  grouped queries; ``o <- o * sigmoid(gate)``; out ``= o W_o``.
- Experts, ``h = N(x)``: ``p = softmax(h W_r)`` over the router's full width;
  the ``num_experts_per_tok`` largest; weights ``p_e / sum of the chosen``
  (``norm_topk_prob``); ``sum_e w_e E_e(h)`` over the experts HELD, ``E(h) = W_d
  (SiLU(W_g h) * (W_u h))``; plus ``sigmoid(h . w_s) * E_shared(h)``.  Only the
  experts ``experts_held`` names are HERE; theirs is the part computed.
- The loss a step differentiates: the mean cross-entropy plus Switch's balance
  loss over the router's full width, a sequence at a time, at
  ``assumed.balance_loss_weight``.

Where this departs from the published description (``config.json`` and the
``qwen3_next`` modelling code as ISSUE 56 states it; nothing was fetched):

1. The published checkpoint stores ``W_qkvz`` and ``W_ba`` grouped by key head
   and ``W_q`` with query and gate interleaved a head; here the columns are ``[q
   | k | v | z]``, ``[b | a]`` and ``[query | gate]``, a head's channels
   together.  Permutations of columns: with seeded weights no arithmetic
   differs.
2. The residual stream is float32; the released weights are bfloat16 and the
   family's code adds in the weights' dtype.
3. No multi-token-prediction module: the catalog row's ``described_as`` names
   one and its ``config`` has no key for it.
4. The 480 experts that other chips hold add nothing here; a token's weights
   are still normalised over all 10 it chose.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

ROW_BLOCK = 128  # query rows of scores at a time
LOGIT_BLOCK = 1024  # positions of logits at a time


def _f32(a):
    import jax.numpy as jnp

    return jnp.asarray(a, jnp.float32)


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    """``"full"`` or ``"gdn"`` of every layer."""
    period = cfg["full_attention_interval"]
    return ["full" if (i + 1) % period == 0 else "gdn" for i in range(cfg["num_hidden_layers"])]


def rotary_dim(cfg: Dict[str, Any]) -> int:
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"])


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


# -- Gated DeltaNet ------------------------------------------------------------


def delta_recurrence(q, k, v, g, beta):
    """Step 6, token by token.  q, k [B, S, Hv, dk] (a key head's, already
    beside each of its value heads), v [B, S, Hv, dv], g, beta [B, S, Hv];
    returns [B, S, Hv, dv]."""
    import jax
    import jax.numpy as jnp

    B, _, H, dk = q.shape

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None, None] * state
        delta = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, out = jax.lax.scan(step, jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(out, 0, 1)


def short_conv_silu(x, w):
    """Causal depthwise convolution, then SiLU.  x [B, S, C], w [K, C]; the
    last tap is the current token's."""
    import jax
    import jax.numpy as jnp

    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j : j + S] * w[j] for j in range(K)))


def delta_decay(h, w, cfg):
    """Step 5's log decay ``g`` [B, S, Hv]."""
    import jax
    import jax.numpy as jnp

    Hv = cfg["linear_num_value_heads"]
    return -jnp.exp(w["a_log"]) * jax.nn.softplus((h @ w["w_ba"])[..., Hv:] + w["dt_bias"])


def delta_net_mixer(h, w, cfg):
    import jax
    import jax.numpy as jnp

    B, S, _ = h.shape
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    keyed, valued = Hk * dk, Hv * dv
    qkvz = h @ w["w_qkvz"]
    qkv = short_conv_silu(qkvz[..., : 2 * keyed + valued], w["conv"])
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(qkv[..., :keyed].reshape(B, S, Hk, dk)) * dk ** -0.5
    k = unit(qkv[..., keyed : 2 * keyed].reshape(B, S, Hk, dk))
    v = qkv[..., 2 * keyed :].reshape(B, S, Hv, dv)
    z = qkvz[..., 2 * keyed + valued :].reshape(B, S, Hv, dv)
    beta = jax.nn.sigmoid((h @ w["w_ba"])[..., :Hv])
    g = delta_decay(h, w, cfg)
    # value head j reads key head j // (Hv / Hk)
    o = delta_recurrence(jnp.repeat(q, Hv // Hk, axis=2), jnp.repeat(k, Hv // Hk, axis=2), v, g, beta)
    y = rms_norm(o, w["o_norm"], cfg["rms_norm_eps"]) * jax.nn.silu(z)
    return y.reshape(B, S, valued) @ w["wo"]


# -- full attention --------------------------------------------------------------


def rope_first(x, rot, theta):
    """x [B, S, H, D]: of the first ``rot`` channels, channel ``i`` turns with
    ``i + rot / 2`` by the angle ``position * theta^(-2 i / rot)``; the others
    pass."""
    import jax.numpy as jnp

    S = x.shape[1]
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(rot // 2, dtype=jnp.float32) * 2 / rot)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2 : rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def attention_mixer(h, w, cfg):
    import jax
    import jax.numpy as jnp

    B, S, _ = h.shape
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, rot, theta = cfg["rms_norm_eps"], rotary_dim(cfg), float(cfg["rope_theta"])
    q_gate = h @ w["wq"]
    q = rms_norm(q_gate[..., : H * hd].reshape(B, S, H, hd), 1.0 + w["q_norm"], eps)
    k = rms_norm((h @ w["wk"]).reshape(B, S, KV, hd), 1.0 + w["k_norm"], eps)
    v = (h @ w["wv"]).reshape(B, S, KV, hd)
    q, k = rope_first(q, rot, theta), rope_first(k, rot, theta)
    q = q.reshape(B, S, KV, H // KV, hd)
    outs = []
    block = min(ROW_BLOCK, S)
    for lo in range(0, S, block):
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", q[:, lo : lo + block], k) / np.sqrt(hd)
        mask = jnp.arange(S)[None, :] <= (lo + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bgrqk,bkgd->bqgrd", probs, v).reshape(B, block, H * hd))
    o = jnp.concatenate(outs, axis=1)
    return (o * jax.nn.sigmoid(q_gate[..., H * hd :])) @ w["wo"]


# -- the experts -------------------------------------------------------------------


def swiglu(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(x, router, cfg):
    """x [T, D] -> (weights [T, E] that are zero off the chosen experts,
    chosen [T, E] bool, the softmax [T, E])."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(x @ router, axis=-1)
    _, best = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    chosen = jnp.zeros_like(probs, bool).at[jnp.arange(x.shape[0])[:, None], best].set(True)
    weights = jnp.where(chosen, probs, 0.0)
    if cfg["norm_topk_prob"]:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return weights, chosen, probs


def moe_layer(h, w, cfg, held: Tuple[int, int], shared: bool = True):
    """The part of an expert layer that the experts ``held`` (first, count)
    give, with the gated shared expert's if ``shared``: ``(out [B, S, D], load
    [E], balance)``.  ``w['w_gate']``, ``w['w_up']`` and ``w['w_down']`` hold
    the held experts only."""
    import jax
    import jax.numpy as jnp

    B, S, D = h.shape
    x = h.reshape(B * S, D)
    weights, chosen, probs = route(x, w["router"], cfg)
    out = jnp.zeros_like(x)
    first, count = held
    for e in range(count):
        out = out + weights[:, first + e, None] * swiglu(x, w["w_gate"][e], w["w_up"][e], w["w_down"][e])
    if shared:
        gate = jax.nn.sigmoid(x @ w["shared_sigmoid"])[:, None]
        out = out + gate * swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"])
    E, k = probs.shape[1], cfg["num_experts_per_tok"]
    f = chosen.reshape(B, S, E).astype(jnp.float32).mean(axis=1) * (E / k)
    p = probs.reshape(B, S, E).mean(axis=1)
    balance = cfg["assumed"]["balance_loss_weight"] * jnp.mean(jnp.sum(f * p, axis=-1))
    return out.reshape(B, S, D), chosen.sum(axis=0).astype(jnp.float32), balance


# -- the model ---------------------------------------------------------------------


def block(x, w, kind, cfg):
    """One layer: ``(x, load [E], balance)``."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, 1.0 + w["attn_norm"], eps)
    x = x + (attention_mixer if kind == "full" else delta_net_mixer)(h, w["mixer"], cfg)
    out, load, balance = moe_layer(rms_norm(x, 1.0 + w["mlp_norm"], eps), w["ffn"], cfg, tuple(cfg["experts_held"]))
    return x + out, load, balance


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
    """``dict(nll [B, S], balance, loads [one [E] a layer], logits [B, S, V]
    if asked)``."""
    import jax
    import jax.numpy as jnp

    tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"])[tokens]
        loads, balance = [], 0.0
        for kind, layer in zip(layer_kinds(cfg), _layers(params), strict=True):
            x, load, bal = block(x, layer, kind, cfg)
            loads.append(load)
            balance = balance + bal
        head = _f32(params["lm_head"])
        final = rms_norm(x, 1.0 + _f32(params["final_norm"]), cfg["rms_norm_eps"])
        out = dict(nll=_nll_blocks(final, head, targets), balance=balance, loads=loads)
        if logits:
            out["logits"] = final @ head
        return out


def loss(params: Dict[str, Any], batch, cfg: Dict[str, Any]):
    """What a training step differentiates: the mean cross-entropy and the
    balance loss."""
    import jax.numpy as jnp

    out = forward(params, batch[0], batch[1], cfg)
    return jnp.mean(out["nll"]) + out["balance"]


def token_nll(params: Dict[str, Any], tokens, targets, cfg: Dict[str, Any]):
    """Next-token cross-entropy of every position, [B, S] float32."""
    return forward(params, tokens, targets, cfg)["nll"]
