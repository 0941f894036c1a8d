"""The plain reference of the architecture ``ling_hybrid`` (Ling-3.0-flash,
``model_type`` ``bailing_hybrid``): forward pass, loss and, through
``jax.grad``, gradients.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no chunking of the
linear attention, no sorting of tokens, no sharding, nothing of
``torchft_tpu``.  One layer's float32 copy at a time.  It reads a
configuration's own keys (``hidden_size``, ``kv_lora_rank``, ...) and the
parameters in the layout ``models/ling_hybrid.py`` keeps them in.

The equations, from the published ``config.json`` and the lines the keys are
named after (DeepSeek-V3 for the latent attention and the router, Kimi
Linear for KDA); what neither states is listed under ``assumed`` in
``configs/ling-3.0-flash-ep32-1x1.json``:

- layer ``i`` mixes with latent attention (MLA) where ``(i + 1) %
  layer_group_size == 0`` and with KDA otherwise; its feed-forward is dense
  (``intermediate_size``) for ``i < first_k_dense_replace`` and routed
  experts after.  Pre-norm residual blocks, RMSNorm.
- KDA, a head: ``S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t
  v_t^T``, ``o_t = S_t^T q_t / sqrt(dk)``, token by token under ``lax.scan``.
  q, k, v come from a projection, a causal depthwise convolution of
  ``short_conv_kernel_size`` and SiLU; q and k are then brought to unit
  length; ``g = kda_lower_bound * sigmoid(exp(a_log) * (x W_g + dt_bias))``
  for every channel, ``b = sigmoid(x W_b)`` a head; the output is
  RMS-normalised a head, gated a head by ``sigmoid(x W_gate)``, projected.
- MLA: queries of ``qk_nope_head_dim + qk_rope_head_dim`` straight from the
  hidden state (no q rank), keys and values expanded from a normalised
  latent of ``kv_lora_rank``, one rotary key of ``qk_rope_head_dim`` shared
  by the heads, rotary on interleaved pairs, causal softmax attention with
  scores in blocks of query rows, the same head-wise gate.
- the router: float32, sigmoid scores; on ``scores + bias`` it keeps
  ``topk_group`` of ``n_group`` groups by the sum of each group's two best,
  then the ``num_experts_per_tok`` best inside them; weights are the
  unbiased scores of the chosen, normalised and scaled by
  ``routed_scaling_factor``.  Only the experts ``experts_held`` names are
  HERE: theirs is the part computed, and the shared expert's is added once.
- the loss: mean next-token cross-entropy over the (sliced) vocabulary; with
  ``num_nextn_predict_layers`` > 0 the multi-token-prediction module's at
  ``t + 2`` times ``mtp_loss_scaling_factor``; the sequence-wise balance
  loss of every router is reported beside it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

SCORE_BLOCK = 512  # query rows of MLA scores at a time
LOGIT_BLOCK = 1024  # positions of logits at a time


def _f32(a):
    import jax.numpy as jnp

    return jnp.asarray(a, jnp.float32)


def layer_kinds(cfg: Dict[str, Any]) -> List[Tuple[str, str]]:
    """(mixer, feed-forward) of every layer, from the two keys that
    declare the pattern."""
    return [
        (
            "mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda",
            "dense" if i < cfg["first_k_dense_replace"] else "moe",
        )
        for i in range(cfg["num_hidden_layers"])
    ]


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def swiglu(gate, up, limit):
    import jax
    import jax.numpy as jnp

    if limit:
        gate = jnp.minimum(gate, limit)
        up = jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


# -- KDA ---------------------------------------------------------------------


def kda_recurrence(q, k, v, g, beta, scale):
    """The gated delta rule, token by token.  q, k, g [B, S, H, dk], v
    [B, S, H, dv], beta [B, S, H]; returns [B, S, H, dv]."""
    import jax
    import jax.numpy as jnp

    B, S, H, dk = q.shape

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None] * state
        delta = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t) * scale

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


def kda_mixer(h, w, cfg):
    import jax
    import jax.numpy as jnp

    B, S, _ = h.shape
    H, dk = cfg["num_attention_heads"], cfg["head_dim"]
    heads = lambda a: a.reshape(B, S, H, -1)  # noqa: E731
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(heads(short_conv_silu(h @ w["wq"], w["conv_q"])))
    k = unit(heads(short_conv_silu(h @ w["wk"], w["conv_k"])))
    v = heads(short_conv_silu(h @ w["wv"], w["conv_v"]))
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(w["a_log"])[None, None, :, None] * heads(h @ w["w_g"] + w["dt_bias"])
    )
    beta = jax.nn.sigmoid(h @ w["w_beta"])
    o = kda_recurrence(q, k, v, g, beta, dk ** -0.5)
    o = rms_norm(o, w["o_norm"], cfg["rms_norm_eps"])
    o = o * jax.nn.sigmoid(h @ w["w_gate"])[..., None]
    return o.reshape(B, S, -1) @ w["wo"]


# -- MLA ---------------------------------------------------------------------


def rope_interleaved(x, theta):
    """Rotary embedding on the pairs (0, 1), (2, 3), ...; x [B, S, ..., R]."""
    import jax.numpy as jnp

    S, R = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R))
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    shape = (1, S) + (1,) * (x.ndim - 3) + (R // 2,)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def mla_mixer(h, w, cfg):
    import jax
    import jax.numpy as jnp

    B, S, _ = h.shape
    H = cfg["num_attention_heads"]
    nope, rot, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    q = (h @ w["wq"]).reshape(B, S, H, nope + rot)
    q = jnp.concatenate([q[..., :nope], rope_interleaved(q[..., nope:], cfg["rope_theta"])], axis=-1)
    kv_a = h @ w["w_kv_a"]
    latent = rms_norm(kv_a[..., :rank], w["kv_norm"], cfg["rms_norm_eps"])
    k_rot = rope_interleaved(kv_a[..., rank:], cfg["rope_theta"])  # [B, S, rot]: one for all heads
    kv = (latent @ w["w_kv_b"]).reshape(B, S, H, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rot[:, :, None, :], (B, S, H, rot))], axis=-1
    )
    v = kv[..., nope:]
    outs = []
    block = min(SCORE_BLOCK, S)
    for lo in range(0, S, block):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo : lo + block], k) / np.sqrt(nope + rot)
        rows = lo + jnp.arange(block)[:, None]
        scores = jnp.where(rows >= jnp.arange(S)[None, :], scores, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v))
    o = jnp.concatenate(outs, axis=1)
    o = o * jax.nn.sigmoid(h @ w["w_gate"])[..., None]
    return o.reshape(B, S, -1) @ w["wo"]


# -- the feed-forward layers ---------------------------------------------------


def route(x, router, bias, cfg):
    """x [T, D] -> (weights [T, E] that are zero off the chosen experts,
    chosen [T, E] bool, scores [T, E])."""
    import jax
    import jax.numpy as jnp

    E, G = router.shape[1], cfg["n_group"]
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ router)
    biased = scores + bias
    grouped = biased.reshape(-1, G, E // G)
    two_best = jnp.sort(grouped, axis=-1)[..., -2:].sum(axis=-1)  # [T, G]
    # the topk_group best groups (ties: the lower index, as lax.top_k breaks them)
    order = jnp.argsort(-two_best, axis=-1, stable=True)[:, : cfg["topk_group"]]
    kept = jnp.zeros_like(two_best, bool).at[jnp.arange(x.shape[0])[:, None], order].set(True)
    masked = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(-1, E)
    best = jnp.argsort(-masked, axis=-1, stable=True)[:, :k]
    chosen = jnp.zeros_like(masked, bool).at[jnp.arange(x.shape[0])[:, None], best].set(True)
    weights = jnp.where(chosen, scores, 0.0)
    if cfg["norm_topk_prob"]:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return weights * cfg["routed_scaling_factor"], chosen, scores


def moe_layer(h, w, cfg, held: Tuple[int, int], limit=0.0, shared_limit=0.0, shared=True):
    """The part of an expert layer that the experts ``held`` (first, count)
    give, with the shared expert's if ``shared``: ``(out [B, S, D], load
    [E], balance)``.  ``w['w_gate']`` etc. hold the held experts only."""
    import jax.numpy as jnp

    B, S, D = h.shape
    x = h.reshape(B * S, D)
    weights, chosen, scores = route(x, w["router"], w["bias"], cfg)
    out = jnp.zeros_like(x)
    first, count = held
    for e in range(count):
        y = swiglu(x @ w["w_gate"][e], x @ w["w_up"][e], limit) @ w["w_down"][e]
        out = out + weights[:, first + e, None] * y
    if shared and "shared_gate" in w:
        out = out + swiglu(x @ w["shared_gate"], x @ w["shared_up"], shared_limit) @ w["shared_down"]
    load = chosen.sum(axis=0).astype(jnp.float32)
    E, k = scores.shape[1], cfg["num_experts_per_tok"]
    f = chosen.reshape(B, S, E).astype(jnp.float32).mean(axis=1) * (E / k)
    p = (scores / scores.sum(axis=-1, keepdims=True)).reshape(B, S, E).mean(axis=1)
    balance = cfg["assumed"]["balance_loss_weight"] * jnp.mean(jnp.sum(f * p, axis=-1))
    return out.reshape(B, S, D), load, balance


def dense_mlp(h, w):
    return swiglu(h @ w["w_gate"], h @ w["w_up"], 0.0) @ w["w_down"]


# -- the model -----------------------------------------------------------------


def block(x, w, kind, cfg, limits):
    """One residual block: ``(x, load or None, balance)``."""
    mixer, ffn = kind
    h = rms_norm(x, w["attn_norm"], cfg["rms_norm_eps"])
    x = x + (mla_mixer if mixer == "mla" else kda_mixer)(h, w["mixer"], cfg)
    h = rms_norm(x, w["mlp_norm"], cfg["rms_norm_eps"])
    if ffn == "dense":
        return x + dense_mlp(h, w["ffn"]), None, 0.0
    out, load, balance = moe_layer(h, w["ffn"], cfg, tuple(cfg["experts_held"]), *limits)
    return x + out, load, balance


def _layers(params: Dict[str, Any]):
    """(flat layer index, that layer's float32 weights), one at a time."""
    import jax

    i = 0
    for group in params["groups"]:
        depth = jax.tree_util.tree_leaves(group)[0].shape[0]
        for j in range(depth):
            yield i, jax.tree_util.tree_map(lambda a: _f32(a[j]), group)
            i += 1


def _nll_blocks(x, head, targets):
    import jax
    import jax.numpy as jnp

    S = x.shape[1]
    out = []
    block_size = min(LOGIT_BLOCK, S)
    for lo in range(0, S, block_size):
        logp = jax.nn.log_softmax(x[:, lo : lo + block_size] @ head, axis=-1)
        out.append(
            -jnp.take_along_axis(logp, targets[:, lo : lo + block_size, None], axis=-1)[..., 0]
        )
    return jnp.concatenate(out, axis=1)


def forward(params: Dict[str, Any], tokens, targets, cfg: Dict[str, Any], logits: bool = False):
    """``dict(nll [B, S], balance, loads [one [E] an expert layer], mtp_nll
    [B, S] or None, logits [B, S, V] if asked)``."""
    import jax
    import jax.numpy as jnp

    tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
    kinds = layer_kinds(cfg)
    expert_limits = cfg["expert_swiglu_limit_list"]
    shared_limits = cfg["share_expert_swiglu_limit_list"]
    with jax.default_matmul_precision("highest"):
        embed = _f32(params["embed"])
        x = embed[tokens]
        loads, balance = [], 0.0
        for i, w in _layers(params):
            x, load, bal = block(x, w, kinds[i], cfg, (expert_limits[i], shared_limits[i]))
            balance = balance + bal
            if load is not None:
                loads.append(load)
        head = _f32(params["lm_head"])
        final = rms_norm(x, _f32(params["final_norm"]), cfg["rms_norm_eps"])
        out = dict(nll=_nll_blocks(final, head, targets), mtp_nll=None)
        if logits:
            out["logits"] = final @ head
        if cfg.get("num_nextn_predict_layers", 0):
            # one module: the hidden state before the final norm beside the
            # NEXT token's embedding, one MLA expert layer (mtp_use_kda:
            # false), the shared head, the token after next
            m = jax.tree_util.tree_map(_f32, params["mtp"])
            z = jnp.concatenate(
                [
                    rms_norm(embed[targets], m["enorm"], cfg["rms_norm_eps"]),
                    rms_norm(x, m["hnorm"], cfg["rms_norm_eps"]),
                ],
                axis=-1,
            ) @ m["proj"]
            z, load, bal = block(z, m["layer"], ("mla", "moe"), cfg, (0.0, 0.0))
            loads.append(load)
            balance = balance + bal
            z = rms_norm(z, m["final_norm"], cfg["rms_norm_eps"])
            out["mtp_nll"] = _nll_blocks(z, head, jnp.roll(targets, -1, axis=1))
        out.update(balance=balance, loads=loads)
        return out


def loss(params: Dict[str, Any], batch, cfg: Dict[str, Any]):
    """What a training step differentiates: the mean cross-entropy, the
    multi-token-prediction loss at its weight, and the balance loss."""
    import jax.numpy as jnp

    out = forward(params, batch[0], batch[1], cfg)
    total = jnp.mean(out["nll"]) + out["balance"]
    if out["mtp_nll"] is not None:
        total = total + cfg["mtp_loss_scaling_factor"] * jnp.mean(out["mtp_nll"])
    return total


def token_nll(params: Dict[str, Any], tokens, targets, cfg: Dict[str, Any]):
    """Next-token cross-entropy of every position, [B, S] float32."""
    return forward(params, tokens, targets, cfg)["nll"]
