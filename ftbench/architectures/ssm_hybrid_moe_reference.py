"""The plain reference of the architecture ``ssm_hybrid_moe``
(NVIDIA-Nemotron-3-Nano-30B-A3B, ``model_type`` ``nemotron_h``): forward pass,
the two losses and, through ``jax.grad``, gradients.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no chunk algebra, no
sorting of tokens, nothing of ``torchft_tpu``.  One layer's float32 copy at a
time; the state-space layer is the TOKEN-BY-TOKEN recurrence (a ``lax.scan``
over the positions with the state ``[heads, head_dim, state]`` as its carry);
attention in blocks of ``ROW_BLOCK`` query rows so that 16,384 positions fit;
``lax.top_k`` on the router's full row.  It reads a configuration's own keys
and the parameters in the layout ``models/ssm_hybrid_moe.py`` keeps them in.

The equations (``h = RMSNorm(x)``; every layer is ``x <- x + f(h)`` for ONE
``f``, by the layer's character of ``hybrid_override_pattern``; a final norm,
then the head); what ``config.json`` does not state is listed under
``assumed`` in ``configs/nemotron-3-nano-30b-a3b-ep8-1x1.json``:

- ``M``, Mamba-2 (arXiv:2405.21060, section 7 and the released code):
  ``[z | xBC | r] = W_in h`` of widths ``heads * head_dim | heads * head_dim +
  2 groups * state | heads``; ``xBC <- silu(conv(xBC) + c)``, a causal
  depthwise convolution of ``conv_kernel`` taps; ``xBC = [X | B | C]``, ``X[t,
  j]`` in ``R^head_dim``, ``B[t, g]`` and ``C[t, g]`` in ``R^state``, head ``j``
  in group ``j // (heads / groups)``; ``dt[t, j] = softplus(r[t, j] +
  dt_bias[j])``; ``a[t, j] = exp(-dt[t, j] exp(A_log[j]))``;
  ``S_t = a_t S_{t-1} + dt_t X_t B_t^T``; ``y_t = S_t C_t + D_j X_t``;
  ``f = W_out RMSNorm_group(y * silu(z))``, the norm over each group's
  ``heads * head_dim / groups`` channels, with a weight.
- ``*``, attention: ``q = W_q h``, ``k, v`` of ``num_key_value_heads`` heads,
  causal ``softmax(q k^T / sqrt(head_dim)) v``, ``W_o``; no bias.
- ``E``, experts: ``s = sigmoid(W_r h)`` over the router's full width; the
  ``num_experts_per_tok`` largest of ``s + bias`` (``n_group`` 1: one group);
  weights ``routed_scaling_factor s_e / sum_chosen s``; ``f = sum_{e chosen and
  held} w_e W_down[e] relu(W_up[e] h)^2 + W_down^sh relu(W_up^sh h)^2``.  Only
  the experts ``experts_held`` names are HERE; theirs is the part computed.
  Balance loss: DeepSeek-V3's sequence-wise ``sum_e f_e P_e`` (arXiv:2412.19437
  eq. 17-20) over the router's full width.
- ``loss = L_LM + sum_layers L_bal``.

Where this departs from the published descriptions (Mamba-2, arXiv:2405.21060;
Nemotron-H, arXiv:2504.03624):

1. The attention layers apply NO position encoding.  That is Nemotron-H's
   published convention (the state-space layers carry position) and AN
   INFERENCE for this model: its ``config.json`` holds ``rope_theta`` 10,000
   and ``partial_rotary_factor`` 1, which that family's modelling code does not
   read.
2. The gated norm is gate-then-norm (``RMSNorm(y * silu(z))``, the released
   code's ``norm_before_gate`` false), over each of the ``n_groups`` groups.
3. The released code runs the scan in chunks of ``chunk_size`` (128); this is
   the recurrence the chunks were derived from, to which they are exact.
4. The router's selection bias is moved by the load after a step and by no
   gradient (DeepSeek-V3's; ``config.json`` names no balancing), and the
   balance loss above is added at a small weight.
5. Multi-token prediction, ``num_logits_to_keep`` and the time-step limit of
   the released code (``time_step_limit`` (0, inf): no clamp) add nothing here.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

ROW_BLOCK = 128  # query rows of scores at a time
LOGIT_BLOCK = 1024  # positions of logits at a time
KINDS = {"M": "ssm", "*": "attention", "E": "experts"}


def _f32(a):
    import jax.numpy as jnp

    return jnp.asarray(a, jnp.float32)


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    """The kind of every layer, a character of the pattern each."""
    return [KINDS[c] for c in cfg["hybrid_override_pattern"]]


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def relu2(x):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(x, 0.0))


# -- Mamba-2 ------------------------------------------------------------------


def conv_silu(x, w, bias):
    """Causal depthwise convolution (the last tap is the current token's),
    a bias a channel, SiLU.  x [B, S, C], w [K, C]."""
    import jax
    import jax.numpy as jnp

    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j : j + S] * w[j] for j in range(K)) + bias)


def ssm_recurrence(x, dt, A_log, Bm, Cm, D):
    """``S_t = a_t S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``, a
    token at a time.  x [B, S, H, P], dt [B, S, H], Bm and Cm [B, S, G, N]."""
    import jax
    import jax.numpy as jnp

    B, S, H, P = x.shape
    heads = H // Bm.shape[2]
    Bh, Ch = jnp.repeat(Bm, heads, axis=2), jnp.repeat(Cm, heads, axis=2)  # [B, S, H, N]
    a = jnp.exp(-dt * jnp.exp(A_log))

    def step(state, now):
        x_t, dt_t, a_t, b_t, c_t = now
        state = a_t[..., None, None] * state + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return state, jnp.sum(state * c_t[..., None, :], axis=-1)

    per_token = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, a, Bh, Ch))
    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, Bm.shape[-1]), jnp.float32), per_token)
    return jnp.moveaxis(y, 0, 1) + D[:, None] * x


def ssm_mixer(h, w, cfg):
    import jax
    import jax.numpy as jnp

    B, S, _ = h.shape
    H, P, N, G = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"], cfg["n_groups"]
    inner = H * P
    z, xbc, r = jnp.split(h @ w["w_in"], [inner, 2 * inner + 2 * G * N], axis=-1)
    x, Bm, Cm = jnp.split(conv_silu(xbc, w["conv"], w["conv_bias"]), [inner, inner + G * N], axis=-1)
    dt = jax.nn.softplus(r + w["dt_bias"])
    y = ssm_recurrence(
        x.reshape(B, S, H, P), dt, w["A_log"], Bm.reshape(B, S, G, N), Cm.reshape(B, S, G, N), w["D"]
    )
    y = y.reshape(B, S, inner) * jax.nn.silu(z)
    y = rms_norm(y.reshape(B, S, G, inner // G), 1.0, cfg["layer_norm_epsilon"]).reshape(B, S, inner)
    return (y * w["o_norm"]) @ w["w_out"]


# -- attention ------------------------------------------------------------------


def attention_mixer(h, w, cfg):
    import jax
    import jax.numpy as jnp

    B, S, _ = h.shape
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (h @ w["wq"]).reshape(B, S, KV, H // KV, hd)
    k = (h @ w["wk"]).reshape(B, S, KV, hd)
    v = (h @ w["wv"]).reshape(B, S, KV, hd)
    outs = []
    block = min(ROW_BLOCK, S)
    for lo in range(0, S, block):
        rows = lo + jnp.arange(block)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", q[:, lo : lo + block], k) / np.sqrt(hd)
        causal = rows[:, None] >= jnp.arange(S)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bgrqk,bkgd->bqgrd", probs, v).reshape(B, block, H * hd))
    return jnp.concatenate(outs, axis=1) @ w["wo"]


# -- experts --------------------------------------------------------------------


def route(x, router, bias, cfg):
    """x [T, D] -> (weights [T, E] that are zero off the chosen experts,
    chosen [T, E] bool, scores [T, E])."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(x @ router)
    _, best = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    chosen = jnp.zeros_like(scores, bool).at[jnp.arange(x.shape[0])[:, None], best].set(True)
    weights = jnp.where(chosen, scores, 0.0)
    if cfg["norm_topk_prob"]:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return weights * cfg["routed_scaling_factor"], chosen, scores


def moe_layer(h, w, cfg, held: Tuple[int, int], shared: bool = True):
    """The part of an expert layer that the experts ``held`` (first, count)
    give, with the shared expert's if ``shared``: ``(out [B, S, D], load
    [E], balance)``.  ``w['w_up']`` and ``w['w_down']`` hold the held experts
    only."""
    import jax.numpy as jnp

    B, S, D = h.shape
    x = h.reshape(B * S, D)
    weights, chosen, scores = route(x, w["router"], w["bias"], cfg)
    out = jnp.zeros_like(x)
    first, count = held
    for e in range(count):
        out = out + weights[:, first + e, None] * (relu2(x @ w["w_up"][e]) @ w["w_down"][e])
    if shared:
        out = out + relu2(x @ w["shared_up"]) @ w["shared_down"]
    E, k = scores.shape[1], cfg["num_experts_per_tok"]
    f = chosen.reshape(B, S, E).astype(jnp.float32).mean(axis=1) * (E / k)
    p = (scores / scores.sum(axis=-1, keepdims=True)).reshape(B, S, E).mean(axis=1)
    balance = cfg["assumed"]["balance_loss_weight"] * jnp.mean(jnp.sum(f * p, axis=-1))
    return out.reshape(B, S, D), chosen.sum(axis=0).astype(jnp.float32), balance


# -- the model --------------------------------------------------------------------


def block(x, w, kind, cfg):
    """One residual layer: ``(x, load or None, balance)``."""
    h = rms_norm(x, w["norm"], cfg["layer_norm_epsilon"])
    if kind == "experts":
        out, load, balance = moe_layer(h, w["ffn"], cfg, tuple(cfg["experts_held"]))
        return x + out, load, balance
    return x + (ssm_mixer if kind == "ssm" else attention_mixer)(h, w, cfg), None, 0.0


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
    """``dict(nll [B, S], balance, loads [one [E] an expert layer], logits
    [B, S, V] if asked)``."""
    import jax
    import jax.numpy as jnp

    tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
    kinds = layer_kinds(cfg)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"])[tokens]
        loads, balance = [], 0.0
        for kind, layer in zip(kinds, _layers(params), strict=True):
            x, load, bal = block(x, layer, kind, cfg)
            balance = balance + bal
            if load is not None:
                loads.append(load)
        head = _f32(params["lm_head"])
        final = rms_norm(x, _f32(params["final_norm"]), cfg["layer_norm_epsilon"])
        out = dict(nll=_nll_blocks(final, head, targets), balance=balance, loads=loads)
        if logits:
            out["logits"] = final @ head
        return out


def losses(params: Dict[str, Any], batch, cfg: Dict[str, Any]):
    """(``L_LM``, the balance loss summed over the expert layers)."""
    import jax.numpy as jnp

    out = forward(params, batch[0], batch[1], cfg)
    return jnp.mean(out["nll"]), out["balance"]


def loss(params: Dict[str, Any], batch, cfg: Dict[str, Any]):
    """What a training step differentiates."""
    lm, balance = losses(params, batch, cfg)
    return lm + balance


def token_nll(params: Dict[str, Any], tokens, targets, cfg: Dict[str, Any]):
    """Next-token cross-entropy of every position, [B, S] float32."""
    return forward(params, tokens, targets, cfg)["nll"]
