"""The plain reference of the architecture ``indexed_sparse_moe``
(Keye-VL-2.0-30B-A3B's language model, ``model_type`` ``KeyeVL2``): forward
pass, the three losses and, through ``jax.grad``, gradients.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no bit masks, no
sorting of tokens, nothing of ``torchft_tpu``.  One layer's float32 copy at a
time, attention in blocks of ``ROW_BLOCK`` query rows so that 16,384
positions fit (the ``[rows, S]`` scores a block makes are a block's, never
the sequence's), ``lax.top_k`` on the full score row, then attention over
the keys and values gathered for every row.  It reads a
configuration's own keys and the parameters in the layout
``models/indexed_sparse_moe.py`` keeps them in.

The equations (h = RMSNorm(x), positions s <= t, sg = stop-gradient); what
the published ``config.json`` does not state is listed under ``assumed`` in
``configs/keye-vl-2.0-30b-a3b-ep8-1x1.json``:

- the index: ``qI[t, j] = rope(W_qI[j] sg(h[t]))`` for the ``indexer_num_heads``
  heads of ``indexer_head_dim``; ``kI[s] = rope(norm(W_kI sg(h[s])))``, ONE key
  head; ``w[t, j] = (W_w sg(h[t]))[j] / sqrt(heads * head_dim)``;
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``; ``S_t`` = the
  ``min(t + 1, topk)`` positions ``s <= t`` of largest ``I[t, s]``, ties to
  the lower ``s``.  The top-k is not differentiated.
- attention over ``S_t``: grouped-query heads of ``head_dim`` with an RMSNorm
  a head on q and k and multimodal rope (pairs ``(i, i + head_dim / 2)``; pair
  ``i`` turns by the position stream whose ``mrope_section`` holds it);
  ``o[t] = sum_h W_o[h] sum_{s in S_t} softmax_{S_t}(q[t, h] . k[s, g(h)] /
  sqrt(head_dim)) v[s, g(h)]``.
- the index's loss: ``p[t, s]`` the mean over the heads of that softmax;
  ``L_I = mean_t KL(sg(p[t, .]) || softmax_{S_t} I[t, .])``.
- the experts: ``g = softmax(W_r h)`` over the router's full width in
  float32, the ``num_experts_per_tok`` largest, renormalised; only the experts
  ``experts_held`` names are HERE, theirs is the part computed and the rest
  is left out.  Balance loss: Switch's ``E sum_e f_e P_e`` a sequence, ``f_e``
  the share of its (token, choice) pairs on expert e, over the full width.
- ``loss = L_LM + index_loss_weight sum_layers L_I + sum_layers L_bal``.

Where this departs from DeepSeek-V3.2's published description of its index
(arXiv 2512.02556 and the released inference code), each because this model
is not that one:

1. V3.2 makes the index's queries from the query LATENT of its latent
   attention; this model has grouped-query attention and no latent, so they
   come from the normalised hidden state.
2. V3.2's index key passes a LayerNorm with weight and bias; here an RMS
   norm without weights, so that the index has three matrices and nothing
   else (``indexer_num_kv_heads`` 1 is the one key head, as there).
3. V3.2's index heads are 128 wide and turn 64 of them; here
   ``indexer_head_dim`` is 64 and all of it turns, by the model's own
   multimodal rope with each section scaled to the index's width.
4. V3.2 rotates the index's q and k (Hadamard) and holds them in FP8 for
   inference; training here keeps them in the model's dtype.
5. V3.2 trains the index in two stages, a dense warm-up (the target over
   the whole row, the model frozen) and the sparse stage (target and softmax
   over ``S_t``, the index's input detached, the model trained by its own
   loss alone).  This is the sparse stage from the first step; the target is
   the head-mean, which is V3.2's head-sum normalised to one.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

ROW_BLOCK = 256  # query rows of scores at a time
LOGIT_BLOCK = 1024  # positions of logits at a time


def _f32(a):
    import jax.numpy as jnp

    return jnp.asarray(a, jnp.float32)


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mrope(x, positions, sections, theta):
    """x [B, S, ..., R]; positions [3, B, S]; ``sections`` frequency pairs a
    stream, summing to R / 2; pairs (i, i + R / 2)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    stream = np.repeat(np.arange(len(sections)), sections)
    angles = jnp.moveaxis(_f32(positions), 0, -1)[..., stream] * freqs  # [B, S, half]
    shape = angles.shape[:2] + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def index_sections(cfg: Dict[str, Any]) -> Tuple[int, ...]:
    scale = cfg["sa_config"]["indexer_head_dim"] / cfg["head_dim"]
    return tuple(int(s * scale) for s in cfg["rope_scaling"]["mrope_section"])


def attention(h, w, positions, cfg):
    """(the mixer's output [B, S, D], ``L_I`` of every row [B, S], every
    row's key set [B, S, topk] int32 and which of its places count [B, S,
    topk] bool: a row before ``topk`` has fewer keys than places)."""
    import jax
    import jax.numpy as jnp

    B, S, _ = h.shape
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    sa = cfg["sa_config"]
    J, DI, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], min(sa["topk"], S)
    theta, eps = float(cfg["rope_theta"]), cfg["rms_norm_eps"]
    sections = tuple(cfg["rope_scaling"]["mrope_section"])
    a, ix = w["attn"], w["index"]
    q = mrope(rms_norm((h @ a["wq"]).reshape(B, S, H, hd), a["q_norm"], eps), positions, sections, theta)
    k = mrope(rms_norm((h @ a["wk"]).reshape(B, S, KV, hd), a["k_norm"], eps), positions, sections, theta)
    v = (h @ a["wv"]).reshape(B, S, KV, hd)
    hs = jax.lax.stop_gradient(h)
    q_index = mrope((hs @ ix["wq"]).reshape(B, S, J, DI), positions, index_sections(cfg), theta)
    k_index = mrope(rms_norm(hs @ ix["wk"], 1.0, eps), positions, index_sections(cfg), theta)
    weight = (hs @ ix["ww"]) / np.sqrt(J * DI)
    outs, kls, sets, counted = [], [], [], []
    block = min(ROW_BLOCK, S)
    batch = jnp.arange(B)[:, None, None]
    for lo in range(0, S, block):
        rows = lo + jnp.arange(block)
        dots = jnp.einsum("btjd,bsd->btjs", q_index[:, lo : lo + block], k_index)
        scores = jnp.einsum("btj,btjs->bts", weight[:, lo : lo + block], jax.nn.relu(dots))
        # S_t: top_k on the full row (ties: the lower position first); a
        # place that fell on a position after the row's own does not count
        causal = rows[:, None] >= jnp.arange(S)[None, :]
        best, at = jax.lax.top_k(jnp.where(causal, jax.lax.stop_gradient(scores), -jnp.inf), topk)
        valid = best > -jnp.inf
        # the picked keys and values of every row [B, rows, topk, KV, hd],
        # each KV head serving its group of query heads
        group = lambda x: x.reshape(B, block, KV, H // KV, *x.shape[3:])  # noqa: E731
        logits = jnp.einsum("btgqd,btpgd->btgqp", group(q[:, lo : lo + block]), k[batch, at]) / np.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(valid[:, :, None, None], logits, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("btgqp,btpgd->btgqd", probs, v[batch, at]).reshape(B, block, H * hd))
        target = jax.lax.stop_gradient(jnp.mean(probs, axis=(2, 3)))  # [B, rows, topk]
        picked_scores = jnp.take_along_axis(scores, at, axis=-1)
        log_q = jax.nn.log_softmax(jnp.where(valid, picked_scores, -jnp.inf), axis=-1)
        kl = jnp.where(valid, target * (jnp.log(jnp.maximum(target, 1e-37)) - log_q), 0.0)
        kls.append(jnp.sum(kl, axis=-1))
        sets.append(at)
        counted.append(valid)
    cat = lambda xs: jnp.concatenate(xs, axis=1)  # noqa: E731
    return cat(outs) @ a["wo"], cat(kls), cat(sets), cat(counted)


def route(x, router, cfg):
    """x [T, D] -> (weights [T, E] that are zero off the chosen experts,
    chosen [T, E] bool, scores [T, E])."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.softmax(x @ router, axis=-1)
    _, best = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    chosen = jnp.zeros_like(scores, bool).at[jnp.arange(x.shape[0])[:, None], best].set(True)
    weights = jnp.where(chosen, scores, 0.0)
    if cfg["norm_topk_prob"]:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return weights, chosen, scores


def moe_layer(h, w, cfg, held: Tuple[int, int]):
    """The part of an expert layer that the experts ``held`` (first, count)
    give: ``(out [B, S, D], load [E], balance)``.  ``w['w_gate']`` etc. hold
    the held experts only."""
    import jax
    import jax.numpy as jnp

    B, S, D = h.shape
    x = h.reshape(B * S, D)
    weights, chosen, scores = route(x, w["router"], cfg)
    out = jnp.zeros_like(x)
    first, count = held
    for e in range(count):
        y = (jax.nn.silu(x @ w["w_gate"][e]) * (x @ w["w_up"][e])) @ w["w_down"][e]
        out = out + weights[:, first + e, None] * y
    E, k = scores.shape[1], cfg["num_experts_per_tok"]
    f = chosen.reshape(B, S, E).astype(jnp.float32).mean(axis=1) / k  # share of the pairs
    p = scores.reshape(B, S, E).mean(axis=1)
    balance = cfg["assumed"]["balance_loss_weight"] * jnp.mean(E * jnp.sum(f * p, axis=-1))
    return out.reshape(B, S, D), chosen.sum(axis=0).astype(jnp.float32), balance


def block(x, w, positions, cfg, held):
    """One residual block: ``(x, L_I a row [B, S], (key sets, the places
    that count), load [E], balance)``."""
    mixed, kl, sets, counted = attention(rms_norm(x, w["attn_norm"], cfg["rms_norm_eps"]), w, positions, cfg)
    x = x + mixed
    out, load, balance = moe_layer(rms_norm(x, w["mlp_norm"], cfg["rms_norm_eps"]), w["ffn"], cfg, held)
    return x + out, kl, (sets, counted), load, balance


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


def forward(
    params: Dict[str, Any], tokens, targets, cfg: Dict[str, Any], positions=None, embeds=None,
    given=None, logits: bool = False, key_sets: bool = False,
):
    """``dict(nll [B, S], index_kl [layers], balance [layers], loads [layers,
    E], keys_per_query [layers], logits [B, S, V] if asked, key_sets [layer
    by layer: positions [B, S, topk] and which of those places count] if
    asked)``."""
    import jax
    import jax.numpy as jnp

    tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (3, B, S))
    held = tuple(cfg["experts_held"])
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"])[tokens]
        if embeds is not None:
            x = jnp.where(jnp.asarray(given)[..., None], _f32(embeds), x)
        kls, balances, loads, keys, sets_of = [], [], [], [], []
        depth = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
        for i in range(depth):
            w = jax.tree_util.tree_map(lambda a: _f32(a[i]), params["layers"])
            x, kl, sets, load, balance = block(x, w, positions, cfg, held)
            kls.append(jnp.mean(kl))
            balances.append(balance)
            loads.append(load)
            keys.append(jnp.sum(sets[1]) / (B * S))
            if key_sets:
                sets_of.append(sets)
        head = _f32(params["lm_head"])
        final = rms_norm(x, _f32(params["final_norm"]), cfg["rms_norm_eps"])
        out = dict(
            nll=_nll_blocks(final, head, targets), index_kl=jnp.stack(kls), balance=jnp.stack(balances),
            loads=jnp.stack(loads), keys_per_query=jnp.stack(keys),
        )
        if logits:
            out["logits"] = final @ head
        if key_sets:
            out["key_sets"] = sets_of
        return out


def losses(params: Dict[str, Any], batch, cfg: Dict[str, Any]):
    """(``L_LM``, ``L_I`` summed over the layers, the balance loss summed
    over the layers), from a batch as the program takes it."""
    import jax.numpy as jnp

    out = forward(params, batch[0], batch[1], cfg, *batch[2:])
    return jnp.mean(out["nll"]), jnp.sum(out["index_kl"]), jnp.sum(out["balance"])


def loss(params: Dict[str, Any], batch, cfg: Dict[str, Any]):
    """What a training step differentiates."""
    lm, index, balance = losses(params, batch, cfg)
    return lm + cfg["assumed"]["index_loss_weight"] * index + balance


def token_nll(params: Dict[str, Any], tokens, targets, cfg: Dict[str, Any]):
    """Next-token cross-entropy of every position, [B, S] float32."""
    return forward(params, tokens, targets, cfg)["nll"]
