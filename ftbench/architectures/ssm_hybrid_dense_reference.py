"""The plain reference of the architecture ``ssm_hybrid_dense``
(granite-4.0-h-micro, ``model_type`` ``granitemoehybrid`` with
``num_local_experts`` 0): forward pass, the loss and, through ``jax.grad``,
gradients.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no chunk algebra, no
rematerialisation, nothing of ``torchft_tpu``.  A Python loop over the layers,
one layer's float32 copy at a time; the state-space mixer is the
TOKEN-BY-TOKEN recurrence (a ``lax.scan`` over the positions whose carry is
the state ``[groups, heads a group, head_dim, state]``); attention a dense
masked softmax of ``ROW_BLOCK`` query rows at a time against every key; the
SwiGLU and the head's logits ``HEAD_ROWS`` positions at a time, so that 16,384
positions at the published widths fit.  It reads a configuration's own keys
and the parameters in the layout ``models/ssm_hybrid_dense.py`` keeps them in.

The equations, with ``h`` the stream of width ``hidden_size`` and ``E`` the
embedding, which is the head too (``tie_word_embeddings``); what
``config.json`` does not state is listed under ``assumed`` in
``configs/granite-4.0-h-micro-vp4-1x1.json``:

- ``h = embedding_multiplier * E[token]``.
- A layer, by its entry of ``layer_types``: ``a = RMSNorm_in(h)``; ``h +=
  residual_multiplier * mixer(a)``; ``m = RMSNorm_post(h)``; ``h +=
  residual_multiplier * W_down (silu(m W_gate) * (m W_up))``, ``W_gate | W_up``
  one matrix of ``hidden_size x 2 shared_intermediate_size``.
- ``mamba`` (Mamba-2, arXiv:2405.21060 section 7 and the released code): ``[z
  | xBC | r] = a W_in`` of widths ``inner | inner + 2 groups * state | heads``,
  ``inner = mamba_n_heads * mamba_d_head``; ``xBC <- silu(conv(xBC) + c)``, a
  causal depthwise convolution of ``mamba_d_conv`` taps; ``xBC = [X | B | C]``,
  ``X[t, j]`` in ``R^d_head``, ``B[t, g]`` and ``C[t, g]`` in ``R^state``, head
  ``j`` in group ``j // (heads / groups)`` (ONE group: every head reads the
  same ``B`` and ``C``); ``dt[t, j] = softplus(r[t, j] + dt_bias[j])``; ``a[t, j]
  = exp(-dt[t, j] exp(A_log[j]))``; ``S_t = a_t S_{t-1} + dt_t X_t B_t^T``;
  ``y_t = S_t C_t + D_j X_t``; ``mixer = W_out (w * RMSNorm_group(y *
  silu(z)))``, the norm over each group's ``inner / groups`` channels.
- ``attention``: ``q, k, v = a W_q, a W_k, a W_v``, no bias, no head norm, NO
  position encoding (``position_embedding_type`` ``nope``); causal softmax of
  ``attention_multiplier * q k^T`` (1/64 where ``1 / sqrt(head_dim)`` is 1/8),
  ``num_attention_heads / num_key_value_heads`` query heads a key head;
  ``mixer = o W_o``.
- ``logits = RMSNorm_f(h) E^T / logits_scaling``.

Where this departs from the published descriptions (Mamba-2, arXiv:2405.21060;
the family's ``config.json``):

1. The gated norm is gate-then-norm (``RMSNorm(y * silu(z))``, the released
   code's ``norm_before_gate`` false), over each of the ``mamba_n_groups``
   groups: ASSUMED, ``config.json`` does not say.
2. The released code runs the scan in chunks of ``mamba_chunk_size`` (256);
   this is the recurrence the chunks were derived from, to which they are exact.
3. ``dt`` is not clamped (the family's code defaults ``time_step_limit`` to (0,
   inf)) and the convolution's bias is present (``mamba_conv_bias`` true).
4. ``rope_theta`` and ``max_position_embeddings`` are in ``config.json`` and
   read by nothing: ``position_embedding_type`` is ``nope``.
"""

from __future__ import annotations

from typing import Any, Dict, List

ROW_BLOCK = 256  # query rows of scores at a time
HEAD_ROWS = 2048  # positions of the SwiGLU and of the head's logits at a time


def _f32(a):
    import jax.numpy as jnp

    return jnp.asarray(a, jnp.float32)


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    """The kind of every layer: ``layer_types`` as it stands."""
    return list(cfg["layer_types"])


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


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
    token at a time.  x [B, S, H, P], dt [B, S, H], Bm and Cm [B, S, G, N]; a
    group's ``B_t`` and ``C_t`` serve its ``H / G`` heads as they are."""
    import jax
    import jax.numpy as jnp

    B, S, H, P = x.shape
    G, N = Bm.shape[2:]
    grouped = lambda v: v.reshape(B, S, G, H // G, *v.shape[3:])  # noqa: E731
    a = jnp.exp(-dt * jnp.exp(A_log))

    def step(state, now):
        x_t, dt_t, a_t, b_t, c_t = now  # [B, G, heads, P], [B, G, heads] twice, [B, G, N] twice
        write = (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :]
        state = a_t[..., None, None] * state + write
        return state, jnp.sum(state * c_t[:, :, None, None, :], axis=-1)

    per_token = tuple(jnp.moveaxis(v, 1, 0) for v in (grouped(x), grouped(dt), grouped(a), Bm, Cm))
    _, y = jax.lax.scan(step, jnp.zeros((B, G, H // G, P, N), jnp.float32), per_token)
    return jnp.moveaxis(y, 0, 1).reshape(B, S, H, P) + D[:, None] * x


def mamba_mixer(h, w, cfg):
    import jax
    import jax.numpy as jnp

    B, S, _ = h.shape
    H, P, N, G = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_n_groups"]
    inner = H * P
    z, xbc, r = jnp.split(h @ w["w_in"], [inner, 2 * inner + 2 * G * N], axis=-1)
    x, Bm, Cm = jnp.split(conv_silu(xbc, w["conv"], w["conv_bias"]), [inner, inner + G * N], axis=-1)
    dt = jax.nn.softplus(r + w["dt_bias"])
    y = ssm_recurrence(
        x.reshape(B, S, H, P), dt, w["A_log"], Bm.reshape(B, S, G, N), Cm.reshape(B, S, G, N), w["D"]
    )
    y = y.reshape(B, S, inner) * jax.nn.silu(z)
    y = rms_norm(y.reshape(B, S, G, inner // G), 1.0, cfg["rms_norm_eps"]).reshape(B, S, inner)
    return (y * w["o_norm"]) @ w["w_out"]


# -- attention ------------------------------------------------------------------


def attention_mixer(h, w, cfg):
    import jax
    import jax.numpy as jnp

    B, S, _ = h.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    q = (h @ w["wq"]).reshape(B, S, KV, H // KV, hd)
    k = (h @ w["wk"]).reshape(B, S, KV, hd)
    v = (h @ w["wv"]).reshape(B, S, KV, hd)
    outs = []
    block = min(ROW_BLOCK, S)
    for lo in range(0, S, block):
        rows = lo + jnp.arange(block)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", q[:, lo : lo + block], k) * cfg["attention_multiplier"]
        causal = rows[:, None] >= jnp.arange(S)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bgrqk,bkgd->bqgrd", probs, v).reshape(B, block, H * hd))
    return jnp.concatenate(outs, axis=1) @ w["wo"]


# -- the model --------------------------------------------------------------------


def swiglu(m, w):
    """``W_down (silu(m W_gate) * (m W_up))``, ``HEAD_ROWS`` positions at a time."""
    import jax
    import jax.numpy as jnp

    S = m.shape[1]
    size = min(HEAD_ROWS, S)
    outs = []
    for lo in range(0, S, size):
        gate, up = jnp.split(m[:, lo : lo + size] @ w["w_gate_up"], 2, axis=-1)
        outs.append((jax.nn.silu(gate) * up) @ w["w_down"])
    return jnp.concatenate(outs, axis=1)


def block(x, w, kind, cfg):
    """One layer: its mixer and its SwiGLU, each a residual branch under the
    residual multiplier."""
    mixer = mamba_mixer if kind == "mamba" else attention_mixer
    x = x + cfg["residual_multiplier"] * mixer(rms_norm(x, w["norm"], cfg["rms_norm_eps"]), w["mixer"], cfg)
    return x + cfg["residual_multiplier"] * swiglu(rms_norm(x, w["post_norm"], cfg["rms_norm_eps"]), w["ffn"])


def _layers(params: Dict[str, Any]):
    """Every layer's float32 weights, one at a time, from the stacked runs
    the program keeps them in."""
    import jax

    for group in params["groups"]:
        for j in range(jax.tree_util.tree_leaves(group)[0].shape[0]):
            yield jax.tree_util.tree_map(lambda a: _f32(a[j]), group)


def hidden(params: Dict[str, Any], tokens, cfg: Dict[str, Any]):
    """tokens [B, S] -> (the final norm of the stream [B, S, D], the
    embedding in float32)."""
    import jax.numpy as jnp

    embed = _f32(params["embed"])
    x = cfg["embedding_multiplier"] * embed[jnp.asarray(tokens)]
    for kind, layer in zip(layer_kinds(cfg), _layers(params), strict=True):
        x = block(x, layer, kind, cfg)
    return rms_norm(x, _f32(params["final_norm"]), cfg["rms_norm_eps"]), embed


def logits(params: Dict[str, Any], tokens, cfg: Dict[str, Any]):
    """[B, S, V] float32, whole: for the tests' sizes."""
    import jax

    with jax.default_matmul_precision("highest"):
        final, embed = hidden(params, tokens, cfg)
        return final @ embed.T / cfg["logits_scaling"]


def token_nll(params: Dict[str, Any], tokens, targets, cfg: Dict[str, Any]):
    """Next-token cross-entropy of every position, [B, S] float32."""
    import jax
    import jax.numpy as jnp

    targets = jnp.asarray(targets)
    with jax.default_matmul_precision("highest"):
        final, embed = hidden(params, tokens, cfg)
        S = final.shape[1]
        size = min(HEAD_ROWS, S)
        out = []
        for lo in range(0, S, size):
            logp = jax.nn.log_softmax(final[:, lo : lo + size] @ embed.T / cfg["logits_scaling"], axis=-1)
            out.append(-jnp.take_along_axis(logp, targets[:, lo : lo + size, None], axis=-1)[..., 0])
        return jnp.concatenate(out, axis=1)


def loss(params: Dict[str, Any], batch, cfg: Dict[str, Any]):
    """What a training step differentiates: the mean cross-entropy."""
    import jax.numpy as jnp

    return jnp.mean(token_nll(params, batch[0], batch[1], cfg))
