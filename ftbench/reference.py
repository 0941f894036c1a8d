"""The plain reference: Mistral-7B-v0.3's forward pass and the
cross-entropy of every position.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no scan, no
sharding, no cache.  It follows the published block (pre-norm RMSNorm,
rotary embedding on halves as in the ``transformers`` implementation,
grouped-query causal attention, SwiGLU, no biases, no sliding window, untied
head) and reads the parameters in the layout ``models/llama.py`` keeps them
in, one layer at a time so that float32 copies of one layer are all the
memory it needs.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def token_nll(params: Dict[str, Any], tokens: np.ndarray, targets: np.ndarray, cfg: Dict) -> Any:
    """Next-token cross-entropy of every position, [B, S] in float32 on
    the device.  ``params`` may hold host or device arrays of any float
    type; ``cfg`` has dim, n_heads, n_kv_heads, rope_theta, norm_eps."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(np.asarray(a), jnp.float32)  # noqa: E731
    n_heads, n_kv = cfg["n_heads"], cfg["n_kv_heads"]
    hd = cfg["dim"] // n_heads
    eps = cfg["norm_eps"]
    B, S = tokens.shape

    def rms_norm(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w

    half = hd // 2
    freqs = 1.0 / (cfg["rope_theta"] ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]

    def rope(x):
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    causal = jnp.tril(jnp.ones((S, S), bool))
    with jax.default_matmul_precision("highest"):
        x = f32(np.asarray(params["embed"])[np.asarray(tokens)])
        layers = params["layers"]
        for i in range(np.asarray(layers["attn_norm"]).shape[0]):
            w = {k: f32(np.asarray(v)[i]) for k, v in layers.items()}
            h = rms_norm(x, w["attn_norm"])
            q = rope((h @ w["wq"]).reshape(B, S, n_heads, hd))
            k = rope((h @ w["wk"]).reshape(B, S, n_kv, hd))
            v = (h @ w["wv"]).reshape(B, S, n_kv, hd)
            k = jnp.repeat(k, n_heads // n_kv, axis=2)
            v = jnp.repeat(v, n_heads // n_kv, axis=2)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
            x = x + attn.reshape(B, S, n_heads * hd) @ w["wo"]
            h = rms_norm(x, w["mlp_norm"])
            x = x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
        logits = rms_norm(x, f32(params["final_norm"])) @ f32(params["lm_head"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, jnp.asarray(targets)[..., None], axis=-1)[..., 0]
