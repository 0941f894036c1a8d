"""``ops/indexed_attention.py``: the Mosaic kernels in interpret mode against
the plain path (dense ``[S, S]`` arrays, ``lax.top_k`` on the full row) and
against dense causal attention where every key is picked.  Float32, seeded
operands, the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops import indexed_attention as ia

CASES = {
    # S, topk, H, KV, D, J, DI, B, blocks (q, k, chunk, s), tied scores
    "under_topk": dict(S=32, topk=64, H=4, KV=2, B=2, blocks=ia.Blocks(16, 16, 32, 16)),
    "four_times_topk": dict(S=64, topk=16, H=4, KV=2, B=2, blocks=ia.Blocks(16, 16, 32, 16)),
    "sixteen_times_topk_blocks_that_differ": dict(S=128, topk=8, H=2, KV=2, B=1, blocks=ia.Blocks(16, 32, 64, 16)),
    "eight_heads_a_kv_head": dict(S=64, topk=16, H=8, KV=1, B=1, blocks=ia.Blocks(16, 16, 32, 16)),
    "tied_scores": dict(S=64, topk=16, H=4, KV=2, B=2, blocks=ia.Blocks(16, 16, 32, 16), ties=True),
    "more_than_32_key_blocks": dict(S=272, topk=24, H=2, KV=1, B=1, blocks=ia.Blocks(16, 8, 136, 8)),
}


def _operands(S, H, KV, B, ties=False, D=16, J=2, DI=8, **_):
    ks = jax.random.split(jax.random.PRNGKey(S + H), 7)
    normal = lambda k, shape: jax.random.normal(k, shape, jnp.float32)  # noqa: E731
    q, k, v = normal(ks[0], (B, S, H, D)), normal(ks[1], (B, S, KV, D)), normal(ks[2], (B, S, KV, D))
    qi, ki, w = normal(ks[3], (B, S, J, DI)), normal(ks[4], (B, S, DI)), 0.3 * normal(ks[5], (B, S, J))
    if ties:
        # small whole numbers: many scores are equal, many dots exactly 0
        qi, ki, w = jnp.round(qi), jnp.round(ki), jnp.round(4 * w) / 4
    return (q, k, v, qi, ki, w), normal(ks[6], (B, S, H, D))


def _kernels(topk, blocks, do, weight):
    def f(q, k, v, qi, ki, w):
        mask, lse_index, keys = ia.select_keys(qi, ki, w, topk=topk, blocks=blocks, interpret=True)
        o, kl = ia.indexed_attention(q, k, v, qi, ki, w, mask, lse_index, blocks=blocks, interpret=True)
        return jnp.sum(o * do) + weight * kl, (o, kl, keys, mask)

    return f


def _plain(topk, do, weight):
    def f(q, k, v, qi, ki, w):
        o, kl, keys = ia.indexed_attention_plain(q, k, v, qi, ki, w, topk=topk)
        return jnp.sum(o * do) + weight * kl, (o, kl, keys)

    return f


def _dense_bits(mask, S, block_k):
    """The bits as booleans [B, S, S]."""
    m = np.asarray(mask)
    out = np.zeros((m.shape[0], S, S), bool)
    for kb in range(S // block_k):
        out[:, :, kb * block_k : (kb + 1) * block_k] = (m[:, kb // 32] >> (kb % 32)) & 1
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_are_the_plain_path(case):
    cfg = CASES[case]
    ops, do = _operands(**cfg)
    topk, blocks = cfg["topk"], cfg["blocks"]
    grad = lambda f: jax.jit(jax.value_and_grad(f, argnums=tuple(range(6)), has_aux=True))  # noqa: E731
    (_, (o, kl, keys, mask)), got = grad(_kernels(topk, blocks, do, 1.0))(*ops)
    (_, (o_want, kl_want, keys_want)), want = grad(_plain(topk, do, 1.0))(*ops)
    # the same key set for the same scores: position for position
    _, picked = ia.picked_plain(*ops[3:], topk)
    S = cfg["S"]
    np.testing.assert_array_equal(_dense_bits(mask, S, blocks.fit(S).k), np.asarray(picked))
    np.testing.assert_array_equal(np.asarray(keys), np.minimum(np.arange(S) + 1, topk)[None].repeat(cfg["B"], 0))
    np.testing.assert_array_equal(np.asarray(keys), np.asarray(keys_want))
    np.testing.assert_allclose(o, o_want, atol=3e-6)
    np.testing.assert_allclose(float(kl), float(kl_want), rtol=1e-5)
    for name, a, b in zip("q k v q_index k_index w".split(), got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)
        assert float(jnp.max(jnp.abs(b))) > 1e-3, name  # a gradient that is there


def test_under_topk_is_dense_causal_attention():
    cfg = CASES["under_topk"]
    (q, k, v, qi, ki, w), _ = _operands(**cfg)
    mask, lse_index, _ = ia.select_keys(qi, ki, w, topk=cfg["topk"], blocks=cfg["blocks"], interpret=True)
    o, _ = ia.indexed_attention(q, k, v, qi, ki, w, mask, lse_index, blocks=cfg["blocks"], interpret=True)
    S, group = cfg["S"], cfg["H"] // cfg["KV"]
    logits = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(k, group, axis=2)) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((S, S), bool)), logits, -jnp.inf), axis=-1)
    np.testing.assert_allclose(o, jnp.einsum("bhts,bshd->bthd", probs, jnp.repeat(v, group, axis=2)), atol=3e-6)


@pytest.mark.parametrize("path", ["kernels", "plain"])
def test_each_loss_reaches_its_own_operands_and_no_other(path):
    """The attention's output moves q, k and v and, EXACTLY, nothing of the
    index; the index's loss moves the index's three operands and, exactly,
    nothing of the attention."""
    cfg = CASES["four_times_topk"]
    ops, do = _operands(**cfg)

    def grads(weight, with_output):
        seed = do if with_output else jnp.zeros_like(do)
        f = _kernels(cfg["topk"], cfg["blocks"], seed, weight) if path == "kernels" else _plain(cfg["topk"], seed, weight)
        return jax.grad(lambda *a: f(*a)[0], argnums=tuple(range(6)))(*ops)

    of_output, of_index_loss = grads(0.0, True), grads(1.0, False)
    for g in of_output[3:] + of_index_loss[:3]:
        assert float(jnp.max(jnp.abs(g))) == 0.0
    for g in of_output[:3] + of_index_loss[3:]:
        assert float(jnp.max(jnp.abs(g))) > 1e-3


def test_blocks_refuse_a_length_they_do_not_divide():
    assert ia.Blocks().refusal(16384) == "" and ia.Blocks().refusal(2048) == ""
    assert "does not divide" in ia.Blocks().refusal(16384 + 128)
    assert ia.Blocks().fit(256) == ia.Blocks(128, 256, 256, 64)
