"""``ops/indexed_attention.py``: the Mosaic kernels in interpret mode against
the plain path (dense ``[S, S]`` arrays, ``lax.top_k`` on the full row) and
against dense causal attention where every key is picked; the launches'
grids against the live (row block, key block) pairs, and their outputs
against the rectangular launches they were before PR 53
(``tests/_indexed_rectangle.py``): the backward's (ONE launch since PR 68,
held to the rectangle's two) and ``L_I``'s bit for bit, the keys-major
forward's (PR 65) to float32's rounding.  Float32, seeded operands, the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import _indexed_rectangle as rectangle
from tests.test_flash_attention import _pallas_calls
from torchft_tpu.ops import indexed_attention as ia

CASES = {
    # S, topk, H, KV, D, J, DI, B, blocks (q, k, chunk, s), tied scores
    "under_topk": dict(S=32, topk=64, H=4, KV=2, B=2, blocks=ia.Blocks(16, 16, 32, 16)),
    "four_times_topk": dict(S=64, topk=16, H=4, KV=2, B=2, blocks=ia.Blocks(16, 16, 32, 16)),
    "sixteen_times_topk_blocks_that_differ": dict(S=128, topk=8, H=2, KV=2, B=1, blocks=ia.Blocks(16, 32, 64, 16)),
    "eight_heads_a_kv_head": dict(S=64, topk=16, H=8, KV=1, B=1, blocks=ia.Blocks(16, 16, 32, 16)),
    "tied_scores": dict(S=64, topk=16, H=4, KV=2, B=2, blocks=ia.Blocks(16, 16, 32, 16), ties=True),
    "more_than_32_key_blocks": dict(S=272, topk=24, H=2, KV=1, B=1, blocks=ia.Blocks(16, 8, 136, 8)),
    # the cell's ratio: four row blocks a key block, a row block sees qi // 4 + 1 key blocks
    "four_row_blocks_a_key_block": dict(S=256, topk=32, H=4, KV=2, B=1, blocks=ia.Blocks(16, 64, 64, 16)),
    "shorter_than_a_key_block": dict(S=16, topk=8, H=4, KV=2, B=2, blocks=ia.Blocks(16, 32, 32, 16)),  # one live pair
    "a_group_of_one": dict(S=64, topk=16, H=2, KV=2, B=1, blocks=ia.Blocks(16, 32, 32, 16)),
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


# the cases that differ in the walk's shape (three more differ in the scores alone)
@pytest.mark.parametrize("case", sorted(set(CASES) - {"under_topk", "four_times_topk", "tied_scores"}))
def test_walked_launches_are_the_rectangles_bit_for_bit(case):
    """What the backward launches and ``L_I``'s give over their live pairs is
    what the parent's gave over the whole rectangle, every bit: the order of
    accumulation inside a row block and inside a key block is the
    rectangle's.  The forward's tile lies keys-major and its ``o`` and ``lse``
    are the rectangle's to float32's rounding."""
    cfg = CASES[case]
    (q, k, v, qi, ki, w), do = _operands(**cfg)
    blocks = cfg["blocks"].fit(cfg["S"])
    if case == "shorter_than_a_key_block":
        assert ia._steps(cfg["S"], blocks).steps == 1
    # one program, as each launch below: run operation by operation the interpreter's loop is seconds of small compiles
    mask, lse_index, _ = jax.jit(lambda *a: ia.select_keys(*a, topk=cfg["topk"], blocks=blocks, interpret=True))(qi, ki, w)
    qh, kh, vh, qih, wh = ia._heads_major(q, k, v, qi, w)
    scale = 1.0 / float(np.sqrt(q.shape[-1]))

    # the tree's forward is keys-major since PR 65: its ``o`` and ``lse`` are the rectangle's to float32's
    # rounding of a denominator summed in another order, and BOTH modules' backward launches and ``L_I`` are
    # handed the tree's two, so that what did not change is still held bit for bit
    forward = jax.jit(lambda module: module._attn_fwd(qh, kh, vh, mask, scale, blocks, True), static_argnums=0)
    (o, lse), (o_want, lse_want) = forward(ia), forward(rectangle)
    assert lse.shape == o.shape[:3] and lse_want.shape == (*lse.shape, ia._ROW_LANES)
    # read over the six cases (interpret mode, float32): o 6.0e-7 at most, lse 4.8e-7
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_want), rtol=0, atol=2e-6, err_msg="o")
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_want[..., 0]), rtol=0, atol=2e-6, err_msg="lse")

    def launches(module):
        lanes = ia._row_lanes(lse)
        grads = module._attn_bwd(qh, kh, vh, mask, o, lanes, do.transpose(0, 2, 1, 3), scale, blocks, True)
        loss = module._index_loss(qh, kh, lanes, mask, qih, wh, ki, ia._row_lanes(lse_index), scale, blocks, True)
        return (*grads, *loss)

    names = "dq dk dv kl d_q_index d_w d_k_index".split()
    run = jax.jit(launches, static_argnums=0)
    for name, got, want in zip(names, run(ia), run(rectangle), strict=True):
        if name in names[:3]:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want), err_msg=name)
        else:
            # L_I's four are EQUAL on the chip (scripts/indexed_attention_probe.py --parent).  HERE the
            # interpreter's body is compiled by XLA for the CPU, bare in the walk and under a ``cond`` in
            # the rectangle, and the two programs differ in the last bit of a few elements (4 of 64 ``kl``
            # in a_group_of_one, ``d_w`` in one more case)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7, err_msg=name)
        assert np.isfinite(np.asarray(got)).all() and float(jnp.max(jnp.abs(got))) > 0, name


def test_a_row_that_picks_nothing_in_its_first_key_blocks():
    """Index scores that rise with the position: a row's key set is the
    ``topk`` positions up to itself, so every key block before those is all
    ``_NEG_INF`` to it.  The keys-major forward keeps such a row's ``m`` at
    ``_NEG_INF`` and adds ``exp(0)`` a key there, and the first picked key's
    correction wipes that to exactly 0: ``o`` and ``lse`` are the plain
    path's."""
    S, topk, H, KV, blocks = 128, 8, 8, 2, ia.Blocks(16, 16, 32, 16)
    (q, k, v, qi, ki, w), _ = _operands(S=S, H=H, KV=KV, B=1)
    qi, w = jnp.ones_like(qi), jnp.ones_like(w)
    ki = jnp.broadcast_to((jnp.arange(1, S + 1, dtype=jnp.float32) / S)[None, :, None], ki.shape)

    @jax.jit
    def kernels(q, k, v, qi, ki, w):
        mask, _, _ = ia.select_keys(qi, ki, w, topk=topk, blocks=blocks, interpret=True)
        qh, kh, vh, _, _ = ia._heads_major(q, k, v, qi, w)
        return mask, *ia._attn_fwd(qh, kh, vh, mask, 1.0 / float(np.sqrt(q.shape[-1])), blocks, True)

    mask, o, lse = kernels(q, k, v, qi, ki, w)
    rows, cols = np.arange(S)[:, None], np.arange(S)[None, :]
    picked = (cols <= rows) & (cols > rows - topk)
    np.testing.assert_array_equal(_dense_bits(mask, S, blocks.k)[0], picked)
    assert not picked[3 * blocks.k :, : 2 * blocks.k].any()  # two whole key blocks and more of nothing, walked all the same
    o_want, _, _ = ia.indexed_attention_plain(q, k, v, qi, ki, w, topk=topk)
    np.testing.assert_allclose(o.transpose(0, 2, 1, 3), o_want, atol=3e-6)
    logits = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(k, H // KV, axis=2)) / np.sqrt(q.shape[-1])
    lse_want = jax.nn.logsumexp(jnp.where(picked, logits, -jnp.inf), axis=-1)
    np.testing.assert_allclose(lse, lse_want, atol=1e-5)


def _live_pairs(S, bq, bk):
    """Pairs of blocks with a key at or before a row, counted position by position."""
    first_key, last_row = np.arange(S // bk)[None, :] * bk, np.arange(S // bq)[:, None] * bq + bq - 1
    return int(np.count_nonzero(first_key <= last_row))


@pytest.mark.parametrize(
    "S,bq,bk,live",
    [
        (2048, 128, 512, 40),
        (4096, 128, 512, 144),
        (1024, 128, 128, 36),
        (16384, 128, 512, 2112),  # the cell: 8,448 steps a launch of 4 KV heads where the rectangle held 16,384
        (256, 128, 512, 2),  # shorter than a key block: two row blocks see the one
    ],
)
def test_launches_walk_only_their_live_blocks(S, bq, bk, live):
    """The real ``pallas_call`` grids of the attention's two launches (the
    forward, and since PR 68 ONE backward launch, which the trace knows as
    ``dsa_attn_dkv`` and which makes ``dq`` too) and of ``L_I``'s hold exactly
    the live pairs: a launch that skipped the dead ones' work (``pl.when``)
    would keep the rectangle."""
    blocks = ia.Blocks(bq, bk, 512, 64).fit(S)
    assert live == _live_pairs(S, blocks.q, blocks.k)
    B, H, KV, D, J, DI = 1, 32, 4, 16, 2, 8
    a = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(dims, dtype)  # noqa: E731
    mask = a(B, -(-(S // blocks.k) // 32), S, blocks.k, dtype=jnp.int32)

    def f(q, k, v, qi, ki, w, mask, lse_index):
        o, kl = ia.indexed_attention(q, k, v, qi, ki, w, mask, lse_index, blocks=blocks, interpret=True)
        return jnp.sum(o) + kl

    grids = _pallas_calls(f, a(B, S, H, D), a(B, S, KV, D), a(B, S, KV, D), a(B, S, J, DI), a(B, S, DI), a(B, S, J), mask, a(B, S))
    steps = (B, KV, live)
    assert grids == {"dsa_attn_fwd": steps, "dsa_probs": (B, live), "dsa_attn_dkv": steps}
    assert live * 2 > (S // blocks.q) * (S // blocks.k)  # the rectangle's other pairs, nearly half, are gone
    assert [len(t) for t in ia._steps(S, blocks).tables] == [live] * 3  # one walk for every launch: 12 bytes a step


def test_the_backward_is_one_launch_with_a_kv_heads_dk_and_dv_resident():
    """``dq``, ``dk`` and ``dv`` leave ONE ``pallas_call``; its second and
    third outputs are a KV head's whole ``dk`` and ``dv`` in float32, a block
    whose index does not move with the step (it stays in fast memory while the
    head's row blocks add to it, and is written once)."""
    S, B, H, KV, D = 256, 2, 4, 2, 16
    blocks = ia.Blocks(16, 64, 64, 16)
    a = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype)  # noqa: E731
    q, kv, rows = a(B, H, S, D), a(B, KV, S, D), a(B, H, S, ia._ROW_LANES, dtype=jnp.float32)
    mask = a(B, 1, S, blocks.k, dtype=jnp.int32)
    jaxpr = jax.make_jaxpr(lambda *ops: ia._attn_bwd(*ops, 0.25, blocks, True))(q, kv, kv, mask, q, rows, q)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "dsa_attn_dkv"
    mapping = call.params["grid_mapping"]
    assert tuple(mapping.grid) == (B, KV, _live_pairs(S, blocks.q, blocks.k))
    nk = S // blocks.k
    dq_aval, *keys_avals = (v.aval for v in call.outvars)
    assert dq_aval.dtype == jnp.bfloat16
    for aval, out in zip(keys_avals, list(mapping.block_mappings_output)[1:], strict=True):
        assert aval.shape == (B, KV, nk, blocks.k, D) and aval.dtype == jnp.float32
        assert tuple(out.block_aval.shape) == (1, 1, nk, blocks.k, D)
        # of the index map's operands (b, h, the step, three tables) the block's index reads b and h alone
        index_map = out.index_map_jaxpr.jaxpr
        assert not index_map.eqns and list(index_map.outvars[:2]) == list(index_map.invars[:2])
        assert [getattr(v, "val", None) for v in index_map.outvars[2:]] == [0, 0, 0]
    assert [o.dtype for o in jaxpr.out_avals] == [jnp.bfloat16] * 3  # rounded once, by XLA


def test_under_topk_is_dense_causal_attention():
    cfg = CASES["under_topk"]
    (q, k, v, qi, ki, w), _ = _operands(**cfg)

    @jax.jit  # one program: run operation by operation the interpreter's loops are 15 s of small compiles
    def kernels(q, k, v, qi, ki, w):
        mask, lse_index, _ = ia.select_keys(qi, ki, w, topk=cfg["topk"], blocks=cfg["blocks"], interpret=True)
        return ia.indexed_attention(q, k, v, qi, ki, w, mask, lse_index, blocks=cfg["blocks"], interpret=True)

    o, _ = kernels(q, k, v, qi, ki, w)
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
        return jax.jit(jax.grad(lambda *a: f(*a)[0], argnums=tuple(range(6))))(*ops)  # one program, not one an operation

    of_output, of_index_loss = grads(0.0, True), grads(1.0, False)
    for g in of_output[3:] + of_index_loss[:3]:
        assert float(jnp.max(jnp.abs(g))) == 0.0
    for g in of_output[:3] + of_index_loss[3:]:
        assert float(jnp.max(jnp.abs(g))) > 1e-3


def test_blocks_refuse_a_length_they_do_not_divide():
    assert ia.Blocks().refusal(16384) == "" and ia.Blocks().refusal(2048) == ""
    assert "does not divide" in ia.Blocks().refusal(16384 + 128)
    assert ia.Blocks().fit(256) == ia.Blocks(128, 256, 256, 64)
    # the backward launch keeps a KV head's dk and dv in fast memory: 32 MiB at the cell's shapes, 64 at twice
    # its length, and past three quarters of the kernels' scoped memory the refusal gives the count
    assert ia.Blocks().refusal(16384, 128) == ia.Blocks().refusal(32768, 128) == ia.Blocks().refusal(16384, 256) == ""
    for seq, head_dim, mib in ((65536, 128, 128), (32768, 256, 128), (49152, 128, 96)):
        refusal = ia.Blocks().refusal(seq, head_dim)
        assert f"take {mib} MiB of fast memory in the backward launch, over 75 of the 100 MiB" in refusal, refusal
