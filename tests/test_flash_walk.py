"""The walk of ``ops/flash_attention.py`` (PR 50): every (row block, key
block) pair of a causal launch held live or dead as plain attention's mask
would, the walk's tables, what they may hold, and the rectangle that a launch
without causality keeps, in interpret mode.  A file of its own beside
``tests/test_flash_attention.py`` so that tier-1's workers share the two."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_flash_attention import _pallas_calls
from torchft_tpu.ops import flash_attention as fa


def _plain_pairs(S, window):
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    return (j <= i) & (j > i - (S if window is None else window))


@pytest.mark.parametrize(
    "S,bq,bk,window,steps",
    [
        (16384, 512, 512, None, 528),  # the cells' full layers, where a rectangle of 1,024 is 48.4 % dead
        (16384, 512, 512, 2048, 150),  # Trinity's windowed layers, where five a row block is 6.3 % dead
        (8192, 512, 512, None, 136),  # Ling
        (2048, 512, 512, None, 10),  # Mistral
        (512, 128, 64, 200, 18),  # by hand: 2 + 4 + 6 + 6 live blocks a row block
        (512, 64, 128, 200, 18),
        (512, 64, 64, 1, 8),  # the query's own position alone: the diagonal blocks
    ],
)
def test_the_walk_holds_every_live_block_of_plain_attention_and_no_other(S, bq, bk, window, steps) -> None:
    """A block against the mask of plain attention, position by position, and
    the walks over it: their steps, their order, their flags."""
    live = fa._live_blocks(S // bq, S // bk, bq, bk, window)
    if S <= 2048:
        alive = _plain_pairs(S, window).reshape(S // bq, bq, S // bk, bk).transpose(0, 2, 1, 3)
        np.testing.assert_array_equal(live, alive.any(axis=(2, 3)))
    rows, keys = fa._walk(live), fa._walk(live, 4)
    assert rows.steps == steps == np.count_nonzero(live) and rows.member is None
    assert keys.steps == 4 * steps  # every member of a group of 4 in turn
    assert live[rows.q, rows.k].all() and live[keys.q, keys.k].all()
    # a row block's visits ascend and end where the next one's begin; first and last are flagged once a block
    assert np.all(np.diff(rows.q) >= 0) and np.all((np.diff(rows.k) > 0) | (np.diff(rows.q) > 0))
    assert np.all(np.diff(keys.k) >= 0)
    for walk, outer in ((rows, rows.q), (keys, keys.k)):
        assert np.count_nonzero(walk.flags & fa._FIRST) == np.count_nonzero(walk.flags & fa._LAST) == len(set(outer))
    # a key block's row blocks ascend within a member, and the members follow one another
    of_last_key = keys.k == keys.k[-1]
    np.testing.assert_array_equal(keys.member[of_last_key], np.repeat(np.arange(4), np.count_nonzero(live[:, -1])))
    assert [len(t) for t in keys.tables] == [4 * steps] * 4 and all(t.dtype == jnp.int32 for t in keys.tables)


def test_tables_over_the_chip_s_smem_are_refused_by_name() -> None:
    """``dkv``'s tables grow with the live pairs times the GQA group: what the
    compiler would refuse with a count of SMEM is refused here with the cause."""
    live = fa._live_blocks(96, 96, 512, 512, None)  # 49,152 positions
    assert len(fa._walk(live).tables) == 3  # forward's and dq's: 4,656 steps
    with pytest.raises(ValueError, match="74496 grid steps.*4 int32 tables.*SMEM.*every member of the GQA group"):
        fa._walk(live, 16).tables
    fits = fa._walk(fa._live_blocks(86, 86, 512, 512, None), 16)  # 44,032 positions: compiled for a described v5e
    assert 16 * fits.steps == 957_696 <= fa._TABLE_BYTES and len(fits.tables) == 4


def _dense(q, k, v):
    """Attention without a mask, heads-major, and its logsumexp."""
    groups = q.shape[1] // k.shape[1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, groups, axis=1)) * 0.125
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), jnp.repeat(v, groups, axis=1)), jax.nn.logsumexp(s, axis=-1)


@pytest.mark.parametrize(
    "H,KV,Sq,Sk,bq,bk",
    [
        (4, 2, 256, 256, 64, 64),  # a square of 4 x 4 blocks, a group of 2
        (8, 1, 128, 512, 64, 128),  # ring attention's off-diagonal step: other keys than rows, a group of 8
        (2, 2, 256, 128, 32, 64),  # more rows than keys
        (16, 1, 64, 64, 64, 64),  # one block, a group of 16
    ],
)
def test_without_causality_the_grid_is_the_rectangle_and_holds_no_table(H, KV, Sq, Sk, bq, bk) -> None:
    """No block is dead without causality, so nothing is gained by tables:
    the launches keep affine index maps over ``(rows, keys)`` and ``(keys,
    group x rows)``, and give plain attention's numbers."""
    kq, kk, kv, kd = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(kq, (1, H, Sq, 64), jnp.float32)
    k = jax.random.normal(kk, (1, KV, Sk, 64), jnp.float32)
    v = jax.random.normal(kv, (1, KV, Sk, 32), jnp.float32)
    do = jax.random.normal(kd, (1, H, Sq, 32), jnp.float32)

    def run(q, k, v):
        o, lse = fa._fwd(q, k, v, 0.125, False, bq, bk, True)
        return (o, lse), fa._bwd(0.125, False, bq, bk, True, (q, k, v, o, fa._spread(lse)), do)

    launches = []
    jaxpr = jax.make_jaxpr(run)(q, k, v).jaxpr
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            mapping = eqn.params["grid_mapping"]
            launches.append((eqn.params["name"], tuple(mapping.grid), mapping.num_index_operands))
    nq, nk = Sq // bq, Sk // bk
    assert launches == [
        ("flash_fwd", (1, H, nq, nk), 0), ("flash_dq", (1, H, nq, nk), 0), ("flash_dkv", (1, KV, nk, H // KV * nq), 0),
    ]
    (o, lse), grads = run(q, k, v)
    want_o, want_lse = _dense(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse), rtol=2e-5, atol=2e-5)
    want = jax.grad(lambda q, k, v: jnp.sum(_dense(q, k, v)[0] * do), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), grads, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4, err_msg=name)


def test_a_causal_launch_hands_its_walk_in_as_tables() -> None:
    """The other side of the fork: three tables forward and in ``dq``, a
    fourth (the group's member) in ``dkv``."""
    q = jnp.zeros((1, 256, 4, 16))
    k = v = jnp.zeros((1, 256, 2, 16))
    grids = _pallas_calls(lambda q, k, v: fa.flash_attention(q, k, v, block_q=64, block_k=64, interpret=True), q, k, v)
    assert grids == {"flash_fwd": (1, 4, 10), "flash_dq": (1, 4, 10), "flash_dkv": (1, 2, 2 * 10)}
    live = fa._live_blocks(4, 4, 64, 64, None)
    assert [len(launch(4, 4, 64, 64, 2, None, True)[1]) for launch in (fa._row_launch, fa._key_launch)] == [3, 4]
    assert [launch(4, 4, 64, 64, 2, None, False)[:2] for launch in (fa._row_launch, fa._key_launch)] == [((4, 4), ()), ((4, 8), ())]
    assert np.count_nonzero(live) == 10
