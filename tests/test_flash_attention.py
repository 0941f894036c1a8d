"""Flash attention kernel (ops/flash_attention.py), interpret mode.

CPU CI runs the Pallas interpreter; the compiled path is checked on the chip
by ``chip_smoke.py`` leg B (Mosaic custom call in the HLO, same reference).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

from torchft_tpu.models.llama import Llama, LlamaConfig
from torchft_tpu.ops import flash_attention as fa
from torchft_tpu.ops.flash_attention import KEPT_NAMES, eva_attention, flash_attention


def _ref_attention(q, k, v, causal=True):
    B, S, H, D = q.shape
    groups = H // k.shape[2]
    kf = jnp.repeat(k, groups, axis=2)
    vf = jnp.repeat(v, groups, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kf).astype(jnp.float32) / np.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf)


def _qkv(B, S, H, KV, D, dtype=jnp.float32):
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (B, S, H, D), dtype),
        jax.random.normal(kk, (B, S, KV, D), dtype),
        jax.random.normal(kv, (B, S, KV, D), dtype),
    )


@pytest.mark.parametrize(
    "B,S,H,KV,D,causal",
    [
        (2, 256, 4, 2, 64, True),  # GQA
        (1, 256, 4, 4, 128, True),  # MHA, wide head
        (2, 256, 8, 1, 64, True),  # MQA
        (2, 256, 4, 2, 64, False),  # bidirectional
        (1, 1024, 2, 1, 64, True),  # multiple 512-blocks
        (1, 2048, 8, 1, 16, True),  # four row blocks: dead, whole and edge blocks at once, a group of 8
        (1, 2048, 16, 1, 8, True),  # the same at a group of 16
    ],
)
def test_forward_matches_reference(B, S, H, KV, D, causal) -> None:
    q, k, v = _qkv(B, S, H, KV, D)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = _ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize(
    "causal,S,bq,bk",
    [
        (True, 256, 512, 512),  # single block (clamped)
        (False, 256, 512, 512),
        (True, 512, 128, 256),  # multi-block dq/dkv accumulation + g_q_map
        (False, 512, 256, 128),
        (True, 256, 64, 32),  # four row blocks, eight key blocks: whole, edge and dead blocks in one launch
        (True, 256, 32, 64),  # and the other way round
    ],
)
def test_backward_matches_reference(causal, S, bq, bk) -> None:
    q, k, v = _qkv(2, S, 4, 2, 64)

    def loss_flash(q, k, v):
        return jnp.sum(
            jnp.sin(
                flash_attention(
                    q, k, v, causal=causal, block_q=bq, block_k=bk,
                    interpret=True,
                )
            )
        )

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(_ref_attention(q, k, v, causal=causal)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
            err_msg=f"d{name}",
        )


def test_block_sizes_do_not_change_math() -> None:
    q, k, v = _qkv(1, 512, 4, 2, 64)
    a = flash_attention(q, k, v, block_q=128, block_k=256, interpret=True)
    b = flash_attention(q, k, v, block_q=512, block_k=512, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


def test_validation() -> None:
    q, k, v = _qkv(1, 256, 4, 3, 64)
    with pytest.raises(ValueError, match="GQA"):
        flash_attention(q, k, v, interpret=True)
    q, k, v = _qkv(1, 320, 4, 2, 64)
    with pytest.raises(ValueError, match="not divisible"):
        flash_attention(q, k, v, block_q=256, block_k=256, interpret=True)


def test_llama_dispatch_gating(monkeypatch) -> None:
    """TORCHFT_FLASH=0 kills the kernel; =1 forces it (interpret off-TPU);
    auto stays off on multi-device CPU (pallas_call is not partitionable)."""
    cfg = LlamaConfig(
        vocab_size=128, dim=64, n_layers=1, n_heads=4, n_kv_heads=2,
        ffn_hidden=128, max_seq_len=256, dtype=jnp.float32,
    )
    model = Llama(cfg)
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    assert not model._use_flash(256)
    monkeypatch.setenv("TORCHFT_FLASH", "1")
    assert model._use_flash(256)
    assert not model._use_flash(100)  # shape-gated even when forced
    monkeypatch.delenv("TORCHFT_FLASH")
    assert not model._use_flash(256)  # auto: CPU backend → naive


def test_llama_flash_equals_naive_loss(monkeypatch) -> None:
    """End-to-end: the full model under forced flash (interpret) matches
    the naive attention path."""
    cfg = LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_hidden=128, max_seq_len=256, dtype=jnp.float32,
    )
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 256), 0, 256)
    batch = (tokens, jnp.roll(tokens, -1, axis=1))

    monkeypatch.setenv("TORCHFT_FLASH", "0")
    ref_loss, ref_grads = jax.value_and_grad(model.loss)(params, batch)
    monkeypatch.setenv("TORCHFT_FLASH", "1")
    loss, grads = jax.value_and_grad(model.loss)(params, batch)

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(ref_grads),
        jax.tree_util.tree_leaves_with_path(grads),
    ):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=2e-4, atol=1e-5,
            err_msg=str(path),
        )


def test_sharded_flash_matches_reference() -> None:
    """shard_map variant over dp=2 x tp=2: local kernels, zero comms, same
    math as the dense reference."""
    from torchft_tpu.parallel.mesh import make_mesh
    from torchft_tpu.ops.flash_attention import flash_attention_sharded

    mesh = make_mesh(dp=2, tp=2, fsdp=2)
    q, k, v = _qkv(4, 256, 4, 2, 64)
    with mesh:
        out = jax.jit(
            lambda q, k, v: flash_attention_sharded(
                q, k, v, mesh=mesh, interpret=True
            )
        )(q, k, v)
    ref = _ref_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_sharded_flash_validation() -> None:
    from torchft_tpu.parallel.mesh import make_mesh
    from torchft_tpu.ops.flash_attention import flash_attention_sharded

    mesh = make_mesh(dp=2, tp=2)
    q, k, v = _qkv(3, 256, 4, 2, 64)  # B=3 not divisible by dp=2
    with pytest.raises(ValueError, match=r"B%\(dp\*fsdp\)"):
        flash_attention_sharded(q, k, v, mesh=mesh, interpret=True)


def test_hsdp_model_sharded_flash_equals_naive(monkeypatch) -> None:
    """Full Llama grad step on a dp x tp x fsdp mesh with the sharded flash
    dispatch forced: loss + grads match the naive path (the multi-chip TPU
    configuration, exercised via interpret on the CPU mesh)."""
    from torchft_tpu.parallel.hsdp import fsdp_shardings
    from torchft_tpu.parallel.mesh import make_mesh, shard_pytree

    cfg = LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_hidden=128, max_seq_len=256, dtype=jnp.float32,
    )
    mesh = make_mesh(dp=2, tp=2, fsdp=2)
    model = Llama(cfg, mesh=mesh)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 256), 0, 256)
    batch = (tokens, jnp.roll(tokens, -1, axis=1))

    monkeypatch.setenv("TORCHFT_FLASH", "0")
    ref_loss, ref_grads = jax.value_and_grad(model.loss)(params, batch)

    monkeypatch.setenv("TORCHFT_FLASH", "1")
    assert model._flash_mesh() is mesh
    params_sh = shard_pytree(params, model.param_specs(), mesh)
    batch_sh_specs = fsdp_shardings(model, mesh)[1]
    batch_sh = tuple(
        jax.device_put(b, sh) for b, sh in zip(batch, batch_sh_specs)
    )
    with mesh:
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(
            params_sh, batch_sh
        )

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(ref_grads),
        jax.tree_util.tree_leaves_with_path(grads),
    ):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=2e-4, atol=1e-5,
            err_msg=str(path),
        )


def test_flash_lse_merge_property() -> None:
    """The (o, lse) pair merges exactly: attention over [K1;K2] equals the
    logsumexp-merge of attention over K1 and K2 — the invariant the
    flash-accelerated ring relies on."""
    from torchft_tpu.ops.flash_attention import flash_attention_lse

    q, k, v = _qkv(1, 256, 4, 2, 64)
    o_all, lse_all = flash_attention_lse(q, k, v, causal=False, interpret=True)

    k1, k2 = k[:, :128], k[:, 128:]
    v1, v2 = v[:, :128], v[:, 128:]
    o1, lse1 = flash_attention_lse(q, k1, v1, causal=False, interpret=True)
    o2, lse2 = flash_attention_lse(q, k2, v2, causal=False, interpret=True)
    lse = jnp.logaddexp(lse1, lse2)
    o = (
        o1.astype(jnp.float32) * jnp.exp(lse1 - lse)[..., None]
        + o2.astype(jnp.float32) * jnp.exp(lse2 - lse)[..., None]
    )
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(o_all), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(lse_all), rtol=1e-5, atol=1e-5
    )


def test_flash_ring_attention_matches_dense(monkeypatch) -> None:
    """Ring attention with per-block flash kernels (TORCHFT_FLASH=1,
    interpret) == dense causal attention, forward and backward."""
    from torchft_tpu.parallel.mesh import make_mesh
    from torchft_tpu.parallel.ring_attention import ring_attention_sharded

    monkeypatch.setenv("TORCHFT_FLASH", "1")
    mesh = make_mesh(sp=4, tp=2)
    q, k, v = _qkv(1, 512, 4, 2, 64)  # S_blk = 128 per sp rank

    def ring_loss(q, k, v):
        with mesh:
            return jnp.sum(
                jnp.sin(ring_attention_sharded(q, k, v, mesh=mesh))
            )

    def dense_loss(q, k, v):
        return jnp.sum(jnp.sin(_ref_attention(q, k, v, causal=True)))

    with mesh:
        out = jax.jit(
            lambda q, k, v: ring_attention_sharded(q, k, v, mesh=mesh)
        )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(_ref_attention(q, k, v, causal=True)),
        rtol=2e-4, atol=2e-4,
    )
    g_ring = jax.jit(jax.grad(ring_loss, (0, 1, 2)))(q, k, v)
    g_dense = jax.grad(dense_loss, (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ring, g_dense):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3,
            err_msg=f"d{name}",
        )


# -- v heads of another size than q's and k's (latent attention: 192 / 128) ----


@pytest.mark.parametrize(
    "H,KV,D,Dv,S,bq,bk,window",
    [
        (2, 2, 192, 128, 256, 128, 128, None),  # Ling-3.0-flash's MLA heads, several blocks
        (4, 2, 48, 32, 256, 512, 512, None),  # the toy widths, grouped, one block
        (2, 2, 32, 64, 256, 128, 256, None),  # v wider than q and k
        # JoyAI's and Ling's heads over four row blocks and eight key blocks:
        # dead, whole and edge blocks in every launch
        (2, 2, 192, 128, 256, 64, 32, None),
        (2, 2, 192, 128, 256, 32, 64, None),  # the blocks the other way round
        (2, 2, 192, 128, 256, 64, 32, 128),  # a window that is a whole number of blocks
        (2, 2, 192, 128, 256, 32, 64, 100),  # and one that is not
    ],
)
def test_value_heads_of_another_size(H, KV, D, Dv, S, bq, bk, window) -> None:
    """Forward and the three gradients against plain attention.  The same
    tolerances as for equal sizes above: float32's rounding through a
    softmax over 256 keys; the output and dv have v's head size."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (2, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (2, S, KV, D), jnp.float32)
    v = jax.random.normal(kv, (2, S, KV, Dv), jnp.float32)
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, block_q=bq, block_k=bk, window=window, interpret=True
    )
    ref = _ref_attention if window is None else lambda q, k, v: _ref_windowed(q, k, v, window)  # noqa: E731
    out = flash(q, k, v)
    assert out.shape == (2, S, H, Dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)), rtol=2e-5, atol=2e-5)
    g_flash = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *a: jnp.sum(jnp.sin(ref(*a))), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_flash, g_ref):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4, err_msg=f"d{name}")


def test_head_sizes_that_do_not_fit_are_refused() -> None:
    q, k, v = _qkv(1, 256, 2, 2, 64)
    with pytest.raises(ValueError):
        flash_attention(q, k[..., :32], v, interpret=True)  # q and k differ
    with pytest.raises(ValueError):
        flash_attention(q, k, v[:, :128], interpret=True)  # v shorter than k


# ---------------------------------------------------------------------------
# a sliding window: the kernels walk only the blocks the window touches
# ---------------------------------------------------------------------------


def _ref_windowed(q, k, v, window):
    """Plain attention under an explicit mask: query ``i`` sees keys ``j``
    with ``i - window < j <= i``."""
    S, D = q.shape[1], q.shape[3]
    groups = q.shape[2] // k.shape[2]
    kf, vf = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kf).astype(jnp.float32) / np.sqrt(D)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    s = jnp.where((j <= i) & (j > i - window), s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1).astype(q.dtype), vf)


def _pallas_calls(fn, *args):
    """(name, grid) of every ``pallas_call`` in ``fn``'s gradient program."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"], tuple(eqn.params["grid_mapping"].grid)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=(0, 1, 2)))(*args).jaxpr)
    return dict(found)


def test_window_walks_only_its_blocks_and_names_its_programs() -> None:
    """A launch's grid holds exactly the pairs of blocks with a live pair of
    positions, windowed or not: a walk that masked the dead ones would keep
    the rectangle."""
    q, k, v = _qkv(1, 2048, 8, 1, 16)
    blocks = dict(block_q=128, block_k=128, interpret=True)
    windowed = _pallas_calls(lambda q, k, v: flash_attention(q, k, v, window=256, **blocks), q, k, v)
    # three key blocks a row block, but one and two for the first two: nothing lies before the sequence
    assert windowed == {"flash_win_fwd": (1, 8, 45), "flash_win_dq": (1, 8, 45), "flash_win_dkv": (1, 1, 8 * 45)}
    full = _pallas_calls(lambda q, k, v: flash_attention(q, k, v, **blocks), q, k, v)
    live = 16 * 17 // 2  # nq (nq + 1) / 2 at equal blocks, of 256
    assert full == {"flash_fwd": (1, 8, live), "flash_dq": (1, 8, live), "flash_dkv": (1, 1, 8 * live)}
    # a window that covers the sequence IS causal attention: the full layers' programs
    assert _pallas_calls(lambda q, k, v: flash_attention(q, k, v, window=2048, **blocks), q, k, v) == full
    # no multiple of a block: one more block at the far edge
    odd = _pallas_calls(lambda q, k, v: flash_attention(q, k, v, window=258, **blocks), q, k, v)
    assert odd["flash_win_fwd"] == (1, 8, 45 + 13) and odd["flash_win_dkv"] == (1, 1, 8 * 58)


@pytest.mark.parametrize("window,causal", [(0, True), (-3, True), (2.5, True), (True, True), (64, False)])
def test_window_validation(window, causal) -> None:
    q, k, v = _qkv(1, 256, 4, 2, 64)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=causal, window=window, interpret=True)


# ---------------------------------------------------------------------------
# what the forward rule keeps for the backward pass
# ---------------------------------------------------------------------------


def _kept_case(kind):
    """(a function of its operands, the operands, o's heads-major shape) of a
    full layer, a window and two key sources, several blocks each."""
    B, S, H, KV, D, Dv = 1, 128, 4, 2, 16, 8
    kq, kk, kv, kp = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, KV, D), jnp.float32)
    blocks = dict(block_q=32, block_k=32, interpret=True)
    if kind == "eva":
        v = jax.random.normal(kv, (B, S, KV, D), jnp.float32)
        pooled = jax.random.normal(kp, (2, B, S // 8, KV, D), jnp.float32)
        return functools.partial(eva_attention, window=64, **blocks), (q, k, v, *pooled), (B, H, S, D)
    v = jax.random.normal(kv, (B, S, KV, Dv), jnp.float32)
    window = dict(full=None, window=40)[kind]
    return functools.partial(flash_attention, window=window, **blocks), (q, k, v), (B, H, S, Dv)


@pytest.mark.parametrize("kind", ["full", "window", "eva"])
def test_what_the_forward_rule_keeps_is_o_and_one_number_a_row(kind, capsys) -> None:
    """Under ``save_only_these_names(*KEPT_NAMES)`` a rematerialised caller
    holds ``o`` [B, H, S, Dv] and the row statistics as ONE float32 a row,
    [B, H, S]: the kernels' trailing 8 lanes, padded to 128 in HBM, are
    spread again in the backward rule and never kept."""
    fn, operands, o_shape = _kept_case(kind)
    print_saved_residuals(
        jax.checkpoint(fn, policy=jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)), *operands
    )
    # a line a residual, ``f32[1,4,128] named 'flash_lse' from <where>``; the operands are always kept
    kept = [line.split()[0] for line in capsys.readouterr().out.splitlines() if " from the argument " not in line]
    shape = lambda dims: "f32[" + ",".join(map(str, dims)) + "]"  # noqa: E731
    assert sorted(kept) == sorted([shape(o_shape), shape(o_shape[:3])])


@pytest.mark.parametrize("kind", ["full", "window", "eva"])
def test_gradients_through_what_is_kept_are_those_of_a_second_forward_bit_for_bit(kind) -> None:
    """The same ``lse`` values reach the same backward kernels whether the
    caller kept them (one number a row) or ran ``flash_fwd`` again.  Operation
    by operation and not one program: XLA fuses ``delta``'s row sums
    differently around a kept ``o`` and a recomputed one, which reads one
    float32 rounding in ``dq`` and ``dk`` on the CPU and is no property of
    the rule."""
    fn, operands, _ = _kept_case(kind)
    policies = jax.checkpoint_policies

    def gradients(policy):
        layer = jax.checkpoint(fn, policy=policy)
        return jax.grad(lambda *a: jnp.sum(jnp.sin(layer(*a))), argnums=tuple(range(len(operands))))(*operands)

    kept, again = gradients(policies.save_only_these_names(*KEPT_NAMES)), gradients(policies.nothing_saveable)
    for name, a, b in zip(("q", "k", "v", "k_pooled", "v_pooled"), kept, again):
        assert float(jnp.max(jnp.abs(a))) > 0, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# the forward's keys-major body over every kind of launch that reaches ``_fwd``
# ---------------------------------------------------------------------------


def _dense_hm(q, k, v, seen, scale):
    """Plain attention over heads-major operands under ``seen`` [Sq, Sk]
    (None: every key): (o [B, H, Sq, Dv], lse [B, H, Sq])."""
    groups = q.shape[1] // k.shape[1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, groups, axis=1)) * scale
    if seen is not None:
        s = jnp.where(seen, s, -1e30)
    lse = jax.nn.logsumexp(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]), jnp.repeat(v, groups, axis=1)), lse


def _sees(Sq, Sk, rule, causal):
    """[Sq, Sk]: which keys a query sees, position by position."""
    if not causal:
        return None
    i, j = np.arange(Sq)[:, None], np.arange(Sk)[None, :]
    if isinstance(rule, fa.Pooled):
        token = j - rule.summaries
        of_summaries = (j < rule.summaries) & (j // rule.per_window < i // rule.window)
        return of_summaries | ((token >= 0) & (token // rule.window == i // rule.window) & (token <= i))
    return (j <= i) & (j > i - (Sq if rule is None else rule))


@pytest.mark.parametrize(
    "H,KV,D,Dv,Sq,Sk,bq,bk,rule,causal",
    [
        pytest.param(2, 2, 32, 32, 256, 256, 64, 64, None, True, id="causal-whole-group-1"),
        pytest.param(8, 1, 16, 16, 256, 256, 32, 64, 100, True, id="window-no-multiple-of-a-block-group-8"),
        pytest.param(16, 1, 8, 8, 256, 256, 64, 128, None, True, id="causal-whole-group-16"),
        # 32 summaries of 8 positions and their padding to a key block of 64, then the tokens
        pytest.param(4, 2, 16, 16, 256, 320, 32, 64, fa.Pooled(64, 8, 64), True, id="pooled-two-key-sources"),
        pytest.param(4, 2, 32, 16, 128, 256, 64, 32, None, False, id="no-causality-Sq-not-Sk"),
        pytest.param(2, 2, 192, 128, 128, 128, 64, 32, None, True, id="latent-heads-192-128"),
        pytest.param(4, 2, 64, 128, 128, 128, 32, 64, 72, True, id="differential-heads-64-128-window"),
    ],
)
def test_keys_major_forward_and_its_rules_agree_with_plain_attention(H, KV, D, Dv, Sq, Sk, bq, bk, rule, causal) -> None:
    """``_fwd``'s ``o`` and ``lse`` against plain attention, the row statistic
    leaving as ``[B, H, S]`` (a row of the kernel's keys-major tile, not the
    ``[B, H, S, 8]`` the backward kernels read), and the gradients of the
    forward rule that takes them (``_flash_hm``; without causality
    ``_flash_hm_lse``, whose ``lse`` takes a cotangent too) against jax's
    through plain attention."""
    kq, kk, kv, kd, kl = jax.random.split(jax.random.PRNGKey(11), 5)
    q = jax.random.normal(kq, (1, H, Sq, D), jnp.float32)
    k = jax.random.normal(kk, (1, KV, Sk, D), jnp.float32)
    v = jax.random.normal(kv, (1, KV, Sk, Dv), jnp.float32)
    do = jax.random.normal(kd, (1, H, Sq, Dv), jnp.float32)
    dlse = jax.random.normal(kl, (1, H, Sq), jnp.float32)
    scale = 1.0 / float(np.sqrt(D))
    dense = functools.partial(_dense_hm, seen=_sees(Sq, Sk, rule, causal), scale=scale)

    o, lse = jax.jit(lambda *a: fa._fwd(*a, scale, causal, bq, bk, True, rule))(q, k, v)
    assert o.shape == (1, H, Sq, Dv) and lse.shape == (1, H, Sq) and lse.dtype == jnp.float32
    want_o, want_lse = dense(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse), rtol=2e-5, atol=2e-5)

    if causal:
        rules = lambda *a: jnp.sum(fa._flash_hm(*a, scale, True, bq, bk, True, rule) * do)  # noqa: E731
        plain = lambda *a: jnp.sum(dense(*a)[0] * do)  # noqa: E731
    else:
        both = lambda o, lse: jnp.sum(o * do) + jnp.sum(lse * dlse)  # noqa: E731
        rules = lambda *a: both(*fa._flash_hm_lse(*a, scale, False, bq, bk, True))  # noqa: E731
        plain = lambda *a: both(*dense(*a))  # noqa: E731
    got = jax.jit(jax.grad(rules, argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(plain, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4, err_msg=name)
