"""``parallel/moe.py`` ``RoutedExperts`` against the plain reference's
expert layer (``ftbench/architectures/ling_hybrid_reference.py``): float32
on the CPU, seeded weights, 16 experts of 32 in 4 groups of which 2, 4 a
token, one shared expert.

Tolerance: both sides add a token's (at most 4) expert outputs and the
shared expert's in float32; the program sorts rows and multiplies group by
group, the reference runs every expert over every token and masks.  5e-6
absolute on outputs of order one is float32's rounding through three
products of 64 and 32 terms (read: 1e-6).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ftbench.architectures import ling_hybrid_reference as ref
from torchft_tpu.parallel.moe import RoutedExperts, RoutedExpertsConfig

TOL = 5e-6
E, HELD = 16, 4
REF_CFG = dict(
    n_group=4, topk_group=2, num_experts_per_tok=4, norm_topk_prob=True,
    routed_scaling_factor=2.5, assumed=dict(balance_loss_weight=1e-4),
)


def _layer(first=4, count=HELD, **over):
    cfg = RoutedExpertsConfig(
        dim=64, expert_hidden=32, num_experts=E, experts_held=(first, count), top_k=4,
        n_group=4, topk_group=2, routed_scaling_factor=2.5, shared_hidden=32,
        balance_loss_weight=1e-4, dtype=jnp.float32,
    )
    return RoutedExperts(dataclasses.replace(cfg, **over))


def _x(seed=1, B=2, S=64):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, S, 64), jnp.float32)


def test_part_of_the_held_experts_agrees_with_the_reference():
    layer = _layer()
    params = layer.init(jax.random.PRNGKey(0))
    params["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(9), (E,))
    x = _x()
    with jax.default_matmul_precision("highest"):
        out, load, balance = jax.jit(layer.apply)(params, x)
        want, want_load, want_balance = ref.moe_layer(x, params, REF_CFG, (4, HELD))
    assert float(jnp.max(jnp.abs(out - want))) < TOL
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))
    assert float(load.sum()) == x.shape[0] * x.shape[1] * 4  # nothing dropped: 4 choices a token
    assert float(balance) == pytest.approx(float(want_balance), rel=1e-5)
    assert layer.path == "ragged_dot"  # off the TPU


def test_bias_changes_the_choice_and_not_the_weights():
    """A bias that lifts one expert over its rival changes WHICH experts a
    token takes; the weights of the chosen stay their unbiased scores."""
    layer = _layer()
    params = layer.init(jax.random.PRNGKey(0))
    x = _x(2).reshape(-1, 64)
    chosen0, weights0, scores = layer.route(params, x)
    params["bias"] = params["bias"].at[5].set(0.3)
    chosen1, weights1, _ = layer.route(params, x)
    took5_before = np.asarray((chosen0 == 5).any(axis=1))
    took5_after = np.asarray((chosen1 == 5).any(axis=1))
    assert took5_after.sum() > took5_before.sum()  # the choice moved
    assert took5_after[took5_before].all()
    # weights: the unbiased scores of the chosen, normalised over the 4, times 2.5
    picked = np.take_along_axis(np.asarray(scores), np.asarray(chosen1), axis=1)
    np.testing.assert_allclose(
        np.asarray(weights1), 2.5 * picked / picked.sum(axis=1, keepdims=True), rtol=1e-6
    )
    # and the reference, given the same bias, chooses the same experts
    want_weights, want_chosen, _ = ref.route(x, params["router"], params["bias"], REF_CFG)
    got = np.zeros((x.shape[0], E), bool)
    np.put_along_axis(got, np.asarray(chosen1), True, axis=1)
    np.testing.assert_array_equal(got, np.asarray(want_chosen))
    dense = np.zeros((x.shape[0], E), np.float32)
    np.put_along_axis(dense, np.asarray(chosen1), np.asarray(weights1), axis=1)
    np.testing.assert_allclose(dense, np.asarray(want_weights), rtol=1e-6, atol=1e-7)


def test_groups_limit_the_choice():
    layer = _layer()
    params = layer.init(jax.random.PRNGKey(0))
    chosen, _, _ = layer.route(params, _x(3).reshape(-1, 64))
    groups = np.asarray(chosen) // (E // 4)
    assert all(len(set(row)) <= 2 for row in groups)  # 2 of the 4 groups


def test_the_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST: four chips, each told another quarter of the 16
    experts and given the same router, tokens and shared expert.  Their
    parts, the shared expert counted once, add up to what the reference
    gives for the whole layer with every expert in one place."""
    whole = _layer(0, E)
    params = whole.init(jax.random.PRNGKey(0))
    params["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(9), (E,))
    x = _x(4)
    with jax.default_matmul_precision("highest"):
        want, want_load, _ = ref.moe_layer(x, params, REF_CFG, (0, E))
        shared, _, _ = ref.moe_layer(
            x, {**params, "w_gate": params["w_gate"][:0], "w_up": params["w_up"][:0],
                "w_down": params["w_down"][:0]}, REF_CFG, (0, 0),
        )
        total = jnp.zeros_like(want)
        for first in range(0, E, HELD):
            mine = {k: (v[first : first + HELD] if k in ("w_gate", "w_up", "w_down") else v)
                    for k, v in params.items()}
            part, load, _ = jax.jit(_layer(first, HELD).apply)(mine, x)
            np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))  # every share scores all 16
            total = total + (part - shared)
        total = total + shared
    assert float(jnp.max(jnp.abs(total - want))) < 4 * TOL


def test_a_load_over_the_usual_buffer_drops_nothing():
    """Every token on the held experts (a router that knows only them):
    the rows pass the buffer of four times the uniform load, the
    ``lax.cond`` takes the full-size one, and the result is still the
    reference's."""
    layer = _layer(0, 4, num_experts=64, n_group=4, topk_group=1)
    params = layer.init(jax.random.PRNGKey(0))
    params["bias"] = jnp.zeros((64,)).at[:4].set(10.0)  # group 0's first four, always
    x = _x(5, B=2, S=256)
    cfg = dict(REF_CFG, topk_group=1)
    with jax.default_matmul_precision("highest"):
        out, load, _ = jax.jit(layer.apply)(params, x)
        want, _, _ = ref.moe_layer(x, params, cfg, (0, 4))
    assert float(load[:4].sum()) == 512 * 4 > 4 * 512 * 4 * 4 / 64  # past the usual buffer
    assert float(jnp.max(jnp.abs(out - want))) < TOL


def test_swiglu_clamp_where_a_layers_entry_is_not_zero():
    layer = _layer()
    params = layer.init(jax.random.PRNGKey(0))
    x = 4.0 * _x(6)
    with jax.default_matmul_precision("highest"):
        out, _, _ = layer.apply(params, x, 0.5, 0.25)
        want, _, _ = ref.moe_layer(x, params, REF_CFG, (4, HELD), 0.5, 0.25)
        free, _, _ = layer.apply(params, x)
    assert float(jnp.max(jnp.abs(out - want))) < TOL
    assert float(jnp.max(jnp.abs(out - free))) > 1e-2  # the clamp bites at these sizes


def test_gradients_reach_router_and_experts_and_never_the_bias():
    layer = _layer()
    params = layer.init(jax.random.PRNGKey(0))
    x = _x(7)

    def loss(p, f):
        out, _, balance = f(p)
        return jnp.sum(out ** 2) + balance

    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p: loss(p, lambda p: layer.apply(p, x)))(params)
        want = jax.grad(lambda p: loss(p, lambda p: ref.moe_layer(x, p, REF_CFG, (4, HELD))))(params)
    assert float(jnp.max(jnp.abs(got["bias"]))) == 0.0
    for name in params:
        scale = float(jnp.max(jnp.abs(want[name]))) + 1e-6
        assert float(jnp.max(jnp.abs(got[name] - want[name]))) < 2e-5 * scale + 1e-6, name


def test_rows_a_grouped_kernel_leaves_unwritten_reach_nothing(monkeypatch):
    """``megablox.gmm`` writes only the rows its groups cover, in the
    backward pass too; past them lies whatever the buffer held (on the chip
    a NaN after six steps: PERF.md section 6, PR 29).  Here the grouped
    product is made to leave NaN there, both ways: the result and every
    gradient must be what they are without the poison."""
    layer = _layer()
    params = layer.init(jax.random.PRNGKey(0))
    x = _x(8)

    def loss(p, x):
        out, _, _ = layer.apply(p, x)
        return jnp.sum(out ** 2)

    with jax.default_matmul_precision("highest"):
        clean = jax.grad(loss, argnums=(0, 1))(params, x)

        def past(rows, sizes):
            return (jnp.arange(rows) >= jnp.sum(sizes))[:, None]

        @jax.custom_vjp
        def poisoned(lhs, rhs, sizes):
            return jnp.where(past(lhs.shape[0], sizes), jnp.nan, jax.lax.ragged_dot(lhs, rhs, sizes))

        def fwd(lhs, rhs, sizes):
            return poisoned(lhs, rhs, sizes), (lhs, rhs, sizes)

        def bwd(res, g):
            lhs, rhs, sizes = res
            routed = ~past(lhs.shape[0], sizes)
            # as the kernels do: only the groups' rows are read, the others never written
            _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, sizes), jnp.where(routed, lhs, 0), rhs)
            d_lhs, d_rhs = vjp(jnp.where(routed, g, 0))
            return jnp.where(routed, d_lhs, jnp.nan), d_rhs, None

        poisoned.defvjp(fwd, bwd)
        monkeypatch.setattr(layer, "_grouped", poisoned)
        dirty = jax.grad(loss, argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(clean), jax.tree_util.tree_leaves(dirty)):
        assert bool(jnp.all(jnp.isfinite(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)
