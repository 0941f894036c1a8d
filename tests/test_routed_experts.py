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
import functools
import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ftbench.architectures import ling_hybrid_reference as ref
from torchft_tpu.parallel import moe
from torchft_tpu.parallel.moe import RoutedExperts, RoutedExpertsConfig, buffer_passes, buffer_size, grouped_tiles

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
    the rows pass four times the uniform load, the buffer is filled as often
    as they need, and the result is still the reference's."""
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


# THE SIZED LAYER: 2,048 tokens, 4 of 32 experts held, 4 a token: a uniform
# router sends 1,024 rows here, the buffer is 1,536 (1.25 times, rounded up to
# 512) and every pair, 8,192, needs six passes through it
SIZE = 1536


def _sized_layer():
    return _layer(0, 4, num_experts=32, n_group=4, topk_group=1)


def _sized_case(layer, rows, seed=5):
    """Params and x [2, 1024, 64] whose router sends exactly ``rows`` pairs
    to the four held experts: feature 0 of x is +1 on ``rows / 4`` tokens
    and -1 on the others, and it alone (16 on the held experts' columns)
    outweighs the rest of the router, so a token takes all four held experts
    or none."""
    params = layer.init(jax.random.PRNGKey(0))
    params["router"] = params["router"].at[0].set(0.0).at[0, :4].set(16.0)
    x = _x(seed, B=2, S=1024)
    sign = jnp.where(jax.random.permutation(jax.random.PRNGKey(seed), 2048) < rows // 4, 1.0, -1.0)
    return params, x.at[:, :, 0].set(sign.reshape(2, 1024))


def _passes_run(monkeypatch, layer):
    """Where in ``order`` every pass the layer really RUNS from here on
    began (a ``jax.debug.callback`` inside the loop's body)."""
    began, through = [], layer._through

    def recording(size, limit, at, *rest):
        jax.debug.callback(lambda at: began.append(int(at)), at)
        return through(size, limit, at, *rest)

    monkeypatch.setattr(layer, "_through", recording)
    return began


@pytest.mark.parametrize(
    "tokens, top_k, held, experts, want",
    [
        (2048, 4, 4, 32, SIZE),
        (16384, 8, 16, 128, 20480),  # Trinity's and Keye's cells: a uniform router sends 16,384
        (16384, 6, 16, 128, 15360),  # Nemotron's: 12,288
        (8192, 8, 8, 256, 2560),  # Ling's: 2,048
        (1024, 4, 4, 32, 1024),  # 640 rounds up to 512's next multiple
        (128, 4, 4, 16, 512),  # toy: every pair, one pass whatever arrives
        (16, 2, 16, 16, 32),  # every expert held: never over tokens * top_k
    ],
)
def test_the_buffer_is_sized_from_the_uniform_load_and_never_over_every_pair(tokens, top_k, held, experts, want):
    size = buffer_size(tokens, top_k, held, experts)
    full, uniform = tokens * top_k, tokens * top_k * held / experts
    assert size == want <= full and (size % 512 == 0 or size == full)
    assert size == full or 1.25 * uniform <= size < 1.25 * uniform + 512
    # the passes: one up to the buffer's edge, even for no rows, one more a row over it, and every pair fits
    assert [int(buffer_passes(size, rows)) for rows in (0, 1, size, size + 1, 2 * size, 2 * size + 1)] == [1, 1, 1, 2, 2, 3]
    assert int(buffer_passes(size, full)) == -(-full // size)
    np.testing.assert_array_equal(buffer_passes(size, jnp.asarray([0.0, size, size + 1.0])), [1, 1, 2])


def _sized_loss(f, p, x):
    out, load, balance = f(p, x)
    return jnp.sum(out ** 2) + balance, (out, load)


@functools.lru_cache(maxsize=None)
def _sized_reference():
    """The reference's side of the sized layer's cases as ONE program, traced
    by the first case (inside its matmul precision) and found by the others:
    run operation by operation it was 10 s and more of each case."""
    cfg = dict(REF_CFG, topk_group=1)
    return jax.jit(jax.value_and_grad(partial(_sized_loss, lambda p, x: ref.moe_layer(x, p, cfg, (0, 4))), argnums=(0, 1), has_aux=True))


@pytest.mark.parametrize(
    "rows, passes",
    [
        (0, 1), (1024, 1), (1536, 1), (1540, 2), (3072, 2), (3076, 3), (4608, 3),
        (8192, 6),  # every token on the held experts, as in the test above
    ],
)
def test_every_number_of_passes_gives_the_references_output_and_gradients(monkeypatch, rows, passes):
    """No load (one pass all the same), a load just under and just over the
    edge of one pass, of two and of three, and every pair: the output and the gradients of x,
    the router and every expert matrix are the reference's, the forward
    pass and the backward pass walk the SAME passes, the fewest that hold
    the rows, and the counter says how many buffer rows that was."""
    layer = _sized_layer()
    assert buffer_size(2048, 4, 4, 32) == SIZE
    params, x = _sized_case(layer, rows)
    began = _passes_run(monkeypatch, layer)
    loss = _sized_loss

    with jax.default_matmul_precision("highest"):
        (_, (out, load)), got = jax.jit(jax.value_and_grad(partial(loss, layer.apply), argnums=(0, 1), has_aux=True))(params, x)
        (_, (want_out, _)), want = _sized_reference()(params, x)
        jax.effects_barrier()
    assert float(load[:4].sum()) == rows
    # forward, and in the backward pass each of them forward again and back
    assert sorted(began) == sorted(2 * [i * SIZE for i in range(passes)])
    assert float(layer.buffer_rows(2048, load[:4].sum())) == passes * SIZE
    assert float(jnp.max(jnp.abs(out - want_out))) < TOL
    for name in ("router", *layer.expert_leaves, *layer.shared_leaves):
        scale = float(jnp.max(jnp.abs(want[0][name]))) + 1e-6
        assert float(jnp.max(jnp.abs(got[0][name] - want[0][name]))) < 2e-5 * scale + 1e-6, name
    assert float(jnp.max(jnp.abs(got[0]["bias"]))) == 0.0
    scale = float(jnp.max(jnp.abs(want[1])))
    assert float(jnp.max(jnp.abs(got[1] - want[1]))) < 2e-5 * scale + 1e-6


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
        # one program a side: run operation by operation the two were 15 s of small compiles
        got = jax.jit(jax.grad(lambda p: loss(p, lambda p: layer.apply(p, x))))(params)
        want = jax.jit(jax.grad(lambda p: loss(p, lambda p: ref.moe_layer(x, p, REF_CFG, (4, HELD)))))(params)
    assert float(jnp.max(jnp.abs(got["bias"]))) == 0.0
    for name in params:
        scale = float(jnp.max(jnp.abs(want[name]))) + 1e-6
        assert float(jnp.max(jnp.abs(got[name] - want[name]))) < 2e-5 * scale + 1e-6, name


@pytest.mark.parametrize("rows", [None, 1136, 1936], ids=["every_pair", "one_pass", "two_passes"])
def test_rows_a_grouped_kernel_leaves_unwritten_reach_nothing(monkeypatch, rows):
    """``megablox.gmm`` writes only the rows its groups cover, in the
    backward pass too; past them lies whatever the buffer held (on the chip
    a NaN after six steps: PERF.md section 6, PR 29).  Here the grouped
    product is made to leave NaN there, both ways: the result and every
    gradient must be what they are without the poison, in the toy layer
    whose buffer is every pair and in the sized layer with 400 rows of its
    only pass unwritten and with 1,136 of its second."""
    if rows is None:
        layer = _layer()
        params, x = layer.init(jax.random.PRNGKey(0)), _x(8)
    else:
        layer = _sized_layer()
        params, x = _sized_case(layer, rows, seed=8)
    began = _passes_run(monkeypatch, layer)

    def loss(p, x):
        out, _, _ = layer.apply(p, x)
        return jnp.sum(out ** 2)

    with jax.default_matmul_precision("highest"):
        clean = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, x)  # one program, not one an operation

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
        dirty = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, x)  # traced anew: another ``_grouped``
        jax.effects_barrier()
    assert set(began) == ({0} if rows in (None, 1136) else {0, SIZE})
    for a, b in zip(jax.tree_util.tree_leaves(clean), jax.tree_util.tree_leaves(dirty)):
        assert bool(jnp.all(jnp.isfinite(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# the grouped products' tiles (PR 51)
# ---------------------------------------------------------------------------

# the five expert cells' configurations, by file under ftbench/configs/
EXPERT_CONFIGS = (
    "ling-3.0-flash-ep32-1x1", "keye-vl-2.0-30b-a3b-ep8-1x1", "nemotron-3-nano-30b-a3b-ep8-1x1",
    "trinity-mini-ep8-1x1", "joyai-llm-flash-ep16-1x1",
)
# a layer's calls: the kind ``grouped_tiles`` is asked for, and whether k and n
# are (dim, hidden) as for ``w_up`` / ``w_gate`` or (hidden, dim) as for ``w_down``
CALLS = {
    "fwd.in": ("gmm", False), "fwd.out": ("gmm", True), "bwd.in": ("gmm_t", True), "bwd.out": ("gmm_t", False),
    "tgmm.in": ("tgmm", False), "tgmm.out": ("tgmm", True),
}


def _cell_shapes(config_name):
    """``(m, dim, hidden, held)`` of the cell that runs ``config_name``: the
    buffer ``_held_part`` makes for its traffic's tokens, and its widths."""
    from tests._ftbench_view import BENCH_DIR, bench

    def read(folder, name):
        with open(os.path.join(BENCH_DIR, folder, name + ".json")) as f:
            return json.load(f)

    (cell,) = [w for w in bench()["workloads"] if w["config"] == config_name]
    cfg, traffic = read("configs", config_name), read("traffic", cell["traffic"])
    tokens = traffic["seq_len"] * traffic["sequences_per_chip"]
    held = cfg["experts_held"][1]
    m = buffer_size(tokens, cfg["num_experts_per_tok"], held, cfg["router_experts"])
    return m, cfg["hidden_size"], cfg["moe_intermediate_size"], held


def _call_tiles(config_name, call):
    m, dim, hidden, held = _cell_shapes(config_name)
    kind, swapped = CALLS[call]
    k, n = (hidden, dim) if swapped else (dim, hidden)
    return kind, m, k, n, grouped_tiles(kind, m, k, n, held)


@pytest.mark.parametrize("call", CALLS)
@pytest.mark.parametrize("config_name", EXPERT_CONFIGS)
def test_tiles_follow_the_cells_shapes(config_name, call):
    """Every call of every expert cell gets tiles the kernels take: the row
    tile divides the buffer, a tile is a multiple of the (8, 128) layout or
    the whole dimension, the reckoned scoped memory is under the limit, and
    none is PR 29's constant (0.4-0.5 us a grid step for 0.085 us of
    product: ISSUE 51)."""
    kind, m, k, n, (tm, tk, tn) = _call_tiles(config_name, call)
    assert m % tm == 0 and tm % 8 == 0
    assert (tk % 128 == 0 or tk == k) and (tn % 128 == 0 or tn == n)
    assert tk <= k and tn <= n
    assert moe.grouped_vmem(kind, tm, tk, tn) <= moe.SCOPED_VMEM
    assert (tm, tk, tn) != (128, 256, 256)
    assert 2 * tm * tk * tn / 197e12 > 1e-6  # a grid step holds over a microsecond of the chip's product


@pytest.mark.parametrize("call", CALLS)
def test_an_expert_of_a_hundred_rows_gets_the_smallest_row_tile(call):
    """Ling's buffer holds 128 rows an expert under a uniform router (the
    ledger reads 108): a row tile is visited once for each expert with rows
    in it, so anything over 128 rows is waste there."""
    m, _, _, held = _cell_shapes("ling-3.0-flash-ep32-1x1")
    assert 4 * m // (5 * held) == 128
    assert _call_tiles("ling-3.0-flash-ep32-1x1", call)[-1][0] == 128


# toy shapes that hold what the cells hold: a width that is no multiple of 128
# (Nemotron's 1,856), an expert with no rows, a 128-row tile that spans three
# experts (rows 0-40, 40-70, 70-120), rows past sum(sizes) in the buffer
TOY_SIZES = (40, 0, 30, 50, 200)
TOY_M = 512


@pytest.mark.parametrize("vmem_kb", [None, 400], ids=["whole_widths", "several_k_tiles"])
@pytest.mark.parametrize("k, n", [(384, 200), (200, 384)], ids=["in", "out"])
def test_grouped_product_agrees_with_ragged_dot_both_ways(monkeypatch, k, n, vmem_kb):
    """``megablox``'s kernels in interpret mode at the tiles ``grouped_tiles``
    gives, the value and both gradients against ``lax.ragged_dot``'s; with
    the scoped memory squeezed the same shapes go through several k tiles
    (the float32 accumulator's round trips; at k 200 ``megablox``'s mask of
    the k remainder)."""
    if vmem_kb:
        monkeypatch.setattr(moe, "SCOPED_VMEM", vmem_kb * 1024)
    held = len(TOY_SIZES)
    tiles = {kind: grouped_tiles(kind, TOY_M, *kn, held) for kind, kn in (("gmm", (k, n)), ("gmm_t", (n, k)), ("tgmm", (k, n)))}
    assert all(moe.grouped_vmem(kind, *t) <= moe.SCOPED_VMEM for kind, t in tiles.items())
    assert (tiles["gmm"][1] < k) == bool(vmem_kb)  # k in several tiles only where it has to be
    ka, kb, kg = jax.random.split(jax.random.PRNGKey(3), 3)
    lhs = jax.random.normal(ka, (TOY_M, k), jnp.float32)
    rhs = jax.random.normal(kb, (held, k, n), jnp.float32) / np.sqrt(k)
    weight = jax.random.normal(kg, (TOY_M, n), jnp.float32)
    sizes = jnp.asarray(TOY_SIZES, jnp.int32)
    routed = (jnp.arange(TOY_M) < sum(TOY_SIZES))[:, None]

    def loss(product):
        # rows past the routed ones are undefined on both paths: masked, as ``_through`` masks them
        return lambda a, b: jnp.sum(jnp.where(routed, product(a, b), 0.0) * weight)

    with jax.default_matmul_precision("highest"):
        got = moe.grouped_product(lhs, rhs, sizes, True)
        want = jax.lax.ragged_dot(lhs, rhs, sizes)
        d_got = jax.grad(loss(lambda a, b: moe.grouped_product(a, b, sizes, True)), argnums=(0, 1))(lhs, rhs)
        d_want = jax.grad(loss(lambda a, b: jax.lax.ragged_dot(a, b, sizes)), argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(np.where(routed, got, 0), np.where(routed, want, 0), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.where(routed, d_got[0], 0), np.where(routed, d_want[0], 0), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(d_got[1], d_want[1], rtol=1e-5, atol=1e-4)
    assert float(jnp.max(jnp.abs(d_want[1][1]))) == 0.0  # the expert with no rows
