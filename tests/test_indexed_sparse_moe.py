"""``models/indexed_sparse_moe.py`` against the plain reference
(``ftbench/architectures/indexed_sparse_moe_reference.py``, which imports
nothing of the program) at toy widths: logits, the three losses, every
leaf's gradient, the two exact zeros, position streams that differ, what a
tower would hand over, the routing options of ``RoutedExperts`` and the sum
of the experts' shares.  Float32, seeded weights, the CPU; the kernels in
interpret mode where a case says so."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ftbench.architectures import indexed_sparse_moe_reference as ref
from torchft_tpu.models import indexed_sparse_moe
from torchft_tpu.models.indexed_sparse_moe import IndexedSparseMoE, indexed_sparse_debug
from torchft_tpu.parallel.moe import RoutedExperts, RoutedExpertsConfig

from tests._once import once_a_run

SEQ = 64  # four times the toy index's 16 keys


def reference_config(cfg):
    """The keys the reference reads, from the program's config."""
    return dict(
        hidden_size=cfg.dim, num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
        rope_scaling=dict(mrope_section=list(cfg.mrope_section)),
        sa_config=dict(indexer_num_heads=cfg.index_heads, indexer_head_dim=cfg.index_head_dim, topk=cfg.index_topk),
        num_experts_per_tok=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob, experts_held=list(cfg.experts_held),
        assumed=dict(balance_loss_weight=cfg.balance_loss_weight, index_loss_weight=cfg.index_loss_weight),
    )


def _batch(model, seed, rows=2, streams=False, tower=False):
    rng = np.random.default_rng(seed)
    cfg = model.config
    tokens = rng.integers(0, cfg.vocab_size, (rows, SEQ)).astype(np.int32)
    batch = (jnp.asarray(tokens), jnp.asarray(np.roll(tokens, -1, axis=1)))
    if streams or tower:
        # an image of 4 x 6 patches after 10 text tokens: the temporal stream
        # stands still over it, the other two walk its rows and columns
        t = np.arange(SEQ)
        pos = np.stack([t, t, t])
        patch = np.arange(24)
        pos[0, 10:34], pos[1, 10:34], pos[2, 10:34] = 10, 10 + patch // 6, 10 + patch % 6
        pos[:, 34:] = pos[:, 34:] - 24 + 6
        batch += (jnp.asarray(np.broadcast_to(pos[:, None], (3, rows, SEQ)).astype(np.int32)),)
    if tower:
        given = np.zeros((rows, SEQ), bool)
        given[:, 10:34] = True
        batch += (jnp.asarray(rng.normal(size=(rows, SEQ, cfg.dim)).astype(np.float32)), jnp.asarray(given))
    return batch


@pytest.fixture(scope="module")
def model():
    return IndexedSparseMoE(indexed_sparse_debug())


@functools.lru_cache(maxsize=None)
def _params():
    """Made once a run of the tests, by one program (``init`` run operation
    by operation is seconds of small compiles in every process that does)."""
    return once_a_run("indexed_sparse_moe-params", lambda: jax.jit(IndexedSparseMoE(indexed_sparse_debug()).init)(jax.random.PRNGKey(3)))


@pytest.fixture(scope="module")
def params():
    return _params()


KINDS = {"text": {}, "streams": dict(streams=True), "tower": dict(tower=True)}


@functools.lru_cache(maxsize=None)
def reference_side(kind):
    """The reference's forward pass, objective and gradients of a kind of
    batch, which depend on no path: computed once a run (un-jitted, they took
    40 s a case in each of a kind's two cases)."""
    model, params = IndexedSparseMoE(indexed_sparse_debug()), _params()
    batch = _batch(model, 5, **KINDS[kind])
    rc = reference_config(model.config)

    def make():
        # ONE program: two compiled the forward pass twice
        return jax.jit(lambda p: (ref.forward(p, batch[0], batch[1], rc, *batch[2:], logits=True), *jax.value_and_grad(lambda p: ref.loss(p, batch, rc))(p)))(params)

    return once_a_run(f"indexed_sparse_moe-reference-{kind}", make)


@pytest.fixture(params=["plain", "kernels"])
def path(request, monkeypatch):
    monkeypatch.setenv("TORCHFT_FLASH", "1" if request.param == "kernels" else "0")
    return request.param


def _leaves(tree):
    return {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("kind", list(KINDS))
def test_forward_and_every_gradient_are_the_reference(params, path, kind):
    model = IndexedSparseMoE(indexed_sparse_debug())  # this case's own: what it traces depends on the path
    batch = _batch(model, 5, **KINDS[kind])
    want, want_objective, want_grads = reference_side(kind)

    def every(p, b):  # ONE program: three compiled the forward pass three times
        return model.apply(p, b[0], *b[2:]), model.loss(p, b), jax.value_and_grad(model.objective, has_aux=True)(p, b)

    logits, loss, ((objective, (signal, summary)), grads) = jax.jit(every)(params, batch)
    assert model.attention_path == ("dsa" if path == "kernels" else "plain: TORCHFT_FLASH=0")
    np.testing.assert_allclose(logits, want["logits"], atol=2e-4)
    assert float(loss) == pytest.approx(float(jnp.mean(want["nll"])), abs=2e-5)
    assert float(objective) == pytest.approx(float(want_objective), abs=5e-5)
    assert signal == []
    stats = model.summary_stats(np.asarray(summary))
    np.testing.assert_allclose(stats["index_kl"], want["index_kl"], rtol=2e-4)
    np.testing.assert_allclose(stats["keys_per_query"], want["keys_per_query"])
    assert stats["keys_per_query"] == [(16 * 17 / 2 + 48 * 16) / 64] * 2
    first, held = model.config.experts_held
    np.testing.assert_array_equal(stats["rows_here"], np.asarray(want["loads"])[:, first : first + held].sum(axis=1))
    assert stats["buffer_rows"] == [float(batch[0].size * model.config.top_k)] * 2  # toy: the buffer is every pair, one pass
    got, wanted = _leaves(grads), _leaves(want_grads)
    assert got.keys() == wanted.keys()
    for name in got:
        np.testing.assert_allclose(got[name], wanted[name], atol=3e-5, err_msg=name)
        assert float(jnp.max(jnp.abs(wanted[name]))) > 1e-6, name  # every leaf learns


def test_position_streams_that_differ_change_the_result(model, params, monkeypatch):
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    text, streams = _batch(model, 5), _batch(model, 5, streams=True)
    a, b = model.apply(params, text[0]), model.apply(params, streams[0], streams[2])
    np.testing.assert_array_equal(np.asarray(a[:, :10]), np.asarray(b[:, :10]))  # alike before the image
    assert float(jnp.max(jnp.abs(a[:, 10:] - b[:, 10:]))) > 1e-3


def test_the_two_losses_reach_their_own_leaves_and_exactly_no_other(params, path):
    """``L_LM``'s gradient on the index's leaves and ``L_I``'s gradient on
    every other leaf are exactly zero, in the program and in the reference."""
    model = IndexedSparseMoE(indexed_sparse_debug())  # this case's own: what it traces depends on the path
    batch = _batch(model, 7)
    rc = reference_config(model.config)
    sides = {
        "program": (
            jax.jit(jax.grad(model.loss))(params, batch),
            jax.jit(jax.grad(lambda p: jnp.sum(model._losses(p, batch)[1][2])))(params),  # L_I, layer by layer
        ),
        # the reference's two, which depend on no path: one program each, once a run
        "reference": once_a_run(
            "indexed_sparse_moe-reference-two-losses",
            lambda: tuple(jax.jit(jax.grad(lambda p, i=i: ref.losses(p, batch, rc)[i]))(params) for i in (0, 1)),
        ),
    }
    for side, (of_lm, of_index) in sides.items():
        for name, g in _leaves(of_lm).items():
            largest = float(jnp.max(jnp.abs(g)))
            assert (largest == 0.0) if "['index']" in name else (largest > 0.0), (side, name)
        for name, g in _leaves(of_index).items():
            largest = float(jnp.max(jnp.abs(g)))
            assert (largest > 0.0) if "['index']" in name else (largest == 0.0), (side, name)


# -- the routing this model publishes, on RoutedExperts ----------------------


def _experts(held, **over):
    options = dict(score_func="softmax", selection_bias=False, balance_loss_weight=1e-3, dtype=jnp.float32)
    return RoutedExperts(RoutedExpertsConfig(
        dim=32, expert_hidden=16, num_experts=16, experts_held=held, top_k=4, **dict(options, **over)
    ))


@pytest.mark.parametrize("norm_topk_prob", [True, False])
def test_softmax_routing_without_groups_or_bias_is_the_reference(norm_topk_prob):
    layer = _experts((4, 8), norm_topk_prob=norm_topk_prob)
    w = layer.init(jax.random.PRNGKey(1))
    assert "bias" not in w and "bias" not in layer.param_specs() and "shared_gate" not in w
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 32), jnp.float32)
    rc = dict(num_experts_per_tok=4, norm_topk_prob=norm_topk_prob, assumed=dict(balance_loss_weight=1e-3))
    with jax.default_matmul_precision("highest"):
        want_out, want_load, want_balance = ref.moe_layer(x, w, rc, (4, 8))
        weights, chosen, _ = ref.route(x.reshape(-1, 32), w["router"], rc)
    out, load, balance = jax.jit(layer.apply)(w, x)
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    np.testing.assert_array_equal(load, want_load)
    assert float(balance) == pytest.approx(float(want_balance), rel=1e-5)
    picked, gates, scores = layer.route(w, x.reshape(-1, 32))
    np.testing.assert_allclose(scores.sum(axis=-1), 1.0, atol=1e-6)  # a softmax over all 16
    np.testing.assert_array_equal(np.sort(picked, axis=-1), np.argwhere(np.asarray(chosen))[:, 1].reshape(-1, 4))
    np.testing.assert_allclose(np.sort(gates, axis=-1), np.sort(np.asarray(weights), axis=-1)[:, -4:], atol=1e-6)
    # each side's gradient is one program: run operation by operation the two took 15 s of small compiles
    g = jax.jit(jax.grad(lambda w: jnp.sum(layer.apply(w, x)[0] ** 2) + layer.apply(w, x)[2]))(w)
    g_want = jax.jit(jax.grad(lambda w: jnp.sum(ref.moe_layer(x, w, rc, (4, 8))[0] ** 2) + ref.moe_layer(x, w, rc, (4, 8))[2]))(w)
    for name in w:
        np.testing.assert_allclose(g[name], g_want[name], atol=3e-5, err_msg=name)


def test_the_options_reject_what_they_do_not_know():
    with pytest.raises(ValueError, match="score_func"):
        _experts((0, 16), score_func="tanh")


def test_eight_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The guide's share test: what the eight chips that share a layer's 16
    experts each compute adds up to the uncut reference's layer (nothing is
    computed alike on every chip here: no shared expert)."""
    whole = _experts((0, 16))
    w = whole.init(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 32), jnp.float32)
    rc = dict(num_experts_per_tok=4, norm_topk_prob=True, assumed=dict(balance_loss_weight=1e-3))
    with jax.default_matmul_precision("highest"):
        uncut, uncut_load, _ = ref.moe_layer(x, w, rc, (0, 16))
    total, rows = jnp.zeros_like(x), 0.0
    for share in range(8):
        first = 2 * share
        layer = _experts((first, 2))
        mine = dict(w, **{k: w[k][first : first + 2] for k in ("w_gate", "w_up", "w_down")})
        out, load, _ = layer.apply(mine, x)
        np.testing.assert_array_equal(load, uncut_load)  # every chip routes over all 16 alike
        total, rows = total + out, rows + float(load[first : first + 2].sum())
    np.testing.assert_allclose(total, uncut, atol=3e-5)
    assert rows == 48 * 4  # every (token, choice) pair landed on exactly one share


def test_a_bfloat16_model_keeps_a_float32_stream_and_routes_on_it():
    """The residual stream is float32 whatever the matrices' dtype, and the
    router reads its float32 norm: which experts a token takes does not turn
    on bfloat16's rounding of what the router reads (PERF.md section 6, PR
    33: that rounding was the forward pass's distance from the reference)."""
    model = IndexedSparseMoE(indexed_sparse_debug(dtype=jnp.bfloat16))
    w = model.init(jax.random.PRNGKey(3))
    tokens, _ = _batch(model, 5)
    x, _ = model._trunk(w, (tokens, None))
    assert x.dtype == jnp.float32 and model.apply(w, tokens).dtype == jnp.float32
    assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(w["layers"]["attn"]) if leaf.ndim > 2} == {jnp.dtype(jnp.bfloat16)}

    layer = _experts((4, 8), dtype=jnp.bfloat16)
    wl = layer.init(jax.random.PRNGKey(1))
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 4096, 32), jnp.float32)
    out, load, _ = layer.apply(wl, h)
    rounded_out, rounded_load, _ = layer.apply(wl, h.astype(jnp.bfloat16))
    assert out.dtype == jnp.float32 and rounded_out.dtype == jnp.bfloat16  # a part comes back in the dtype given
    picked, _, _ = layer.route(wl, h.reshape(-1, 32))
    np.testing.assert_array_equal(load, np.bincount(np.asarray(picked).ravel(), minlength=16))
    assert not np.array_equal(load, rounded_load)  # among 4,096 tokens some choice turns on the rounding


# ---------------------------------------------------------------------------
# what a rematerialised layer keeps (PR 34)
# ---------------------------------------------------------------------------

# every launch of the path; ``dsa_attn_dkv`` is the whole backward of the attention since PR 68 (it makes
# ``dq`` too), and a name that is not here, ``dsa_attn_dq`` as any other, fails the count with a KeyError
KERNEL_NAMES = ("dsa_index", "dsa_select", "dsa_attn_fwd", "dsa_attn_dkv", "dsa_probs")


def _kernel_calls(monkeypatch):
    """How often each ``pallas_call`` name stands in the jaxpr of ``jax.grad``
    of the model's objective on the kernels' path (``interpret``), the
    sub-jaxprs of ``scan``, ``checkpoint`` and ``custom_vjp`` included.  The
    layers are one ``scan`` each way, so a count is a count a layer body,
    forward and backward bodies together."""
    monkeypatch.setenv("TORCHFT_FLASH", "1")
    model = IndexedSparseMoE(indexed_sparse_debug())
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = _batch(model, 5)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: model.objective(p, batch)[0]))(shapes)
    assert model.attention_path == "dsa"
    counts = dict.fromkeys(KERNEL_NAMES, 0)

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                counts[eqn.params["name"]] += 1
            for value in eqn.params.values():
                for inner in value if isinstance(value, (list, tuple)) else (value,):
                    inner = getattr(inner, "jaxpr", inner)  # a ClosedJaxpr's
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jaxpr.jaxpr)
    return counts


@pytest.fixture(scope="module")
def kernel_calls():
    with pytest.MonkeyPatch.context() as patch:
        return _kernel_calls(patch)


@pytest.mark.parametrize("name", KERNEL_NAMES)
def test_every_kernel_runs_once_a_layer(name, kernel_calls):
    """The backward pass's copy of a layer's forward holds neither
    ``dsa_attn_fwd`` nor ``dsa_probs``: their outputs are among what the
    layer keeps.  (On the parent of PR 34, which kept the selection alone,
    those two count 2.)  Counted in the jaxpr: what is dead there is not
    lowered."""
    assert kernel_calls[name] == 1


def test_a_layer_that_keeps_the_selection_alone_runs_two_kernels_twice(monkeypatch):
    """The count above sees a rematerialised kernel where there is one."""
    monkeypatch.setattr(indexed_sparse_moe, "KEPT_NAMES", ())
    assert _kernel_calls(monkeypatch) == dict.fromkeys(KERNEL_NAMES, 1) | {"dsa_attn_fwd": 2, "dsa_probs": 2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_keeping_the_kernels_outputs_moves_no_bit(dtype, monkeypatch):
    """The objective, the summary and every gradient leaf are, to the bit,
    those of the same model keeping the selection alone: what is kept is
    what would have been computed again."""
    monkeypatch.setenv("TORCHFT_FLASH", "1")
    model = IndexedSparseMoE(indexed_sparse_debug(dtype=dtype))
    w = model.init(jax.random.PRNGKey(3))
    batch = _batch(model, 11)

    def step():
        # a function of its own, so that nothing traced before is found again
        return jax.jit(jax.value_and_grad(lambda p: model.objective(p, batch), has_aux=True))(w)

    (objective, (_, summary)), grads = step()
    monkeypatch.setattr(indexed_sparse_moe, "KEPT_NAMES", ())
    (want_objective, (_, want_summary)), want_grads = step()
    assert model.attention_path == "dsa"
    np.testing.assert_array_equal(objective, want_objective)
    np.testing.assert_array_equal(summary, want_summary)
    got, wanted = _leaves(grads), _leaves(want_grads)
    assert got.keys() == wanted.keys()
    for name in got:
        assert got[name].dtype == wanted[name].dtype
        np.testing.assert_array_equal(got[name], wanted[name], err_msg=name)
        assert float(jnp.max(jnp.abs(got[name].astype(jnp.float32)))) > 0, name
