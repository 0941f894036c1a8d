"""``models/prerouted_moe.py`` against the plain float32 reference
(``ftbench/architectures/prerouted_moe_reference.py``, which imports nothing of
the program) at toy widths on the published lists' first period with the
window SHORTER than the sequence and SEVEN query heads to a key head: logits,
the loss and every leaf's gradient (the routers' included) for the cell's four
layers together; that the router reads the layer's INPUT and
its gradient enters the stream there; ReGLU; rope on the windowed layers
alone; the window's count; the sum of the two chips' shares; the float32
stream; a group of seven through the flash kernels; what a rematerialised
layer keeps.  Float32, seeded weights, the CPU; the kernels in interpret mode
where a case says so.

Tolerances, with their reasons.  Both sides are float32 with matrix products
at ``highest``; they differ in the ORDER of float32 additions: sorted rows
against masked experts, flash's blocks against one softmax a row, the softmax
over 16 logits taken at 3 against the softmax over the 3.  Through 4 layers
that read 2e-6 on logits of up to 4.5 and 1e-6 of a leaf's largest gradient:
limits of 1e-4 on the logits, 2e-5 on the loss and 1e-3 of a leaf's largest
gradient (+1e-6).  bfloat16 anywhere reads 1e-1 on the logits, a choice of
experts that differs above 1e-1, a dropped term (rope, a norm, a window one
position off, SiLU for the ReLU, a router that reads ``m``) at least 1e-2: all
fail."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ftbench.architectures import prerouted_moe_reference as ref
from torchft_tpu.models.prerouted_moe import KERNEL_PATH, PreroutedMoE, PreroutedMoEConfig, prerouted_moe_debug
from torchft_tpu.ops import flash_attention as flash
from torchft_tpu.parallel.moe import RoutedExperts, RoutedExpertsConfig

from tests._once import once_a_run
from tests._toys import gradients_jaxpr, program_side

SEQ = 64  # the toy window is 24: every row past the 24th sees fewer keys than causal attention gives it
# the cell's four layers together (one global without rope, three windowed with it); a kind alone is
# ``test_rope_is_on_the_layers_the_list_names_alone``'s and ``test_the_window_counts_the_querys_own_position``'s
LAYERS = {"the-cell's-four": {}}


def reference_config(c: PreroutedMoEConfig) -> dict:
    """The configuration file's keys for a ``PreroutedMoEConfig``."""
    return dict(
        sliding_window_layout=list(c.sliding_window_layout), rope_layout=list(c.rope_layout), hidden_size=c.dim,
        num_attention_heads=c.n_heads, num_key_value_heads=c.n_kv_heads, head_dim=c.head_dim,
        sliding_window_size=c.sliding_window, rope_theta=c.rope_theta, rms_norm_eps=c.norm_eps,
        moe_num_active_primary_experts=c.top_k, experts_held=list(c.experts_held),
    )


@functools.lru_cache(maxsize=None)
def _params(**over):
    """The toy's parameters, made once a run of the tests; the norms, which
    ``init`` leaves at 1, get values of their own: a gradient is only tested
    where the leaf's value matters."""
    model = PreroutedMoE(prerouted_moe_debug(**over))

    def stir(path, p):
        names = [getattr(k, "key", "") for k in path]
        if "norms" not in names and names[-1] != "final_norm":
            return p
        return p + 0.1 * jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(3), len(names[-1])), p.shape)

    def make():  # ONE program: ``init`` run operation by operation is 10-20 s of small compiles
        return jax.jit(lambda key: jax.tree_util.tree_map_with_path(stir, model.init(key)))(jax.random.PRNGKey(0))

    return once_a_run(f"prerouted_moe-params-{sorted(over.items())}", make)


def _setup(**over):
    """(config, a model of its own, the parameters, a batch): the model is
    the caller's alone, since what it traces depends on ``TORCHFT_FLASH``."""
    cfg = prerouted_moe_debug(**over)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, SEQ)).astype(np.int32)
    return cfg, PreroutedMoE(cfg), _params(**over), (jnp.asarray(tokens), jnp.asarray(np.roll(tokens, -1, axis=1)))


@functools.lru_cache(maxsize=None)
def reference_side(case):
    """The reference's logits, loss and gradients of a case, computed once a
    run for both of the program's paths."""
    cfg, _, params, batch = _setup(**LAYERS[case])
    rc = reference_config(cfg)

    def make():
        # ONE program: two compiled the forward pass twice
        return jax.jit(lambda p: (ref.forward(p, *batch, rc, logits=True), *jax.value_and_grad(lambda p: ref.loss(p, batch, rc))(p)))(params)

    return once_a_run(f"prerouted_moe-reference-{case}", make)


@functools.lru_cache(maxsize=None)
def programs_side(case, path):
    """(model, logits, loss, ((objective, (signal, summary)), gradients)) of
    a case on ``path``, computed once a process."""
    _, model, params, batch = _setup(**LAYERS[case])
    return (model, *program_side(model, params, batch, path))


def _leaves(tree):
    return {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("path", ["plain", "kernels"])
@pytest.mark.parametrize("case", list(LAYERS))
def test_logits_loss_and_every_gradient_agree_with_the_reference(case, path):
    cfg, _, _, batch = _setup(**LAYERS[case])
    want, want_loss, want_grads = reference_side(case)
    model, logits, loss, ((objective, (signal, summary)), grads) = programs_side(case, path)
    assert model.attention_path == (KERNEL_PATH if path == "kernels" else "plain: TORCHFT_FLASH=0")
    np.testing.assert_allclose(logits, want["logits"], atol=1e-4)
    assert float(loss) == pytest.approx(float(jnp.mean(want["nll"])), abs=2e-5)
    # there is no auxiliary loss: what a step differentiates IS the cross-entropy
    assert float(objective) == pytest.approx(float(want_loss), abs=2e-5)
    # no state the optimizer does not own: no signal; the summary is a row a layer, every layer an expert layer
    assert signal == [] and summary.shape == (cfg.n_layers, 4) == (len(want["loads"]), 4)
    first, held = cfg.experts_held
    stats = model.summary_stats(np.asarray(summary))
    assert stats["rows_here"] == [float(load[first : first + held].sum()) for load in want["loads"]]
    assert stats["load_max"] == [float(load[first : first + held].max()) for load in want["loads"]]
    assert stats["buffer_rows"] == [float(batch[0].size * cfg.top_k)] * cfg.n_layers  # toy: the buffer is every pair, one pass
    got, wanted = _leaves(grads), _leaves(want_grads)
    assert got.keys() == wanted.keys() and sum(name.endswith("['router']") for name in got) == len(model.groups)
    for name in got:
        scale = float(jnp.max(jnp.abs(wanted[name])))
        assert scale > 1e-7, name  # every leaf learns, the routers too
        np.testing.assert_allclose(got[name], wanted[name], atol=1e-3 * scale + 1e-6, err_msg=name)


def test_layer_kinds_come_from_the_two_published_lists_and_runs_are_stacked():
    cfg = PreroutedMoEConfig()
    kinds = cfg.kinds()
    assert len(kinds) == cfg.n_layers == 52
    assert kinds == [(False, False), (True, True), (True, True), (True, True)] * 13  # the NoPE-global layer FIRST
    cut = PreroutedMoE(prerouted_moe_debug())  # the cell's four layers: one global, a run of three windowed
    assert cut.groups == [((False, False), 1), ((True, True), 3)]
    groups = _params()["groups"]
    assert [w["wq"].shape[0] for w in groups] == [1, 3]  # a run is one stacked leaf
    assert all("router" in w["ffn"] and "bias" not in w["ffn"] for w in groups)  # every layer routes, none has a bias
    assert all(sorted(w["ffn"]) == ["router", "w_down", "w_gate", "w_up"] for w in groups)  # no shared expert
    assert all(sorted(w["norms"]) == ["ffn_in", "mixer_in"] for w in groups)  # two norms a layer
    assert sorted(groups[0]) == ["ffn", "norms", "wk", "wo", "wq", "wv"]  # no gate, no head norm
    assert float(jnp.max(jnp.abs(groups[1]["wq"][0] - groups[1]["wq"][1]))) > 0
    # the lists are independent: a windowed layer without rope is a kind of its own
    assert PreroutedMoE(prerouted_moe_debug(sliding_window_layout=(1, 1), rope_layout=(0, 1))).groups == [((True, False), 1), ((True, True), 1)]
    assert not any(jax.tree_util.tree_leaves(cut.state_mask())) and cut.advance_state([], []) == []
    with pytest.raises(ValueError, match="0 or 1"):
        PreroutedMoE(prerouted_moe_debug(rope_layout=(0, 1, 2, 1)))
    with pytest.raises(ValueError, match="an entry a layer"):
        PreroutedMoE(prerouted_moe_debug(rope_layout=(0, 1)))


def test_parameter_counts_of_the_published_sizes():
    """ISSUE 67's arithmetic: 936.8 M on one chip's share of four layers, 21.5 B whole."""
    here = PreroutedMoE(
        PreroutedMoEConfig(sliding_window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1), experts_held=(0, 32), vocab_size=18_992)
    )
    assert here.num_params() == 936_778_240
    by_run = [
        sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(layer)) // depth
        for (_, depth), layer in zip(here.groups, here._shapes["groups"])
    ]
    attention = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert attention == 20_971_520
    layer = attention + 2560 * 64 + 32 * 3 * 2560 * 768 + 2 * 2560  # the router, 32 held experts, two norms
    assert by_run == [layer, layer] and layer == 209_884_160
    assert here.num_params() == 4 * layer + 2 * 18_992 * 2560 + 2560
    assert PreroutedMoE(PreroutedMoEConfig()).num_params() == 21_506_562_560


EXPERTS = dict(dim=32, expert_hidden=24, num_experts=16, top_k=3, score_func="softmax", selection_bias=False, dtype=jnp.float32)
RC = dict(moe_num_active_primary_experts=3)


def _experts(held, **over):
    return RoutedExperts(RoutedExpertsConfig(experts_held=held, **{**EXPERTS, "expert_form": "reglu", **over}))


def test_route_from_is_the_routers_input_and_by_default_the_experts_own():
    """``route_from=None`` is the parent's layer bit for bit (the router reads
    what the experts read); with ``route_from=a`` the choice, the weights AND
    the gradient of the weights are ``a``'s, and the result is the
    reference's, which routing on ``m`` is not."""
    experts = _experts((0, 16))
    w = experts.init(jax.random.PRNGKey(4))
    m = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 32), jnp.float32)
    a = jax.random.normal(jax.random.PRNGKey(6), (1, 48, 32), jnp.float32)

    def both(w, m, a):
        return experts.apply(w, m), experts.apply(w, m, route_from=m), experts.apply(w, m, route_from=a)

    default, same, early = jax.jit(both)(w, m, a)
    for got, want in zip(default[:2], same[:2]):
        np.testing.assert_array_equal(got, want)
    assert float(jnp.max(jnp.abs(early[0] - default[0]))) > 1e-2 and not np.array_equal(early[1], default[1])

    @jax.jit
    def reference(a, m):  # ONE program
        with jax.default_matmul_precision("highest"):
            wanted = lambda a, m: jnp.sum(jnp.sin(ref.moe_layer(a, m, w, RC, (0, 16))[0]))  # noqa: E731
            return ref.moe_layer(a, m, w, RC, (0, 16)), jax.grad(wanted, (0, 1))(a, m)

    (want, want_load), (want_ga, want_gm) = reference(a, m)
    np.testing.assert_allclose(early[0], want, atol=3e-5)
    np.testing.assert_array_equal(early[1], want_load)
    # the weights' gradient reaches a and never m; the experts' reaches m and never a
    early_sum = lambda a, m: jnp.sum(jnp.sin(experts.apply(w, m, route_from=a)[0]))  # noqa: E731
    (ga, gm), held_apart = jax.jit(
        lambda a, m: (jax.grad(early_sum, (0, 1))(a, m), jax.grad(lambda m: early_sum(jax.lax.stop_gradient(a), m))(m))
    )(a, m)
    assert float(jnp.max(jnp.abs(want_ga))) > 1e-3
    np.testing.assert_allclose(ga, want_ga, atol=1e-3 * float(jnp.max(jnp.abs(want_ga))))
    np.testing.assert_allclose(gm, want_gm, atol=1e-3 * float(jnp.max(jnp.abs(want_gm))))
    np.testing.assert_allclose(gm, held_apart, atol=1e-6)


def test_reglu_is_relu_of_the_gate_times_up():
    """An expert of the form "reglu" is ``W_down (relu(m W_gate) * (m
    W_up))``: one expert held, one chosen, its weight 1."""
    experts = _experts((0, 1), num_experts=1, top_k=1)
    w = experts.init(jax.random.PRNGKey(2))
    assert sorted(w) == ["router", "w_down", "w_gate", "w_up"] and experts.expert_leaves == ("w_gate", "w_up", "w_down")
    assert sorted(experts.param_specs()) == sorted(w)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 32), jnp.float32)

    @jax.jit
    def every(w, x):  # ONE program
        with jax.default_matmul_precision("highest"):
            gate, up = x[0] @ w["w_gate"][0], x[0] @ w["w_up"][0]
            plain = [(act(gate) * up) @ w["w_down"][0] for act in (jax.nn.relu, jax.nn.silu)]
        other = _experts((0, 1), num_experts=1, top_k=1, expert_form="swiglu")
        return experts.apply(w, x), other.apply(w, x)[0], plain

    (out, load, _), swiglu, (want, silu) = every(w, x)
    np.testing.assert_allclose(out[0], want, atol=2e-5)
    assert float(jnp.max(jnp.abs(out[0] - silu))) > 1e-2 and float(load[0]) == 40
    np.testing.assert_allclose(swiglu[0], silu, atol=2e-5)  # the same leaves under the other form
    with pytest.raises(ValueError, match="clamp"):
        experts.apply(w, x, swiglu_limit=7.0)
    with pytest.raises(ValueError, match="none of swiglu, reglu and relu2"):
        _experts((0, 1), expert_form="geglu")


def test_two_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The guide's share test: what the TWO chips that share a layer's 16
    experts each compute (experts 0-7 and 8-15) adds up to the uncut
    reference's whole layer; nothing is counted twice, since there is no
    shared expert."""
    w = _experts((0, 16)).init(jax.random.PRNGKey(4))
    m = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 32), jnp.float32)
    a = jax.random.normal(jax.random.PRNGKey(6), (1, 48, 32), jnp.float32)

    @jax.jit
    def every(w, m, a):  # ONE program: the uncut reference and both shares
        with jax.default_matmul_precision("highest"):
            uncut = ref.moe_layer(a, m, w, RC, (0, 16))
        shares = [
            _experts((first, 8)).apply(dict(w, **{k: w[k][first : first + 8] for k in ("w_gate", "w_up", "w_down")}), m, route_from=a)
            for first in (0, 8)
        ]
        return uncut, shares

    (uncut, uncut_load), shares = every(w, m, a)
    total, rows = jnp.zeros_like(m), 0.0
    for first, (out, load, _) in zip((0, 8), shares):
        np.testing.assert_array_equal(load, uncut_load)  # both chips route over all 16 alike
        assert float(jnp.max(jnp.abs(out))) > 1e-2
        total, rows = total + out, rows + float(load[first : first + 8].sum())
    np.testing.assert_allclose(total, uncut, atol=3e-5)
    assert rows == 48 * 3  # every (token, choice) pair landed on exactly one share


def test_the_router_reads_the_layers_input_before_the_attention(monkeypatch):
    """The choice of experts is a function of ``RMSNorm_in(h)`` alone: with
    the attention's output projection at zero or not, a layer's loads are the
    same; routed on ``m`` they would move."""
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    cfg, model, params, batch = _setup(sliding_window_layout=(0,), rope_layout=(0,))
    loads = lambda p: jax.jit(model.objective)(p, batch)[1][1][:, 0]  # noqa: E731 — rows_here a layer
    layer = params["groups"][0]
    louder = dict(params, groups=[dict(layer, wo=8.0 * layer["wo"], wv=-layer["wv"])])
    np.testing.assert_array_equal(loads(params), loads(louder))
    seen = []
    real = model.moe.apply
    monkeypatch.setattr(model.moe, "apply", lambda w, m, route_from: seen.append((m, route_from)) or real(w, m, route_from=route_from))
    x, _ = model._trunk(params, batch[0])
    (m, a), = seen
    assert m is not a and m.dtype == a.dtype == jnp.float32


def test_rope_is_on_the_layers_the_list_names_alone(monkeypatch):
    """A layer whose ``rope_layout`` is 0 has no position encoding: with keys
    and values that do not depend on position, the last row of a sequence and
    of the same sequence with its earlier tokens permuted agree.  A layer with
    rope does not, and the program follows the reference in both."""
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, 512, (1, SEQ)).astype(np.int32)
    shuffled = tokens.copy()
    shuffled[0, : SEQ - 1] = rng.permutation(tokens[0, : SEQ - 1])
    for rope, moved in ((0, False), (1, True)):
        # no window, so that only rope tells the two kinds apart
        cfg, model, params, _ = _setup(sliding_window_layout=(0,), rope_layout=(rope,))
        rc = reference_config(cfg)
        a, b, want = jax.jit(  # ONE program
            lambda p: (model.apply(p, tokens)[0, -1], model.apply(p, shuffled)[0, -1],
                       ref.forward(p, shuffled, shuffled, rc, logits=True)["logits"][0, -1])
        )(params)
        assert (float(jnp.max(jnp.abs(a - b))) > 1e-3) == moved, rope
        np.testing.assert_allclose(b, want, atol=1e-4)


def test_the_window_counts_the_querys_own_position(monkeypatch):
    """Query ``i`` sees keys ``i - window < j <= i``: a change to the token
    ``window`` positions back does not reach it, one to the token ``window -
    1`` back does."""
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    cfg, model, params, _ = _setup(sliding_window_layout=(1,), rope_layout=(1,))
    W, i = cfg.sliding_window, SEQ - 1
    tokens = np.random.default_rng(4).integers(0, 512, (1, SEQ)).astype(np.int32)
    last_row = jax.jit(lambda p, t: model.apply(p, t)[0, i])
    base = last_row(params, tokens)
    for back, reaches in ((W, False), (W - 1, True)):
        other = tokens.copy()
        other[0, i - back] = (other[0, i - back] + 1) % 512
        moved = float(jnp.max(jnp.abs(last_row(params, other) - base)))
        assert (moved > 1e-5) == reaches, (back, moved)


def test_a_bfloat16_model_keeps_a_float32_stream_and_routes_on_it(monkeypatch):
    """The residual stream is float32 whatever the matrices' dtype and the
    router reads the float32 norm (PERF.md section 6, PR 33)."""
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    model = PreroutedMoE(prerouted_moe_debug(dtype=jnp.bfloat16))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(3))
    assert params["embed"].dtype == jnp.bfloat16 and params["groups"][1]["ffn"]["router"].dtype == jnp.float32
    seen = []
    real = model.moe.apply
    monkeypatch.setattr(model.moe, "apply", lambda w, m, route_from: seen.append((m.dtype, route_from.dtype)) or real(w, m, route_from=route_from))
    tokens = jax.ShapeDtypeStruct((1, SEQ), jnp.int32)
    x, _ = jax.eval_shape(model._trunk, params, tokens)
    # a stacked run is traced once: two runs
    assert x.dtype == jnp.float32 and seen == [(jnp.float32, jnp.float32)] * 2
    assert jax.eval_shape(model.apply, params, tokens).dtype == jnp.float32


@functools.lru_cache(maxsize=None)
def _group_of_seven():
    """window -> ((plain's output, its three gradients), (the kernels')), whole
    and under a window of 24: ONE program for both cases."""
    B, S, H, KV, hd = 1, 64, 14, 2, 16
    q, k, v, do = (
        jax.random.normal(jax.random.PRNGKey(i), (B, S, h, hd), jnp.float32) for i, h in enumerate((H, KV, KV, H))
    )

    def plain(window, q, k, v):
        grouped = q.reshape(B, S, KV, H // KV, hd)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", grouped, k, precision="highest") / np.sqrt(hd)
        i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        seen = (j <= i) if window is None else (j <= i) & (j > i - window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v, precision="highest").reshape(B, S, H, hd)

    def kernels(window, q, k, v):
        return flash.flash_attention(q, k, v, causal=True, block_q=16, block_k=16, window=window, interpret=True)

    def every(q, k, v):
        return [
            [
                (f(window, q, k, v), jax.grad(lambda *a: jnp.sum(f(window, *a) * do), (0, 1, 2))(q, k, v))
                for f in (plain, kernels)
            ]
            for window in (None, 24)
        ]

    return dict(zip((None, 24), jax.jit(every)(q, k, v)))


@pytest.mark.parametrize("window", [None, 24])
def test_a_group_of_seven_query_heads_through_the_flash_kernels(window):
    """28 query heads over 4 key heads is a GQA group of SEVEN, which no other
    cell launches: ``dkv``'s walk holds a step for every member.  The kernels
    (interpret mode) against plain attention, whole and under a window, the
    output and all three gradients."""
    (want, want_grads), (got, got_grads) = _group_of_seven()[window]
    np.testing.assert_allclose(got, want, atol=2e-5)
    for g, w, name in zip(got_grads, want_grads, "qkv"):
        np.testing.assert_allclose(g, w, atol=1e-4 * float(jnp.max(jnp.abs(w))) + 1e-6, err_msg=f"d{name}")
    # the tables of the published shape: 3,696 steps a key head on the global layer, far inside SMEM
    launch = flash._key_launch(32, 32, 512, 512, 7, None, True)
    assert launch[0] == (528 * 7,) and 4 * 4 * launch[0][0] < flash._TABLE_BYTES
    walked = flash._key_launch(32, 32, 512, 512, 7, 4096, True)[0][0]
    assert walked == 7 * sum(min(9, 32 - j) for j in range(32))  # a key block is seen by nine row blocks at the most


@pytest.mark.parametrize(
    "kernel,count",
    [
        # one run holds the global layer, one the three windowed: a run's body is traced once
        ("flash_win_fwd", 2), ("flash_win_dq", 1), ("flash_win_dkv", 1),
        ("flash_fwd", 1), ("flash_dq", 1), ("flash_dkv", 1),
    ],
)
def test_what_a_rematerialised_layer_keeps_and_what_it_runs_again(kernel, count):
    """Every layer is rematerialised.  The GLOBAL layer keeps what flash made
    (``flash.KEPT_NAMES``): a second ``flash_fwd`` would read 2.  A WINDOWED
    layer keeps nothing of the kind and ``flash_win_fwd`` stands twice a run."""
    text = _gradients_jaxpr()
    assert text.count(f"name={kernel}\n") + text.count(f"name={kernel} ") == count, kernel


@functools.lru_cache(maxsize=None)
def _gradients_jaxpr():
    """Traced once for the six kernels' counts."""
    _, model, params, batch = _setup()
    return gradients_jaxpr(model, params, batch)
