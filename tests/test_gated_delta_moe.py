"""``models/gated_delta_moe.py`` against the plain float32 reference
(``ftbench/architectures/gated_delta_moe_reference.py``, which imports nothing
of the program and runs the delta rule token by token) at toy widths in the
published pattern, two value heads a key head, a quarter of a head rotated:
logits, every position's loss, the objective, every leaf's gradient, layer
kind by layer kind and for a period together, on the plain path and through
the kernels (interpret mode); the layers from the pattern; the parameter count
of the published sizes; the unbounded decay; the gated norm, partial rope and
both gates; flash at heads of 256; the gated shared expert; the sum of the
experts' shares; the float32 stream; what a rematerialised layer keeps.
Float32, seeded weights, the CPU.

Tolerances, with their reasons.  Both sides are float32 with matrix products
at ``highest``; they differ in the ORDER of float32 additions: the chunked
delta rule against the recurrence (``tests/test_gdn.py``), sorted rows against
masked experts, flash's blocks against one softmax a row.  Through four layers
that read 1e-5 on logits of up to 4 and 1e-4 of a leaf's largest gradient: limits of 3e-4 on
the logits and on a position's loss, 2e-5 on the mean loss and 1e-3 of a leaf's
largest gradient (+1e-6).  bfloat16 anywhere reads 1e-1 on the logits, a choice
of experts that differs above 1e-1, a dropped term (a gate, a norm, rope, the
shared expert's gate, the decay) at least 1e-2: all fail."""

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ftbench.architectures import gated_delta_moe_reference as ref
from torchft_tpu.models.gated_delta_moe import (
    KERNEL_PATH, SUMMARY_FIELDS, GatedDeltaMoE, GatedDeltaMoEConfig, gated_delta_debug,
)
from torchft_tpu.ops import flash_attention as flash
from torchft_tpu.parallel.moe import RoutedExperts, RoutedExpertsConfig

from tests._once import once_a_run
from tests._toys import gradients_jaxpr, program_side

SEQ = 64
# the two kinds of layer, each alone, and a period together
LAYERS = {
    "delta-net": dict(n_layers=1),
    "full-attention": dict(n_layers=1, full_attention_interval=1),
    "a-period": {},
}


def reference_config(c: GatedDeltaMoEConfig) -> dict:
    """The configuration file's keys for a ``GatedDeltaMoEConfig``."""
    return dict(
        num_hidden_layers=c.n_layers, full_attention_interval=c.full_attention_interval, hidden_size=c.dim,
        num_attention_heads=c.n_heads, num_key_value_heads=c.n_kv_heads, head_dim=c.head_dim,
        partial_rotary_factor=c.rotary_dim / c.head_dim, rope_theta=c.rope_theta, rms_norm_eps=c.norm_eps,
        linear_num_key_heads=c.linear_key_heads, linear_num_value_heads=c.linear_value_heads,
        linear_key_head_dim=c.linear_key_head_dim, linear_value_head_dim=c.linear_value_head_dim,
        num_experts_per_tok=c.top_k, norm_topk_prob=c.norm_topk_prob, experts_held=list(c.experts_held),
        assumed=dict(balance_loss_weight=c.balance_loss_weight),
    )


@functools.lru_cache(maxsize=None)
def _params(**over):
    """The toy's parameters, made once a run of the tests."""
    model = GatedDeltaMoE(gated_delta_debug(**over))

    def stir(path, p):
        """What ``init`` leaves at a constant gets values of its own: a
        gradient is only tested where the leaf's value matters."""
        name = getattr(path[-1], "key", "")
        # the same noise in every process: ``hash`` of a string is salted anew in each
        noise = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(3), zlib.crc32(name.encode()) % 997), p.shape)
        return p + 0.1 * noise if name.endswith("_norm") or name == "dt_bias" else p

    def make():  # ONE program: ``init`` run operation by operation is 10-20 s of small compiles
        return jax.jit(lambda key: jax.tree_util.tree_map_with_path(stir, model.init(key)))(jax.random.PRNGKey(0))

    return once_a_run(f"gated_delta_moe-params-{sorted(over.items())}", make)


def _setup(**over):
    """(config, a model of its own, the parameters, a batch): the model is
    the caller's alone, since what it traces depends on ``TORCHFT_FLASH``."""
    cfg = gated_delta_debug(**over)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, SEQ)).astype(np.int32)
    return cfg, GatedDeltaMoE(cfg), _params(**over), (jnp.asarray(tokens), jnp.asarray(np.roll(tokens, -1, axis=1)))


@functools.lru_cache(maxsize=None)
def reference_side(case):
    """The reference's logits, losses and gradients of a case, computed once a
    run for both of the program's paths."""
    cfg, _, params, batch = _setup(**LAYERS[case])
    rc = reference_config(cfg)

    def make():
        # ONE program: two compiled the forward pass twice
        return jax.jit(lambda p: (ref.forward(p, *batch, rc, logits=True), *jax.value_and_grad(lambda p: ref.loss(p, batch, rc))(p)))(params)

    return once_a_run(f"gated_delta_moe-reference-{case}", make)


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
def test_logits_losses_and_every_gradient_agree_with_the_reference(case, path):
    cfg, _, params, batch = _setup(**LAYERS[case])
    want, want_loss, want_grads = reference_side(case)
    model, logits, loss, ((objective, (signal, summary)), grads) = programs_side(case, path)
    assert model.attention_path == (KERNEL_PATH if path == "kernels" else "plain: TORCHFT_FLASH=0")
    np.testing.assert_allclose(logits, want["logits"], atol=3e-4)
    # every position's loss, as the chip's comparison takes it
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch[1][..., None], axis=-1)[..., 0]
    np.testing.assert_allclose(nll, want["nll"], atol=3e-4)
    assert float(loss) == pytest.approx(float(jnp.mean(want["nll"])), abs=2e-5)
    # what a step differentiates: the cross-entropy and the routers' balance loss
    assert float(objective) == pytest.approx(float(want_loss), abs=2e-5)
    assert float(want["balance"]) > 1e-4 and signal == []  # no leaf is the optimizer's to leave alone
    first, held = cfg.experts_held
    stats = model.summary_stats(np.asarray(summary))
    assert list(stats) == list(SUMMARY_FIELDS)
    assert stats["rows_here"] == [float(load[first : first + held].sum()) for load in want["loads"]]
    # the most negative log decay a token had: the reference's, DeltaNet layer by layer; 0 on a full layer
    for kind, got in zip(cfg.kinds(), stats["decay_min"]):
        assert (got == 0.0) == (kind == "full")
    if case == "delta-net":
        layer = jax.tree_util.tree_map(lambda a: a[0], params["groups"][0])
        with jax.default_matmul_precision("highest"):
            h = ref.rms_norm(params["embed"][batch[0]], 1.0 + layer["attn_norm"], cfg.norm_eps)
            g = ref.delta_decay(h, layer["mixer"], reference_config(cfg))
        assert stats["decay_min"][0] == pytest.approx(float(g.min()), rel=1e-5) and float(g.min()) < -5.5
    got, wanted = _leaves(grads), _leaves(want_grads)
    assert got.keys() == wanted.keys()
    for name in got:
        scale = float(jnp.max(jnp.abs(wanted[name])))
        assert scale > 1e-7, name  # every leaf learns
        np.testing.assert_allclose(got[name], wanted[name], atol=1e-3 * scale + 1e-6, err_msg=name)


def test_layer_kinds_come_from_the_pattern_and_runs_are_stacked():
    cfg = GatedDeltaMoEConfig()
    assert len(cfg.kinds()) == 48 and cfg.kinds() == ["gdn", "gdn", "gdn", "full"] * 12
    cut = GatedDeltaMoE(gated_delta_debug(n_layers=8))  # the cell's depth: two whole periods
    assert cut.groups == [("gdn", 3), ("full", 1)] * 2
    groups = cut.init(jax.random.PRNGKey(0))["groups"]
    assert [w["attn_norm"].shape[0] for w in groups] == [3, 1, 3, 1]  # a run is one stacked leaf
    assert sorted(groups[0]["mixer"]) == ["a_log", "conv", "dt_bias", "o_norm", "w_ba", "w_qkvz", "wo"]
    assert sorted(groups[1]["mixer"]) == ["k_norm", "q_norm", "wk", "wo", "wq", "wv"]
    assert all("shared_sigmoid" in w["ffn"] and "bias" not in w["ffn"] for w in groups)  # experts in EVERY layer
    # one convolution over q, k and v together; z, b and a do not pass it
    c = cut.config
    keyed, valued = c.linear_key_heads * c.linear_key_head_dim, c.linear_value_heads * c.linear_value_head_dim
    assert groups[0]["mixer"]["conv"].shape == (3, c.conv_kernel, 2 * keyed + valued)
    assert groups[0]["mixer"]["w_qkvz"].shape == (3, c.dim, 2 * keyed + 2 * valued)
    # stacked runs are the same layers: the second of three is not the first
    assert float(jnp.max(jnp.abs(groups[0]["mixer"]["wo"][0] - groups[0]["mixer"]["wo"][1]))) > 0
    with pytest.raises(ValueError, match="value heads into key heads"):
        GatedDeltaMoE(gated_delta_debug(linear_value_heads=3))


def test_parameter_counts_of_the_published_sizes():
    """ISSUE 56's arithmetic (but for ``A_log``, ``dt_bias`` and the head norm,
    which it counted twice): 1,173.5 M on one chip's share of eight layers."""
    here = GatedDeltaMoE(GatedDeltaMoEConfig(n_layers=8, experts_held=(0, 32), vocab_size=18_992))
    assert here.num_params() == 1_173_540_992
    by_run = [
        sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(layer)) // depth
        for (_, depth), layer in zip(here.groups, here._shapes["groups"])
    ]
    experts = 2048 * 512 + 33 * 3 * 2048 * 512 + 2048  # router, 32 held and the shared one, its gate
    assert experts == 104_859_648
    delta_net = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128 + 4096 * 2048
    full = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    assert (delta_net, full) == (33_718_464, 27_263_488)
    assert by_run == [delta_net + experts + 4096, full + experts + 4096] * 2
    whole = GatedDeltaMoEConfig()
    assert whole.num_experts == 512 and whole.top_k == 10 and whole.rotary_dim == 64 and whole.head_dim == 256


def test_the_seeded_decay_is_unbounded_below():
    """``A_log = log(U(0, 16))`` and ``dt_bias = 1`` at the published widths'
    32 value heads: the log decay of a token reaches well under ``ops/kda.py``'s
    -5.5 already at ``a = 0``, and no bound holds it."""
    model = GatedDeltaMoE(gated_delta_debug(linear_key_heads=16, linear_value_heads=32))
    mixer = jax.jit(lambda key: model._init_mixer("gdn", key))(jax.random.PRNGKey(0))
    assert float(jnp.max(jnp.abs(mixer["dt_bias"] - 1.0))) == 0.0
    A = np.exp(np.asarray(mixer["a_log"]))
    assert 0.0 < A.min() < 2.0 and 14.0 < A.max() < 16.0
    at_zero = -A * np.log1p(np.exp(1.0))
    assert at_zero.min() < -18.0
    assert (-A * np.log1p(np.exp(4.0))).min() < -55.0  # a = 3: nothing bounds it


def test_the_gated_norm_partial_rope_and_both_gates_are_there(monkeypatch):
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    cfg, model, params, batch = _setup()
    rc = reference_config(cfg)
    base = model.apply(params, batch[0])
    delta, full = params["groups"]

    def logits_with(groups):
        changed = dict(params, groups=groups)
        got = model.apply(changed, batch[0])
        np.testing.assert_allclose(got, ref.forward(changed, *batch, rc, logits=True)["logits"], atol=3e-4)
        return float(jnp.max(jnp.abs(got - base)))

    keyed = cfg.linear_key_heads * cfg.linear_key_head_dim
    valued = cfg.linear_value_heads * cfg.linear_value_head_dim
    # the DeltaNet output gate: SiLU(z) on the normed output (the reference's order, which every call
    # here is held to); with z's columns at 0 the mixer gives nothing
    w = delta["mixer"]["w_qkvz"]
    no_z = dict(delta, mixer=dict(delta["mixer"], w_qkvz=w.at[..., 2 * keyed + valued :].set(0.0)))
    assert logits_with([no_z, full]) > 1e-2
    # the attention's gate: sigmoid of the second half of W_q's columns; at 0 it is one half
    q = cfg.n_heads * cfg.head_dim
    wq = full["mixer"]["wq"]
    halved = dict(full, mixer=dict(full["mixer"], wq=wq.at[..., q:].set(0.0), wo=2.0 * full["mixer"]["wo"]))
    assert logits_with([delta, halved]) > 1e-3
    # the head norms weigh with 1 + w: a weight of -1 silences the queries, so attention is a running mean
    silent = dict(full, mixer=dict(full["mixer"], q_norm=jnp.full_like(full["mixer"]["q_norm"], -1.0)))
    assert logits_with([delta, silent]) > 1e-3
    # rope turns the first quarter of a head and passes the rest
    x = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 2, cfg.head_dim))
    turned = ref.rope_first(x, cfg.rotary_dim, cfg.rope_theta)
    assert float(jnp.max(jnp.abs(turned[..., cfg.rotary_dim :] - x[..., cfg.rotary_dim :]))) == 0.0
    assert float(jnp.max(jnp.abs(turned[:, 1:, :, : cfg.rotary_dim] - x[:, 1:, :, : cfg.rotary_dim]))) > 1e-1
    np.testing.assert_allclose(jnp.linalg.norm(turned, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def test_flash_at_heads_of_256_is_the_plain_path():
    """The full layers' heads: 256 for q, k and v, a group of 8 query heads a
    KV head (interpret mode here; ``tests/test_ftbench_compile_*`` compiles
    them for the chip at 16,384 positions).  Outputs and the three gradients
    against a plain softmax: float32's summation order, 1e-5."""
    B, S, H, KV, hd = 1, 128, 8, 1, 256
    q, k, v, w = (
        jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(2), i), shape, jnp.float32)
        for i, shape in enumerate([(B, S, H, hd), (B, S, KV, hd), (B, S, KV, hd), (B, S, H, hd)])
    )

    def plain(q, k, v):
        scores = jnp.einsum("bqhd,bkgd->bhqk", q, k) / np.sqrt(hd)
        scores = jnp.where(jnp.arange(S)[None, :] <= jnp.arange(S)[:, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkgd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    kernels = lambda q, k, v: flash.flash_attention(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)  # noqa: E731
    both = jax.jit(lambda *a: [(f(*a), jax.grad(lambda *b: jnp.sum(f(*b) * w), argnums=(0, 1, 2))(*a)) for f in (plain, kernels)])
    with jax.default_matmul_precision("highest"):
        (want, want_grads), (got, got_grads) = both(q, k, v)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-5)


RC = dict(num_experts_per_tok=4, norm_topk_prob=True, assumed=dict(balance_loss_weight=1e-3))


def _experts(held, gated=True, **over):
    return RoutedExperts(
        RoutedExpertsConfig(
            **dict(
                dict(
                    dim=32, expert_hidden=24, num_experts=16, experts_held=held, top_k=4, score_func="softmax",
                    selection_bias=False, shared_hidden=24, gated_shared=gated, balance_loss_weight=1e-3, dtype=jnp.float32,
                ),
                **over,
            )
        )
    )


def test_the_shared_expert_stands_behind_its_gate():
    """``sigmoid(x . w_s)`` a token times the shared expert, against the
    formula; the held experts' part is what it was without the gate."""
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 32), jnp.float32)
    w = _experts((4, 4)).init(jax.random.PRNGKey(4))
    assert w["shared_sigmoid"].shape == (32,) and w["shared_sigmoid"].dtype == jnp.float32
    assert list(_experts((4, 4)).param_specs()) == [*(k for k in w if k != "shared_sigmoid"), "shared_sigmoid"]
    bare = {k: v for k, v in w.items() if k != "shared_sigmoid"}
    with jax.default_matmul_precision("highest"):
        gated, load, balance = _experts((4, 4)).apply(w, x)
        ungated, load_bare, _ = _experts((4, 4), gated=False).apply(bare, x)
        flat = x.reshape(-1, 32)
        shared = ref.swiglu(flat, w["shared_gate"], w["shared_up"], w["shared_down"])
        gate = jax.nn.sigmoid(flat @ w["shared_sigmoid"])[:, None]
        want, want_load, want_balance = ref.moe_layer(x, w, RC, (4, 4))
    np.testing.assert_array_equal(load, load_bare)
    np.testing.assert_allclose(gated - ungated, ((gate - 1.0) * shared).reshape(x.shape), atol=1e-5)
    assert 0.02 < float(gate.min()) and float(gate.max()) < 0.98 and float(jnp.std(gate)) > 0.05  # a gate a token
    np.testing.assert_allclose(gated, want, atol=3e-5)
    np.testing.assert_array_equal(load, want_load)
    assert float(balance) == pytest.approx(float(want_balance), rel=1e-5)
    with pytest.raises(ValueError, match="gated_shared"):
        _experts((4, 4), shared_hidden=0)


@pytest.mark.parametrize("score_func,bias", [("sigmoid", True), ("softmax", False)])
def test_experts_without_the_flag_give_bit_for_bit_what_they_gave(score_func, bias):
    """No other model's program may change: without ``gated_shared`` the
    leaves, their values from a key and the layer's program are what they
    were (``tests/fixtures/lowered_steps.json`` holds the five models' whole
    steps to that)."""
    plain = _experts((4, 4), gated=False, score_func=score_func, selection_bias=bias)
    gated = _experts((4, 4), score_func=score_func, selection_bias=bias)
    w, w_gated = plain.init(jax.random.PRNGKey(4)), gated.init(jax.random.PRNGKey(4))
    assert "shared_sigmoid" not in w and set(w_gated) == set(w) | {"shared_sigmoid"}
    for name in w:  # the new leaf's key is its own: every other leaf is drawn as before
        np.testing.assert_array_equal(w[name], w_gated[name])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 16, 32), jnp.float32)
    # the gate is ONE sigmoid more (an expert's SiLU is one too), and without the flag there is none more
    text = str(jax.make_jaxpr(lambda w, x: plain.apply(w, x))(w, x))
    assert str(jax.make_jaxpr(lambda w, x: gated.apply(w, x))(w_gated, x)).count("logistic") == text.count("logistic") + 1


def test_sixteen_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The guide's share test: what the sixteen chips that share a layer's
    experts each compute, the gated shared expert (which every chip computes
    alike) counted once, adds up to the uncut reference's whole layer."""
    experts = lambda held: _experts(held, num_experts=32, top_k=5)  # noqa: E731
    rc = dict(RC, num_experts_per_tok=5)
    w = experts((0, 32)).init(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, uncut_load, _ = ref.moe_layer(x, w, rc, (0, 32))
        without, _, _ = ref.moe_layer(x, w, rc, (0, 32), shared=False)
    shared_part = uncut - without
    assert float(jnp.max(jnp.abs(shared_part))) > 1e-2
    total, rows = jnp.zeros_like(x), 0.0
    for share in range(16):
        first = 2 * share
        mine = dict(w, **{k: w[k][first : first + 2] for k in ("w_gate", "w_up", "w_down")})
        out, load, _ = experts((first, 2)).apply(mine, x)
        np.testing.assert_array_equal(load, uncut_load)  # every chip routes over all 32 alike
        total, rows = total + out, rows + float(load[first : first + 2].sum())
    np.testing.assert_allclose(total - 15 * shared_part, uncut, atol=3e-5)
    assert rows == 48 * 5  # every (token, choice) pair landed on exactly one share


def test_a_bfloat16_model_keeps_a_float32_stream_and_routes_on_it(monkeypatch):
    """The residual stream is float32 whatever the matrices' dtype and the
    router and the shared expert's gate read its float32 norm."""
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    model = GatedDeltaMoE(gated_delta_debug(dtype=jnp.bfloat16))
    params = jax.jit(model.init)(jax.random.PRNGKey(3))
    ffn = params["groups"][0]["ffn"]
    assert params["embed"].dtype == jnp.bfloat16 and ffn["w_up"].dtype == jnp.bfloat16
    assert ffn["router"].dtype == ffn["shared_sigmoid"].dtype == params["groups"][0]["mixer"]["a_log"].dtype == jnp.float32
    seen = []
    real = model.moe.apply
    monkeypatch.setattr(model.moe, "apply", lambda w, x, *a: seen.append(x.dtype) or real(w, x, *a))
    tokens = jnp.zeros((1, SEQ), jnp.int32)
    x, _ = jax.jit(model._trunk)(params, tokens)
    assert x.dtype == jnp.float32 and seen == [jnp.float32] * 2  # a stacked run is traced once
    assert jax.jit(model.apply)(params, tokens).dtype == jnp.float32


@pytest.mark.parametrize(
    "kernel,count",
    # two runs (three DeltaNet layers, one full layer): a run's body is traced once
    [("gdn_fwd", 2), ("gdn_bwd", 1), ("flash_fwd", 1), ("flash_dq", 1), ("flash_dkv", 1)],
)
def test_what_a_rematerialised_layer_keeps_and_what_it_runs_again(kernel, count):
    """Every layer is rematerialised.  A FULL layer keeps what flash made
    (``flash.KEPT_NAMES``): a second ``flash_fwd`` would read 2.  A DeltaNet
    layer keeps nothing (the states its backward reads are 537 MB a layer at
    the published widths) and ``gdn_fwd`` stands twice."""
    text = _gradients_jaxpr()
    assert text.count(f"name={kernel}\n") + text.count(f"name={kernel} ") == count, kernel


@functools.lru_cache(maxsize=None)
def _gradients_jaxpr():
    """Traced once for the five kernels' counts."""
    _, model, params, batch = _setup()
    return gradients_jaxpr(model, params, batch)
