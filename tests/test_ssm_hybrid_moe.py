"""``models/ssm_hybrid_moe.py`` against the plain float32 reference
(``ftbench/architectures/ssm_hybrid_moe_reference.py``, which imports nothing
of the program) at toy widths on the pattern ``MEMEM*EME``: logits, the two
losses, every leaf's gradient; the pattern string; the squared-ReLU expert
form against a dense loop over experts; the sum of the experts' shares; the
float32 stream; that each dear kernel stands once in a step's program.
Float32, seeded weights, the CPU; the kernels in interpret mode where a case
says so.

Tolerances, with their reasons.  Both sides are float32 with matrix products
at ``highest``; they differ in the ORDER of float32 additions: the chunked
scan against the per-token recurrence, sorted rows against masked experts,
flash's blocks against one softmax a row.  Through 9 layers that read 4e-5 on
logits of up to 4 and 3e-5 of a leaf's largest gradient: limits of 3e-4 on the
logits, 2e-5 on the losses and 1e-3 of a leaf's largest gradient (+1e-6).
bfloat16 anywhere reads 1e-1 on the logits, a choice of experts that differs
above 1e-1, a dropped term (the shared expert, ``D x``, the convolution's bias,
the gate) at least 1e-2: all fail."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ftbench.architectures import ssm_hybrid_moe_reference as ref
from torchft_tpu.models.ssm_hybrid_moe import KERNEL_PATH, SsmHybridMoE, SsmHybridMoEConfig, ssm_hybrid_debug
from torchft_tpu.parallel.moe import RoutedExperts, RoutedExpertsConfig

from tests._once import once_a_run
from tests._toys import gradients_jaxpr, program_side

SEQ = 64  # four chunks of the toy scan


def reference_config(c: SsmHybridMoEConfig) -> dict:
    """The configuration file's keys for a ``SsmHybridMoEConfig``."""
    return dict(
        hybrid_override_pattern=c.pattern, mamba_num_heads=c.ssm_heads, mamba_head_dim=c.ssm_head_dim,
        ssm_state_size=c.ssm_state, n_groups=c.ssm_groups, num_attention_heads=c.n_heads,
        num_key_value_heads=c.n_kv_heads, head_dim=c.head_dim, num_experts_per_tok=c.top_k,
        norm_topk_prob=c.norm_topk_prob, routed_scaling_factor=c.routed_scaling_factor,
        layer_norm_epsilon=c.norm_eps, experts_held=list(c.experts_held),
        assumed=dict(balance_loss_weight=c.balance_loss_weight),
    )


@functools.lru_cache(maxsize=None)
def _params(**over):
    """The toy's parameters, made once a run of the tests (``init`` runs
    operation by operation, 10-20 s of small compiles in every process that
    makes them)."""
    model = SsmHybridMoE(ssm_hybrid_debug(**over))

    def stir(path, p, is_state):
        """What ``init`` leaves at a constant gets values of its own: a bias
        of zero routes nothing, and a gradient is only tested where the
        leaf's value matters."""
        name = getattr(path[-1], "key", "")
        noise = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(3), hash(name) % 997), p.shape)
        if is_state or name == "conv_bias":
            return 0.05 * noise
        return p + 0.1 * noise if name in ("D", "o_norm", "norm") else p

    def make():  # ONE program: ``init`` run operation by operation is 10-20 s of small compiles
        return jax.jit(lambda key: jax.tree_util.tree_map_with_path(stir, model.init(key), model.state_mask()))(jax.random.PRNGKey(0))

    return once_a_run(f"ssm_hybrid_moe-params-{sorted(over.items())}", make)


def _setup(**over):
    """(config, a model of its own, the parameters, a batch): the model is
    the caller's alone, since what it traces depends on ``TORCHFT_FLASH``."""
    cfg = ssm_hybrid_debug(**over)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, SEQ)).astype(np.int32)
    return cfg, SsmHybridMoE(cfg), _params(**over), (jnp.asarray(tokens), jnp.asarray(np.roll(tokens, -1, axis=1)))


@pytest.fixture(scope="module")
def reference_side():
    """The reference's logits, losses and gradients, computed once a run for
    both of the program's paths."""
    cfg, _, params, batch = _setup()
    rc = reference_config(cfg)

    def make():
        # ONE program: two compiled the forward pass twice
        return jax.jit(lambda p: (ref.forward(p, *batch, rc, logits=True), *jax.value_and_grad(lambda p: ref.loss(p, batch, rc))(p)))(params)

    return once_a_run("ssm_hybrid_moe-reference", make)


@pytest.fixture(params=["plain", "kernels"])
def path(request, monkeypatch):
    monkeypatch.setenv("TORCHFT_FLASH", "1" if request.param == "kernels" else "0")
    return request.param


def _leaves(tree):
    return {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_logits_loss_and_every_gradient_agree_with_the_reference(path, reference_side):
    cfg, model, params, batch = _setup()
    want, want_objective, want_grads = reference_side
    logits, loss, ((objective, (signal, summary)), grads) = program_side(model, params, batch, path)
    assert model.attention_path == (KERNEL_PATH if path == "kernels" else "plain: TORCHFT_FLASH=0")
    np.testing.assert_allclose(logits, want["logits"], atol=3e-4)
    assert float(loss) == pytest.approx(float(jnp.mean(want["nll"])), abs=2e-5)
    assert float(objective) == pytest.approx(float(want_objective), abs=2e-5)
    assert float(want["balance"]) > 0 and float(objective) > float(jnp.mean(want["nll"]))
    # the signal is every expert layer's load, in the layers' order
    assert len(signal) == cfg.pattern.count("E") == len(want["loads"])
    for got, load in zip(signal, want["loads"]):
        np.testing.assert_array_equal(got, load[None])  # a stacked run of one layer
    first, held = cfg.experts_held
    stats = model.summary_stats(np.asarray(summary))
    assert stats["rows_here"] == [float(load[first : first + held].sum()) for load in want["loads"]]
    assert stats["buffer_rows"] == [float(batch[0].size * cfg.top_k)] * len(want["loads"])  # toy: the buffer is every pair, one pass
    got, wanted = _leaves(grads), _leaves(want_grads)
    assert got.keys() == wanted.keys()
    for name in got:
        if name.endswith("['bias']"):
            assert float(jnp.max(jnp.abs(got[name]))) == 0.0, name  # no gradient moves a selection bias
            continue
        scale = float(jnp.max(jnp.abs(wanted[name])))
        assert scale > 1e-7, name  # every leaf learns
        np.testing.assert_allclose(got[name], wanted[name], atol=1e-3 * scale + 1e-6, err_msg=name)


def test_layers_are_one_mixer_each_from_the_pattern_string():
    cfg = SsmHybridMoEConfig()
    kinds = cfg.kinds()
    assert len(kinds) == cfg.n_layers == 52
    assert (kinds.count("ssm"), kinds.count("experts"), kinds.count("attention")) == (23, 23, 6)
    # no two neighbours of one kind: every run of the published pattern is one layer
    assert cfg.groups() == [(kind, 1) for kind in kinds]
    model = SsmHybridMoE(ssm_hybrid_debug(pattern="MM*EEE"))
    assert model.groups == [("ssm", 2), ("attention", 1), ("experts", 3)]
    groups = model.init(jax.random.PRNGKey(0))["groups"]
    assert [sorted(w)[:2] for w in groups] == [["A_log", "D"], ["norm", "wk"], ["ffn", "norm"]]
    assert [w["norm"].shape for w in groups] == [(2, 64), (1, 64), (3, 64)]  # a run is one stacked leaf
    assert "w_gate" not in groups[2]["ffn"] and "shared_gate" not in groups[2]["ffn"]
    # stacked runs are the same layers: the second of two M layers is not the first
    assert float(jnp.max(jnp.abs(groups[0]["w_in"][0] - groups[0]["w_in"][1]))) > 0
    with pytest.raises(ValueError, match="a layer is one of"):
        SsmHybridMoE(ssm_hybrid_debug(pattern="ME-"))
    with pytest.raises(ValueError, match="pattern"):
        SsmHybridMoE(ssm_hybrid_debug(pattern=""))


def test_parameter_counts_of_the_published_sizes():
    """ISSUE 35's arithmetic: 986 M on one chip's share of nine layers, 31.58 B whole."""
    here = SsmHybridMoE(SsmHybridMoEConfig(pattern="MEMEM*EME", experts_held=(0, 16), vocab_size=16_384))
    assert here.num_params() == 986_254_848
    by_kind = {
        kind: sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(layer))
        for (kind, _), layer in zip(here.groups, here._shapes["groups"])
    }
    assert by_kind == {"ssm": 38_744_896, "experts": 179_948_288, "attention": 23_399_040}
    assert SsmHybridMoE(SsmHybridMoEConfig()).num_params() == 31_577_940_288


def _experts(held, **over):
    options = dict(
        dim=32, expert_hidden=24, num_experts=16, experts_held=held, top_k=4, routed_scaling_factor=2.5,
        shared_hidden=40, balance_loss_weight=1e-3, expert_form="relu2", dtype=jnp.float32,
    )
    return RoutedExperts(RoutedExpertsConfig(**{**options, **over}))


RC = dict(num_experts_per_tok=4, norm_topk_prob=True, routed_scaling_factor=2.5, assumed=dict(balance_loss_weight=1e-3))


def test_squared_relu_experts_of_two_matrices_are_a_dense_loop_over_experts():
    layer = _experts((4, 8))
    w = layer.init(jax.random.PRNGKey(1))
    assert sorted(w) == ["bias", "router", "shared_down", "shared_up", "w_down", "w_up"]
    assert sorted(layer.param_specs()) == sorted(w)
    w["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(6), (16,))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want_out, want_load, want_balance = jax.jit(lambda w: ref.moe_layer(x, w, RC, (4, 8)))(w)
    out, load, balance = jax.jit(layer.apply)(w, x)
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    np.testing.assert_array_equal(load, want_load)
    assert float(balance) == pytest.approx(float(want_balance), rel=1e-5)
    objective = lambda f: (lambda w: jnp.sum(f(w)[0] ** 2) + f(w)[2])  # noqa: E731
    # each side's gradient is one program: run operation by operation the two took 15 s of small compiles
    g = jax.jit(jax.grad(objective(lambda w: layer.apply(w, x))))(w)
    with jax.default_matmul_precision("highest"):
        g_want = jax.jit(jax.grad(objective(lambda w: ref.moe_layer(x, w, RC, (4, 8)))))(w)
    for name in w:
        scale = float(jnp.max(jnp.abs(g_want[name])))
        np.testing.assert_allclose(g[name], g_want[name], atol=1e-4 * scale + 1e-6, err_msg=name)


def test_the_expert_form_rejects_what_it_does_not_know():
    with pytest.raises(ValueError, match="expert_form"):
        _experts((0, 16), expert_form="geglu")
    layer = _experts((0, 16))
    with pytest.raises(ValueError, match="no gate"):
        layer.apply(layer.init(jax.random.PRNGKey(0)), jnp.zeros((1, 8, 32)), swiglu_limit=7.0)


def test_eight_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The guide's share test: what the eight chips that share a layer's 16
    experts each compute, the shared expert (which every chip computes
    alike) counted once, adds up to the uncut reference's layer."""
    whole = _experts((0, 16))
    w = whole.init(jax.random.PRNGKey(4))
    w["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(7), (16,))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, uncut_load, _ = ref.moe_layer(x, w, RC, (0, 16))
        shared_part = ref.relu2(x @ w["shared_up"]) @ w["shared_down"]
    total, rows = jnp.zeros_like(x), 0.0
    for share in range(8):
        first = 2 * share
        mine = dict(w, **{k: w[k][first : first + 2] for k in ("w_up", "w_down")})
        out, load, _ = _experts((first, 2)).apply(mine, x)
        np.testing.assert_array_equal(load, uncut_load)  # every chip routes over all 16 alike
        total, rows = total + out, rows + float(load[first : first + 2].sum())
    np.testing.assert_allclose(total - 7 * shared_part, uncut, atol=3e-5)
    assert rows == 48 * 4  # every (token, choice) pair landed on exactly one share


def test_a_bfloat16_model_keeps_a_float32_stream_and_routes_on_it(monkeypatch):
    """The residual stream is float32 whatever the matrices' dtype and the
    router reads its float32 norm (PERF.md section 6, PR 33)."""
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    model = SsmHybridMoE(ssm_hybrid_debug(dtype=jnp.bfloat16))
    params = model.init(jax.random.PRNGKey(3))
    assert params["embed"].dtype == jnp.bfloat16 and params["groups"][1]["ffn"]["router"].dtype == jnp.float32
    seen = []
    real = model.moe.apply
    monkeypatch.setattr(model.moe, "apply", lambda w, x, *a: seen.append(x.dtype) or real(w, x, *a))
    tokens = jnp.zeros((1, SEQ), jnp.int32)
    x, _, _ = model._trunk(params, tokens)
    assert x.dtype == jnp.float32 and seen == [jnp.float32] * 4
    assert model.apply(params, tokens).dtype == jnp.float32


@pytest.mark.parametrize(
    "kernel,count", [("ssd_fwd", 8), ("ssd_bwd", 4), ("flash_fwd", 1), ("flash_dq", 1), ("flash_dkv", 1)]
)
def test_what_a_rematerialised_layer_keeps_and_what_it_runs_again(kernel, count):
    """Every layer is rematerialised.  The attention layer keeps what flash
    made (``flash.KEPT_NAMES``): a second ``flash_fwd`` would read 2.  The four
    state-space layers keep nothing of the scan (no room at the published
    widths): ``ssd_fwd`` stands twice a layer."""
    text = _gradients_jaxpr()
    assert text.count(f"name={kernel}\n") + text.count(f"name={kernel} ") == count, kernel


@functools.lru_cache(maxsize=None)
def _gradients_jaxpr():
    """Traced once for the five kernels' counts."""
    _, model, params, batch = _setup()
    return gradients_jaxpr(model, params, batch)
