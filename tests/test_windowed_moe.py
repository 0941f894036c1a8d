"""``models/windowed_moe.py`` against the plain float32 reference
(``ftbench/architectures/windowed_moe_reference.py``, which imports nothing of
the program) at toy widths on the published list's first eight layers with
the window SHORTER than the sequence: logits, the loss, every leaf's gradient,
layer kind by layer kind and for the eight together; rope on the windowed
layers alone; the gate; the embedding's scale; the four norms; the bias
update; the sum of the experts' shares; the float32 stream; that the windowed
layers take the kernels that walk, and what a rematerialised layer keeps.
Float32, seeded weights, the CPU; the kernels in interpret mode where a case
says so.

Tolerances, with their reasons.  Both sides are float32 with matrix products
at ``highest``; they differ in the ORDER of float32 additions: sorted rows
against masked experts, flash's blocks against one softmax a row.  Through 8
layers of four norms that read 3e-5 on logits of up to 4 and 2e-5 of a leaf's
largest gradient: limits of 3e-4 on the logits, 2e-5 on the loss and 1e-3 of a
leaf's largest gradient (+1e-6).  bfloat16 anywhere reads 1e-1 on the logits,
a choice of experts that differs above 1e-1, a dropped term (the gate, a
norm, rope, the shared expert, a window one position off) at least 1e-2: all
fail."""

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ftbench.architectures import windowed_moe_reference as ref
from torchft_tpu.models.windowed_moe import KERNEL_PATH, WindowedMoE, WindowedMoEConfig, windowed_moe_debug
from torchft_tpu.parallel.moe import RoutedExperts, RoutedExpertsConfig

from tests._once import once_a_run
from tests._toys import gradients_jaxpr, program_side

SEQ = 64  # the toy window is 24: every row past the 24th sees fewer keys than causal attention gives it
S, F = "sliding_attention", "full_attention"
# the three kinds of layer the cell has, each alone, and the eight together
LAYERS = {
    "windowed-dense": dict(layer_types=(S,), num_dense_layers=1),
    "windowed-experts": dict(layer_types=(S,), num_dense_layers=0),
    "full-experts": dict(layer_types=(F,), num_dense_layers=0),
    "the-cell's-eight": {},
}


def reference_config(c: WindowedMoEConfig) -> dict:
    """The configuration file's keys for a ``WindowedMoEConfig``."""
    return dict(
        layer_types=list(c.layer_types), num_dense_layers=c.num_dense_layers, hidden_size=c.dim,
        num_attention_heads=c.n_heads, num_key_value_heads=c.n_kv_heads, head_dim=c.head_dim,
        sliding_window=c.sliding_window, rope_theta=c.rope_theta, rms_norm_eps=c.norm_eps,
        num_experts_per_tok=c.top_k, route_norm=c.route_norm, route_scale=c.route_scale,
        mup_enabled=c.embed_scale, experts_held=list(c.experts_held),
    )


@functools.lru_cache(maxsize=None)
def _params(**over):
    """The toy's parameters, made once a run of the tests (``init`` runs
    operation by operation, 10-20 s of small compiles in every process that
    makes them)."""
    model = WindowedMoE(windowed_moe_debug(**over))

    def stir(path, p, is_state):
        """What ``init`` leaves at a constant gets values of its own: a bias
        of zero routes nothing, and a gradient is only tested where the
        leaf's value matters."""
        names = [getattr(k, "key", "") for k in path]
        # the same noise in every process: ``hash`` of a string is salted anew in each
        noise = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(3), zlib.crc32(names[-1].encode()) % 997), p.shape)
        if is_state:
            return 0.05 * noise
        return p + 0.1 * noise if "norms" in names or names[-1].endswith("_norm") else p

    def make():  # ONE program: ``init`` run operation by operation is 10-20 s of small compiles
        return jax.jit(lambda key: jax.tree_util.tree_map_with_path(stir, model.init(key), model.state_mask()))(jax.random.PRNGKey(0))

    return once_a_run(f"windowed_moe-params-{sorted(over.items())}", make)


def _setup(**over):
    """(config, a model of its own, the parameters, a batch): the model is
    the caller's alone, since what it traces depends on ``TORCHFT_FLASH``."""
    cfg = windowed_moe_debug(**over)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, SEQ)).astype(np.int32)
    return cfg, WindowedMoE(cfg), _params(**over), (jnp.asarray(tokens), jnp.asarray(np.roll(tokens, -1, axis=1)))


@functools.lru_cache(maxsize=None)
def reference_side(case):
    """The reference's logits, loss and gradients of a case, computed once a
    run for both of the program's paths."""
    cfg, _, params, batch = _setup(**LAYERS[case])
    rc = reference_config(cfg)

    def make():
        # ONE program: two compiled the forward pass twice
        return jax.jit(lambda p: (ref.forward(p, *batch, rc, logits=True), *jax.value_and_grad(lambda p: ref.loss(p, batch, rc))(p)))(params)

    return once_a_run(f"windowed_moe-reference-{case}", make)


@functools.lru_cache(maxsize=None)
def programs_side(case, path):
    """(model, logits, loss, ((objective, (signal, summary)), gradients)) of
    a case on ``path``, computed once a process."""
    _, model, params, batch = _setup(**LAYERS[case])
    return (model, *program_side(model, params, batch, path))


@pytest.fixture(params=["plain", "kernels"])
def path(request, monkeypatch):
    monkeypatch.setenv("TORCHFT_FLASH", "1" if request.param == "kernels" else "0")
    return request.param


def _leaves(tree):
    return {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("case", list(LAYERS))
def test_logits_loss_and_every_gradient_agree_with_the_reference(case, path):
    cfg, _, _, batch = _setup(**LAYERS[case])
    want, want_loss, want_grads = reference_side(case)
    model, logits, loss, ((objective, (signal, summary)), grads) = programs_side(case, path)
    assert model.attention_path == (KERNEL_PATH if path == "kernels" else "plain: TORCHFT_FLASH=0")
    np.testing.assert_allclose(logits, want["logits"], atol=3e-4)
    assert float(loss) == pytest.approx(float(jnp.mean(want["nll"])), abs=2e-5)
    # there is no auxiliary loss: what a step differentiates IS the cross-entropy
    assert float(objective) == pytest.approx(float(want_loss), abs=2e-5)
    # the signal is every expert layer's load, in the layers' order, a stacked run a leaf
    loads = np.concatenate([np.asarray(s) for s in signal]) if signal else np.zeros((0, cfg.num_experts))
    assert len(loads) == cfg.n_layers - cfg.num_dense_layers == len(want["loads"])
    np.testing.assert_array_equal(loads, np.stack(want["loads"]) if want["loads"] else loads)
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


def test_layer_kinds_come_from_the_published_list_and_runs_are_stacked():
    cfg = WindowedMoEConfig()
    kinds = cfg.kinds()
    assert len(kinds) == cfg.n_layers == 32
    assert [a for a, _ in kinds] == [S, S, S, F] * 8
    assert [f for _, f in kinds] == ["dense"] * 2 + ["moe"] * 30
    cut = windowed_moe_debug()  # the cell's eight layers: S-dense, S S, F, S S S, F
    assert WindowedMoE(cut).groups == [((S, "dense"), 1), ((S, "moe"), 2), ((F, "moe"), 1), ((S, "moe"), 3), ((F, "moe"), 1)]
    groups = WindowedMoE(cut).init(jax.random.PRNGKey(0))["groups"]
    assert [w["wq"].shape[0] for w in groups] == [1, 2, 1, 3, 1]  # a run is one stacked leaf
    assert "router" not in groups[0]["ffn"] and "router" in groups[1]["ffn"]
    assert sorted(groups[0]["norms"]) == ["ffn_in", "ffn_out", "mixer_in", "mixer_out"]  # four norms a layer
    # stacked runs are the same layers: the second of two layers is not the first
    assert float(jnp.max(jnp.abs(groups[1]["wq"][0] - groups[1]["wq"][1]))) > 0
    with pytest.raises(ValueError, match="a layer is one of"):
        WindowedMoE(windowed_moe_debug(layer_types=(S, "chunked_attention")))
    with pytest.raises(ValueError, match="layer_types"):
        WindowedMoE(windowed_moe_debug(layer_types=()))


def test_parameter_counts_of_the_published_sizes():
    """ISSUE 41's arithmetic: 1,108.9 M on one chip's share of eight layers."""
    here = WindowedMoE(
        WindowedMoEConfig(
            layer_types=WindowedMoEConfig.layer_types[:8], num_dense_layers=1, experts_held=(0, 16), vocab_size=25_024
        )
    )
    assert here.num_params() == 1_108_939_648
    by_run = [
        sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(layer)) // depth
        for (_, depth), layer in zip(here.groups, here._shapes["groups"])
    ]
    attention = 2 * 2048 * 4096 + 2048 * 4096 + 2 * 2048 * 512 + 4 * 2048 + 2 * 128  # q, g, o; k, v; the norms
    assert attention == 27_271_424
    assert by_run[0] == attention + 3 * 2048 * 6144  # the dense layer
    expert_layer = attention + 17 * 3 * 2048 * 1024 + 2048 * 128 + 128  # 16 held and the shared one, router, bias
    assert by_run[1:] == [expert_layer] * 4 and expert_layer == 134_488_448


def test_rope_is_on_the_windowed_layers_alone(monkeypatch):
    """A FULL layer has no position encoding: with keys and values that do
    not depend on position, the last row of a sequence and of the same
    sequence with its earlier tokens permuted agree.  A WINDOWED layer's do
    not (rope), and the program follows the reference in both."""
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, 512, (1, SEQ)).astype(np.int32)
    shuffled = tokens.copy()
    shuffled[0, : SEQ - 1] = rng.permutation(tokens[0, : SEQ - 1])
    for kind, moved in ((F, False), (S, True)):
        # the window covers the sequence, so that only rope tells the two kinds apart
        cfg, model, params, _ = _setup(layer_types=(kind,), num_dense_layers=1, sliding_window=SEQ)
        a = model.apply(params, jnp.asarray(tokens))[0, -1]
        b = model.apply(params, jnp.asarray(shuffled))[0, -1]
        assert (float(jnp.max(jnp.abs(a - b))) > 1e-3) == moved, kind
        want = ref.forward(params, shuffled, shuffled, reference_config(cfg), logits=True)["logits"][0, -1]
        np.testing.assert_allclose(b, want, atol=3e-4)


def test_the_window_counts_the_querys_own_position(monkeypatch):
    """Query ``i`` sees keys ``i - window < j <= i``: a change to the token
    ``window`` positions back does not reach it, one to the token ``window -
    1`` back does."""
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    cfg, model, params, _ = _setup(layer_types=(S,), num_dense_layers=1)
    W, i = cfg.sliding_window, SEQ - 1
    tokens = np.random.default_rng(4).integers(0, 512, (1, SEQ)).astype(np.int32)
    base = model.apply(params, jnp.asarray(tokens))[0, i]
    for back, reaches in ((W, False), (W - 1, True)):
        other = tokens.copy()
        other[0, i - back] = (other[0, i - back] + 1) % 512
        moved = float(jnp.max(jnp.abs(model.apply(params, jnp.asarray(other))[0, i] - base)))
        assert (moved > 1e-5) == reaches, (back, moved)


def test_the_gate_the_embeddings_scale_and_the_branch_norms_are_there(monkeypatch):
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    # two dense layers: no router downstream turns a rounding into another choice of experts
    cfg, model, params, batch = _setup(layer_types=(S, F), num_dense_layers=2)
    base = model.apply(params, batch[0])
    layer = params["groups"][0]
    # the gate: sigmoid(a Wg) multiplies the attention's output; with Wg at 0 it is one half
    halved = dict(layer, wg=jnp.zeros_like(layer["wg"]), wo=2.0 * layer["wo"])
    ungated = model.apply(dict(params, groups=[halved, params["groups"][1]]), batch[0])
    assert float(jnp.max(jnp.abs(ungated - base))) > 1e-2
    want = ref.forward(dict(params, groups=[halved, params["groups"][1]]), *batch, reference_config(cfg), logits=True)
    np.testing.assert_allclose(ungated, want["logits"], atol=3e-4)
    # the embedding's scale: sqrt(dim) on the stream, so rows sqrt(dim) times larger without it are the same model
    plain = WindowedMoE(windowed_moe_debug(layer_types=(S, F), num_dense_layers=2, embed_scale=False))
    scaled_rows = dict(params, embed=params["embed"] * np.float32(np.sqrt(cfg.dim)))
    np.testing.assert_allclose(plain.apply(scaled_rows, batch[0]), base, atol=1e-5)
    assert float(jnp.max(jnp.abs(plain.apply(params, batch[0]) - base))) > 1e-2
    # the norm on a branch: a mixer output twice as large adds the same to the stream (but for the
    # norm's epsilon beside a toy branch's mean square; without the norm the logits move by over 1e-1)
    doubled = dict(layer, wo=2.0 * layer["wo"])
    np.testing.assert_allclose(model.apply(dict(params, groups=[doubled, params["groups"][1]]), batch[0]), base, atol=2e-3)


def test_the_bias_moves_against_the_load_and_nothing_else_does():
    cfg, _, params, batch = _setup()
    model, _, _, ((_, (signal, _)), _) = programs_side("the-cell's-eight", "plain")  # ``objective``, as the step's program has it
    mask = model.state_mask()
    state = [p for p, m in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(mask)) if m]
    assert [s.shape for s in state] == [(2, 16), (1, 16), (3, 16), (1, 16)]  # a router a layer, stacked by run
    moved = model.advance_state(state, signal)
    for before, after, load in zip(state, moved, signal):
        want = before + cfg.bias_update_rate * np.sign(np.mean(load, axis=-1, keepdims=True) - load)
        np.testing.assert_allclose(after, want, atol=1e-7)
        assert float(jnp.max(jnp.abs(after - before))) == pytest.approx(cfg.bias_update_rate, rel=1e-4)


RC = dict(num_experts_per_tok=4, route_norm=True, route_scale=2.826)


def _experts(held):
    return RoutedExperts(
        RoutedExpertsConfig(
            dim=32, expert_hidden=24, num_experts=16, experts_held=held, top_k=4, routed_scaling_factor=2.826,
            shared_hidden=24, dtype=jnp.float32,
        )
    )


def test_eight_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The guide's share test: what the eight chips that share a layer's 16
    experts each compute, the shared expert (which every chip computes
    alike) counted once, adds up to the uncut reference's whole layer."""
    w = _experts((0, 16)).init(jax.random.PRNGKey(4))
    w["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(7), (16,))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, uncut_load = ref.moe_layer(x, w, RC, (0, 16))
        shared_part = ref.swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"])
    total, rows = jnp.zeros_like(x), 0.0
    for share in range(8):
        first = 2 * share
        mine = dict(w, **{k: w[k][first : first + 2] for k in ("w_gate", "w_up", "w_down")})
        out, load, _ = _experts((first, 2)).apply(mine, x)
        np.testing.assert_array_equal(load, uncut_load)  # every chip routes over all 16 alike
        total, rows = total + out, rows + float(load[first : first + 2].sum())
    np.testing.assert_allclose(total - 7 * shared_part, uncut, atol=3e-5)
    assert rows == 48 * 4  # every (token, choice) pair landed on exactly one share


def test_a_bfloat16_model_keeps_a_float32_stream_and_routes_on_it(monkeypatch):
    """The residual stream is float32 whatever the matrices' dtype and the
    router reads its float32 norm (PERF.md section 6, PR 33)."""
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    model = WindowedMoE(windowed_moe_debug(dtype=jnp.bfloat16))
    params = model.init(jax.random.PRNGKey(3))
    assert params["embed"].dtype == jnp.bfloat16 and params["groups"][1]["ffn"]["router"].dtype == jnp.float32
    seen = []
    real = model.moe.apply
    monkeypatch.setattr(model.moe, "apply", lambda w, x, *a: seen.append(x.dtype) or real(w, x, *a))
    tokens = jnp.zeros((1, SEQ), jnp.int32)
    x, _ = model._trunk(params, tokens)
    # a stacked run is traced once: four runs of expert layers
    assert x.dtype == jnp.float32 and seen == [jnp.float32] * 4
    assert model.apply(params, tokens).dtype == jnp.float32


@pytest.mark.parametrize(
    "kernel,count",
    [
        # three runs hold windowed layers (1, 2 and 3 deep), two a full layer: a run's body is traced once
        ("flash_win_fwd", 6), ("flash_win_dq", 3), ("flash_win_dkv", 3),
        ("flash_fwd", 2), ("flash_dq", 2), ("flash_dkv", 2),
    ],
)
def test_what_a_rematerialised_layer_keeps_and_what_it_runs_again(kernel, count):
    """Every layer is rematerialised.  A FULL layer keeps what flash made
    (``flash.KEPT_NAMES``): a second ``flash_fwd`` a run would read 4.  A
    WINDOWED layer keeps nothing of the kind (no room for all eight at the
    published widths) and ``flash_win_fwd`` stands twice a run.  The
    windowed layers' kernels are the ``flash_win_*`` ones, whose grid holds
    the window's blocks alone (``tests/test_flash_attention.py``)."""
    text = _gradients_jaxpr()
    assert text.count(f"name={kernel}\n") + text.count(f"name={kernel} ") == count, kernel


@functools.lru_cache(maxsize=None)
def _gradients_jaxpr():
    """Traced once for the six kernels' counts."""
    _, model, params, batch = _setup()
    return gradients_jaxpr(model, params, batch)
