"""``models/looped.py`` against the plain float32 reference
(``ftbench/architectures/looped_reference.py``, which imports nothing of the
program) at toy widths: every pass's logits, the exit distribution, ``loss``,
``objective`` and every leaf's gradient; a stacked leaf's gradient as the SUM
over the passes; the final norm after every pass; the blocked head against the
whole one.  Float32, seeded weights, the CPU; the flash kernels in interpret
mode where a case says so.

Tolerances, with their reasons.  Both sides are float32 with matrix products
at ``highest``; they differ in the ORDER of float32 additions (the kernels'
blocks with a running maximum against one softmax a row, ``log p`` from
``softplus`` against products of sigmoids).  Through the toy's two layers and
four passes that reads 2e-6 on logits and cross-entropies of up to 7 and 1.2e-6
of a leaf's largest gradient: limits of 5e-5 on the logits, 2e-5 on the losses
(the harness's own tie) and 1e-3 of a leaf's largest gradient (+1e-6).  A model
whose later passes start from the stream BEFORE the final norm reads 0.1 to 1
on the logits: it fails a thousand times over."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ftbench.architectures import looped_reference as ref
from torchft_tpu.models.looped import KERNEL_PATH, Looped, LoopedConfig, looped_debug

from tests._once import once_a_run
from tests._toys import gradients_jaxpr, on_path

SEQ = 64
CASES = {
    "four-passes": {},  # the toy: two layers, a head in blocks of 32
    "one-pass": dict(n_passes=1),
    "one-layer-three-passes": dict(n_layers=1, n_passes=3),
}


def reference_config(c: LoopedConfig) -> dict:
    """The configuration file's keys for a ``LoopedConfig``."""
    return dict(
        num_attention_heads=c.n_heads, rope_theta=c.rope_theta, rms_norm_eps=c.norm_eps, total_ut_steps=c.n_passes,
        assumed=dict(entropy_beta=c.entropy_beta),
    )


@functools.lru_cache(maxsize=None)
def _params(n_layers):
    """The toy's parameters, made once a run of the tests.  Every norm's
    weight starts at 1 and the gate's bias at 0: each gets values of its own,
    so that a norm left out or a bias dropped shows."""
    model = Looped(looped_debug(n_layers=n_layers))

    def stir(path, p):
        name = jax.tree_util.keystr(path)
        if "norm" not in name and "'b'" not in name:
            return p
        return p + 0.2 * jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(3), len(name) + p.size), p.shape)

    return once_a_run(
        f"looped-params-{n_layers}", lambda: jax.jit(lambda key: jax.tree_util.tree_map_with_path(stir, model.init(key)))(jax.random.PRNGKey(0))
    )


def _setup(**over):
    """(config, a model of its own, the parameters, a batch): the model is
    the caller's alone, since what it traces depends on ``TORCHFT_FLASH``."""
    cfg = looped_debug(**over)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, SEQ)).astype(np.int32)
    return cfg, Looped(cfg), _params(cfg.n_layers), (jnp.asarray(tokens), jnp.asarray(np.roll(tokens, -1, axis=1)))


@functools.lru_cache(maxsize=None)
def reference_side(case):
    """The reference's logits, passes and gradients of a case, computed once
    a run for both of the program's paths."""
    cfg, _, params, batch = _setup(**CASES[case])
    rc = reference_config(cfg)

    def make():
        return dict(
            logits=jax.jit(lambda p: ref.logits(p, batch[0], rc))(params),
            passes=jax.jit(lambda p: ref.passes(p, batch, rc))(params),
            gradients=jax.jit(jax.grad(lambda p: ref.objective(p, batch, rc)))(params),
            token_nll=jax.jit(lambda p: ref.token_nll(p, *batch, rc))(params),
        )

    return once_a_run(f"looped-reference-{case}", make)


@functools.lru_cache(maxsize=None)
def programs_side(case, path):
    """(model, (every pass's logits, ``p``), ``apply``'s logits, ``loss``,
    ((objective, (signal, summary)), gradients)) of a case on ``path``: ONE
    program, computed once a process for the two tests that read it."""
    _, model, params, batch = _setup(**CASES[case])

    def every(p, b):
        return model.apply_all(p, b[0]), model.apply(p, b[0]), model.loss(p, b), jax.value_and_grad(model.objective, has_aux=True)(p, b)

    with on_path(path):
        return (model, *jax.jit(every)(params, batch))


def _leaves(tree):
    return {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _assert_gradients_agree(got, want):
    got, want = _leaves(got), _leaves(want)
    assert set(got) == set(want)
    for name, w in want.items():
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(got[name], w, atol=1e-3 * scale + 1e-6, err_msg=name)


@pytest.mark.parametrize("path", ["plain", "kernels"])
@pytest.mark.parametrize("case", list(CASES))
def test_every_pass_agrees_with_the_reference_at_every_position(case, path):
    cfg, _, _, batch = _setup(**CASES[case])
    want = reference_side(case)
    model, (logits, p), last, loss, ((objective, (signal, summary)), _) = programs_side(case, path)
    T = cfg.n_passes
    assert model.attention_path == (KERNEL_PATH if path == "kernels" else "plain: TORCHFT_FLASH=0")
    assert logits.shape == (T, 2, SEQ, cfg.vocab_size) and logits.dtype == jnp.float32 and p.shape == (T, 2, SEQ)
    np.testing.assert_allclose(logits, want["logits"], atol=5e-5)
    np.testing.assert_allclose(p, want["passes"]["p"], atol=2e-6)
    # an exit distribution: it sums to 1 at every position, whatever the gate says
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, atol=2e-6)
    # ``apply`` is the LAST pass, and ``loss`` the mean of its cross-entropy: the tie the benchmark holds
    np.testing.assert_allclose(last, want["logits"][-1], atol=5e-5)
    assert float(loss) == pytest.approx(float(jnp.mean(want["passes"]["nll"][-1])), abs=2e-5)
    assert float(loss) == pytest.approx(float(jnp.mean(want["token_nll"])), abs=2e-5)
    assert float(objective) == pytest.approx(float(want["passes"]["objective"]), abs=2e-5)
    # no state the optimizer does not own; the summary is T losses, T probabilities, the entropy
    assert signal == [] and summary.shape == (2 * T + 1,)
    stats = model.summary_stats(np.asarray(summary))
    assert sorted(stats) == ["exit_entropy", "exit_p", "pass_nll"]
    np.testing.assert_allclose(stats["pass_nll"], jnp.mean(want["passes"]["nll"], axis=(1, 2)), atol=2e-5)
    np.testing.assert_allclose(stats["exit_p"], jnp.mean(want["passes"]["p"], axis=(1, 2)), atol=2e-6)
    assert sum(stats["exit_p"]) == pytest.approx(1.0, abs=1e-5)
    if T == 1:
        # one pass: the exit is certain, the objective IS the loss
        assert stats["exit_p"] == [1.0] and stats["exit_entropy"] == 0.0
        assert float(objective) == pytest.approx(float(loss), abs=1e-6)
    else:
        assert 0.0 < stats["exit_entropy"] <= np.log(T) + 1e-6 and float(objective) != pytest.approx(float(loss), abs=1e-3)


@pytest.mark.parametrize("path", ["plain", "kernels"])
@pytest.mark.parametrize("case", list(CASES))
def test_every_leafs_gradient_agrees_with_the_references(case, path):
    cfg, _, params, _ = _setup(**CASES[case])
    *_, (_, gradients) = programs_side(case, path)
    _assert_gradients_agree(gradients, reference_side(case)["gradients"])
    g = _leaves(gradients)
    if cfg.n_passes == 1:
        # no pass before the last: the gate is not in the objective at all
        assert not np.any(g["['gate']['w']"]) and not np.any(g["['gate']['b']"])
    else:
        assert all(float(jnp.max(jnp.abs(x))) > 0 for x in g.values())
    # ONE set of leaves: a stacked leaf's gradient is a layer's, never a pass's
    assert g["['layers']['wq']"].shape == params["layers"]["wq"].shape == (cfg.n_layers, cfg.dim, cfg.dim)


def test_a_stacked_leafs_gradient_is_the_sum_over_the_passes():
    """Every pass given leaves of its own (the stack tiled ``T`` times, equal
    values): the gradient with respect to pass ``t``'s copy is that pass's
    contribution with the other passes' uses stopped, and the four add up to
    the gradient of the one shared set, leaf by leaf."""
    cfg, model, params, batch = _setup()
    T = cfg.n_passes
    tiled = jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (T, *a.shape)), params["layers"])
    with on_path("plain"):
        shared = jax.jit(jax.grad(lambda p: model.objective(p, batch)[0]))(params)
        by_pass = jax.jit(jax.grad(lambda own: model.objective(params, batch, own)[0]))(tiled)
    for name, whole in _leaves(shared["layers"]).items():
        parts = _leaves(by_pass)[name]
        assert parts.shape == (T, *whole.shape)
        scale = float(jnp.max(jnp.abs(whole)))
        np.testing.assert_allclose(jnp.sum(parts, axis=0), whole, atol=1e-5 * scale + 1e-8, err_msg=name)
        # no pass is idle, and no pass alone is the whole
        for t in range(T):
            assert float(jnp.max(jnp.abs(parts[t]))) > 1e-3 * scale, (name, t)
            assert float(jnp.max(jnp.abs(parts[t] - whole))) > 1e-2 * scale, (name, t)


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_dq", "flash_dkv"])
def test_what_a_rematerialised_layer_keeps_and_what_it_runs_again(kernel):
    """The layers are one scan inside the passes' scan, whose body is traced
    once, rematerialised but for its input and what flash made
    (``flash.KEPT_NAMES``): a second ``flash_fwd`` in the body would read 2."""
    text = _gradients_jaxpr()
    assert text.count(f"name={kernel}\n") + text.count(f"name={kernel} ") == 1, kernel


@functools.lru_cache(maxsize=None)
def _gradients_jaxpr():
    """Traced once for the three kernels' counts."""
    _, model, params, batch = _setup()
    return gradients_jaxpr(model, params, batch)


def test_the_last_passs_gate_draws_no_gradient_and_p_needs_no_sigmoid_of_it():
    cfg, model, params, _ = _setup()
    x_all = jax.random.normal(jax.random.PRNGKey(5), (cfg.n_passes, 2, 8, cfg.dim))
    weights = jax.random.normal(jax.random.PRNGKey(6), (cfg.n_passes, 2, 8))
    through = jax.grad(lambda x: jnp.sum(weights * model._exit_log_p(params, x)))(x_all)
    assert not np.any(through[-1]) and all(np.any(through[t]) for t in range(cfg.n_passes - 1))
    p = jnp.exp(model._exit_log_p(params, 30.0 * x_all))  # gates driven to 0 and 1: still a distribution, no NaN
    assert np.all(np.isfinite(p)) and np.allclose(jnp.sum(p, axis=0), 1.0, atol=1e-6)


def test_the_final_norm_is_applied_after_every_pass():
    """A model whose NEXT pass starts from the stream before the final norm
    (the norm before the head alone, as a plain decoder has it) is another
    model from the second pass on; the program is not that one."""
    cfg, _, params, batch = _setup()
    rc = reference_config(cfg)

    def head_only(p):
        x, out = ref._f32(p["embed"])[batch[0]], []
        with jax.default_matmul_precision("highest"):
            for _ in range(cfg.n_passes):
                for i in range(cfg.n_layers):
                    x = ref.block(x, jax.tree_util.tree_map(lambda a: a[i], p["layers"]), rc)
                out.append(ref.rms_norm(x, p["final_norm"], cfg.norm_eps) @ p["lm_head"])
        return jnp.stack(out)

    without = jax.jit(head_only)(params)
    _, (logits, _), *_ = programs_side("four-passes", "plain")
    np.testing.assert_allclose(logits[0], without[0], atol=5e-5)  # the first pass is the same
    assert float(jnp.max(jnp.abs(logits[1:] - without[1:]))) > 0.05


def test_the_blocked_head_equals_the_whole_one():
    cfg, _, params, batch = _setup()
    assert SEQ % cfg.head_block == 0 and cfg.head_block < SEQ

    def side(head_block):
        model = Looped(looped_debug(head_block=head_block))
        with on_path("plain"):
            return jax.jit(lambda p: (model.pass_losses(p, batch)[0], jax.grad(lambda q: model.objective(q, batch)[0])(p)))(params)

    (nll, gradients), (whole_nll, whole_gradients) = side(cfg.head_block), side(4 * SEQ)
    np.testing.assert_allclose(nll, whole_nll, atol=2e-6)
    _assert_gradients_agree(gradients, whole_gradients)
    # a block that does not divide the sequence: the head is whole, not wrong
    np.testing.assert_allclose(side(24)[0], whole_nll, atol=2e-6)


def test_one_set_of_leaves_at_the_published_widths():
    """``num_params`` at eight of the published layers: ISSUE 59's count, no
    leaf a pass; shapes alone, nothing is allocated."""
    model = Looped(LoopedConfig(n_layers=8))
    assert model.num_params() == 612_438_017 == 8 * 51_388_416 + 2 * 100_663_296 + 2048 + 2049
    shapes = model._shapes
    assert shapes["layers"]["w_gate"].shape == (8, 2048, 5632) and shapes["layers"]["norms"]["ffn_out"].shape == (8, 2048)
    assert shapes["gate"]["w"].shape == (2048,) and shapes["gate"]["b"].shape == (1,)
    assert Looped(LoopedConfig(n_layers=48)).num_params() == 2_667_974_657
    assert Looped.summary_stats(np.arange(9.0)) == dict(pass_nll=[0.0, 1.0, 2.0, 3.0], exit_p=[4.0, 5.0, 6.0, 7.0], exit_entropy=8.0)
    with pytest.raises(ValueError, match="runs once at least"):
        Looped(LoopedConfig(n_passes=0))


def test_a_group_of_several_chips_is_refused_as_llamas_one_chip_kernels_are(monkeypatch):
    from torchft_tpu.parallel.mesh import make_mesh

    monkeypatch.delenv("TORCHFT_FLASH", raising=False)
    monkeypatch.setenv("TORCHFT_FLASH_PLATFORM", "tpu")
    model = Looped(looped_debug(), mesh=make_mesh(fsdp=2, devices=jax.devices()[:2]))
    assert "a group of 2 chips" in model._kernel_refusal(128)
    model.mesh = make_mesh(fsdp=1, devices=jax.devices()[:1])
    assert model._kernel_refusal(128) is None and "does not divide" in model._kernel_refusal(20)
