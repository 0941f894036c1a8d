"""``models/ssm_hybrid_dense.py`` against the plain float32 reference
(``ftbench/architectures/ssm_hybrid_dense_reference.py``, which imports nothing
of the program) at toy widths on the published period of ten layers: logits,
``loss`` and every leaf's gradient, the tied embedding's included (it gets
cotangents from both ends), on the plain path and with the kernels in interpret
mode; each of the four multipliers against the reference at another value, and
at 1, 1, ``1 / sqrt(head_dim)``, 1 the plain pre-norm layer; the runs of five
and four stacked layers against the same layers one by one; what a
rematerialised layer keeps.  Float32, seeded weights, the CPU.

Tolerances, with their reasons.  Both sides are float32 with matrix products at
``highest``; they differ in the ORDER of float32 additions (the scan's chunks
against the token recurrence, the attention kernels' blocks with a running
maximum against one pass a row, the head in blocks).  Through the toy's ten
layers that reads 2e-6 on logits of up to 1.5 and 1e-5 of a leaf's largest
gradient: limits of 1e-4 on the logits, 2e-5 on the loss (the harness's own
tie) and 1e-3 of a leaf's largest gradient (+1e-6)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ftbench.architectures import ssm_hybrid_dense_reference as ref
from torchft_tpu.models.ssm_hybrid_dense import KERNEL_PATH, SsmHybridDense, SsmHybridDenseConfig, ssm_hybrid_dense_debug

from tests._once import once_a_run
from tests._toys import gradients_jaxpr, on_path, program_side

SEQ = 64  # four scan chunks of 16, two head blocks of 32
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier", "attention_multiplier", "logits_scaling")


def reference_config(c: SsmHybridDenseConfig) -> dict:
    """The configuration file's keys for a ``SsmHybridDenseConfig``."""
    return dict(
        hidden_size=c.dim, layer_types=list(c.layer_types), mamba_n_heads=c.ssm_heads, mamba_d_head=c.ssm_head_dim,
        mamba_d_state=c.ssm_state, mamba_n_groups=c.ssm_groups, num_attention_heads=c.n_heads,
        num_key_value_heads=c.n_kv_heads, rms_norm_eps=c.norm_eps, **{name: getattr(c, name) for name in MULTIPLIERS},
    )


@functools.lru_cache(maxsize=None)
def _params():
    """The toy's parameters, made once a run of the tests.  Every norm's
    weight starts at 1, the convolution's bias at 0 and ``D`` at 1: each gets
    values of its own, so that one left out shows."""
    model = SsmHybridDense(ssm_hybrid_dense_debug())

    def stir(path, p):
        name = jax.tree_util.keystr(path)
        if not any(word in name for word in ("norm", "conv_bias", "'D'")):
            return p
        return p + 0.2 * jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(3), len(name) + p.size), p.shape)

    return once_a_run(
        "ssm-hybrid-dense-params", lambda: jax.jit(lambda key: jax.tree_util.tree_map_with_path(stir, model.init(key)))(jax.random.PRNGKey(0))
    )


def _setup():
    """(config, a model of its own, the parameters, a batch): the model is
    the caller's alone, since what it traces depends on ``TORCHFT_FLASH``."""
    cfg = ssm_hybrid_dense_debug()
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, SEQ)).astype(np.int32)
    return cfg, SsmHybridDense(cfg), _params(), (jnp.asarray(tokens), jnp.asarray(np.roll(tokens, -1, axis=1)))


@functools.lru_cache(maxsize=None)
def reference_side():
    """The reference's logits, loss and gradients, computed once a run for
    both of the program's paths."""
    cfg, _, params, batch = _setup()
    rc = reference_config(cfg)

    def make():
        loss, gradients = jax.jit(jax.value_and_grad(lambda p: ref.loss(p, batch, rc)))(params)
        return dict(logits=jax.jit(lambda p: ref.logits(p, batch[0], rc))(params), loss=loss, gradients=gradients)

    return once_a_run("ssm-hybrid-dense-reference", make)


@functools.lru_cache(maxsize=None)
def programs_side(path):
    """(model, logits, loss, ((objective, (signal, summary)), gradients)) on
    ``path``: ONE program, once a process."""
    _, model, params, batch = _setup()
    with jax.default_matmul_precision("highest"):
        return (model, *program_side(model, params, batch, path))


def _leaves(tree):
    return {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_logits_and_loss_agree_with_the_reference(path):
    cfg, _, _, _ = _setup()
    want = reference_side()
    model, logits, loss, ((objective, (signal, summary)), _) = programs_side(path)
    assert model.attention_path == (KERNEL_PATH if path == "kernels" else "plain: TORCHFT_FLASH=0")
    assert model.groups == [("mamba", 5), ("attention", 1), ("mamba", 4)] and cfg.ssm_groups == 1
    assert logits.shape == (2, SEQ, cfg.vocab_size) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want["logits"], atol=1e-4)
    assert float(loss) == pytest.approx(float(want["loss"]), abs=2e-5)
    # seeded weights: a token's logit for itself is of order one, so the loss is that of a guess
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 0.3
    assert float(objective) == pytest.approx(float(loss), abs=1e-6) and signal == []
    # the scans ran: a step of 1e-3 to 0.1 (and what the seeded W_in adds under the softplus) against A up to 16
    assert list(model.summary_stats(np.asarray(summary))) == ["decay_min"] and -40.0 < float(summary[0]) < -0.3


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_every_leafs_gradient_agrees_with_the_references(path):
    *_, (_, gradients) = programs_side(path)
    got, want = _leaves(gradients), _leaves(reference_side()["gradients"])
    assert set(got) == set(want) and len(got) == 1 + 12 + 8 + 12 + 1  # ONE tied leaf, three runs' stacks, the final norm
    for name, w in want.items():
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(got[name], w, atol=1e-3 * scale + 1e-6, err_msg=name)
        assert float(jnp.max(jnp.abs(got[name]))) > 0, name
    # the tied leaf's gradient has the head's part in EVERY row, the batch's tokens or not
    assert bool(jnp.all(jnp.max(jnp.abs(got["['embed']"]), axis=1) > 0))


@functools.lru_cache(maxsize=None)
def _by_multipliers():
    """(the program's logits, the reference's) as functions of the four
    multipliers, a program each: the multipliers are the configuration's, read
    where they act, so a traced value goes where a published one does."""
    cfg, _, params, (tokens, _) = _setup()
    rc = reference_config(cfg)

    def program(values):
        return SsmHybridDense(dataclasses.replace(cfg, **dict(zip(MULTIPLIERS, values)))).apply(params, tokens)

    def reference(values):
        return ref.logits(params, tokens, dict(rc, **dict(zip(MULTIPLIERS, values))))

    with on_path("plain"), jax.default_matmul_precision("highest"):
        return jax.jit(program), jax.jit(reference)


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_moves_the_result_as_the_reference_says(name):
    cfg, _, _, _ = _setup()
    program, reference = _by_multipliers()
    published = [float(getattr(cfg, m)) for m in MULTIPLIERS]
    # the toy's scores are small under 1/64 at heads of 8: the attention's multiplier is moved far
    factor = 40.0 if name == "attention_multiplier" else 1.5
    moved = [factor * v if m == name else v for m, v in zip(MULTIPLIERS, published)]
    with on_path("plain"):
        base, got = program(published), program(moved)
    assert float(jnp.max(jnp.abs(got - base))) > 1e-3, name
    np.testing.assert_allclose(got, reference(moved), atol=1e-4)


def test_at_unit_multipliers_the_layer_is_the_plain_pre_norm_layer():
    """1, 1, ``1 / sqrt(head_dim)``, 1: ``h = E[token]``, ``h += mixer(norm(h))``,
    ``h += ffn(norm(h))``, ``logits = norm(h) E^T``, written out here over the
    reference's mixers."""
    cfg, _, params, (tokens, _) = _setup()
    unit = (1.0, 1.0, cfg.head_dim ** -0.5, 1.0)
    rc = dict(reference_config(cfg), attention_multiplier=unit[2])

    def pre_norm(params):
        embed = params["embed"]
        x = embed[tokens]
        for kind, w in zip(cfg.layer_types, ref._layers(params)):
            mixer = ref.mamba_mixer if kind == "mamba" else ref.attention_mixer
            x = x + mixer(ref.rms_norm(x, w["norm"], cfg.norm_eps), w["mixer"], rc)
            x = x + ref.swiglu(ref.rms_norm(x, w["post_norm"], cfg.norm_eps), w["ffn"])
        return ref.rms_norm(x, params["final_norm"], cfg.norm_eps) @ embed.T

    with on_path("plain"), jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(_by_multipliers()[0](unit), jax.jit(pre_norm)(params), atol=1e-4)


def test_stacked_runs_are_the_same_layers_one_by_one():
    """The runs of five and four Mamba-2 layers under their ``lax.scan``
    against ``_block`` called on every layer's own slice of the stacks."""
    cfg, model, params, (tokens, _) = _setup()

    def one_by_one(params):
        x = params["embed"][tokens].astype(jnp.float32) * cfg.embedding_multiplier
        lows = []
        for (kind, depth), stacked in zip(model.groups, params["groups"]):
            for j in range(depth):
                x, low = model._block(x, jax.tree_util.tree_map(lambda a: a[j], stacked), kind, False)
                lows.append(low)
        return x, jnp.min(jnp.stack(lows))

    with on_path("plain"), jax.default_matmul_precision("highest"):
        (x, low), (want, want_low) = jax.jit(lambda p: model._trunk(p, tokens))(params), jax.jit(one_by_one)(params)
    np.testing.assert_allclose(x, want, atol=2e-5)
    assert float(low[0]) == pytest.approx(float(want_low), rel=1e-6)


def test_a_rematerialised_layer_keeps_what_the_kernels_made():
    """The gradient's program launches every forward kernel ONCE a layer:
    nine ``ssd_fwd`` and nine ``ssd_bwd``, one of each flash kernel."""
    _, model, params, batch = _setup()
    text = gradients_jaxpr(model, params, batch)
    # a run of depth d holds its body once: three runs, (5, 1, 4)
    assert [text.count(f"name={k}") for k in ("ssd_fwd", "ssd_bwd", "flash_fwd", "flash_dq", "flash_dkv")] == [2, 2, 1, 1, 1]


def test_a_configuration_that_does_not_fit_is_refused():
    with pytest.raises(ValueError, match="a layer is one of"):
        SsmHybridDense(ssm_hybrid_dense_debug(layer_types=("mamba", "experts")))
    with pytest.raises(ValueError, match="divide into"):
        SsmHybridDense(ssm_hybrid_dense_debug(n_kv_heads=3))
