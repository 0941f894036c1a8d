"""``models/sambay.py`` against the plain float32 reference
(``ftbench/architectures/sambay_reference.py``, which imports nothing of the
program) at toy widths: logits, ``loss`` and every leaf's gradient, on the
plain path and with the kernels in interpret mode; a windowed layer over
several windows; ``m``, ``K`` and ``V`` with TWO (G C) pairs, so that a summed
cotangent is in every comparison, and that sum against its readers one by one;
the tied leaf's gradient as the sum of the gather's and the head's; the blocked
head against the whole one.  Float32, seeded weights, the CPU.

Tolerances, with their reasons.  Both sides are float32 with matrix products at
``highest``; they differ in the ORDER of float32 additions (the scan's chunks
and the attention kernels' blocks with a running maximum against one pass a
row; a LayerNorm by ``rsqrt`` against a division).  Through the toy's eight
layers that reads 1.5e-5 on logits of up to 4.5 and 2e-5 of a leaf's largest
gradient: limits of 1e-4 on the logits, 2e-5 on the loss (the harness's own
tie) and 1e-3 of a leaf's largest gradient (+1e-6).  The same model with its
stream and matrices in bfloat16 (``test_bfloat16_fails_the_limits``) reads
3e-2 on the logits, three hundred times the limit."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ftbench.architectures import sambay_reference as ref
from torchft_tpu.models.sambay import KERNEL_PATH, SambaY, SambaYConfig, sambay_debug

from tests._once import once_a_run
from tests._toys import gradients_jaxpr, on_path

SEQ = 64  # four windows of 16, four scan chunks of 16, two head blocks of 32
CASES = {
    "two-readers": {},  # the toy: M S | M F | G C G C
    "the-cells-pattern": dict(pattern="MSMSMFGC", published_index=(0, 1, 2, 3, 16, 17, 18, 19)),
}


def reference_config(c: SambaYConfig) -> dict:
    """The configuration file's keys for a ``SambaYConfig``."""
    return dict(
        hidden_size=c.dim, num_attention_heads=c.n_heads, num_key_value_heads=c.n_kv_heads, sliding_window=c.window,
        layer_norm_eps=c.norm_eps, layer_pattern=c.pattern, layer_index=list(c.published_index),
    )


@functools.lru_cache(maxsize=None)
def _params(case):
    """The toy's parameters, made once a run of the tests.  Every bias starts
    at 0 and every norm's weight at 1: each gets values of its own, so that a
    bias dropped or a norm left out shows."""
    model = SambaY(sambay_debug(**CASES[case]))

    def stir(path, p):
        name = jax.tree_util.keystr(path)
        if not any(word in name for word in ("norm", "'b'", "'b_", "'bo'", "conv_bias", "'D'")):
            return p
        return p + 0.2 * jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(3), len(name) + p.size), p.shape)

    return once_a_run(
        f"sambay-params-{case}", lambda: jax.jit(lambda key: jax.tree_util.tree_map_with_path(stir, model.init(key)))(jax.random.PRNGKey(0))
    )


def _setup(case="two-readers", **over):
    """(config, a model of its own, the parameters, a batch): the model is
    the caller's alone, since what it traces depends on ``TORCHFT_FLASH``."""
    cfg = sambay_debug(**CASES[case], **over)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, SEQ)).astype(np.int32)
    return cfg, SambaY(cfg), _params(case), (jnp.asarray(tokens), jnp.asarray(np.roll(tokens, -1, axis=1)))


@functools.lru_cache(maxsize=None)
def reference_side(case):
    """The reference's logits, loss and gradients of a case, computed once a
    run for both of the program's paths."""
    cfg, _, params, batch = _setup(case)
    rc = reference_config(cfg)

    def make():
        loss, gradients = jax.jit(jax.value_and_grad(lambda p: ref.loss(p, batch, rc)))(params)
        return dict(logits=jax.jit(lambda p: ref.logits(p, batch[0], rc))(params), loss=loss, gradients=gradients)

    return once_a_run(f"sambay-reference-{case}", make)


@functools.lru_cache(maxsize=None)
def programs_side(case, path):
    """(model, logits, loss, ((objective, (signal, summary)), gradients)) of a
    case on ``path``: ONE program, once a process."""
    from tests._toys import program_side

    _, model, params, batch = _setup(case)
    with jax.default_matmul_precision("highest"):
        return (model, *program_side(model, params, batch, path))


def _leaves(tree):
    return {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _assert_gradients_agree(got, want, rel=1e-3):
    got, want = _leaves(got), _leaves(want)
    assert set(got) == set(want)
    for name, w in want.items():
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(got[name], w, atol=rel * scale + 1e-6, err_msg=name)


@pytest.mark.parametrize("path", ["plain", "kernels"])
@pytest.mark.parametrize("case", list(CASES))
def test_logits_and_loss_agree_with_the_reference(case, path):
    cfg, _, _, _ = _setup(case)
    want = reference_side(case)
    model, logits, loss, ((objective, (signal, summary)), _) = programs_side(case, path)
    assert model.attention_path == (KERNEL_PATH if path == "kernels" else "plain: TORCHFT_FLASH=0")
    assert logits.shape == (2, SEQ, cfg.vocab_size) and logits.dtype == jnp.float32
    # SEQ is four windows: a windowed layer that saw every earlier key would differ from the second window on
    assert SEQ >= 4 * cfg.window
    np.testing.assert_allclose(logits, want["logits"], atol=1e-4)
    assert float(loss) == pytest.approx(float(want["loss"]), abs=2e-5)
    assert float(objective) == pytest.approx(float(loss), abs=1e-6) and signal == []
    stats = model.summary_stats(np.asarray(summary))
    assert sorted(stats) == ["decay_min", "lambda"]
    # the scans ran (a step of 1e-3 to 0.1 against A down to -8 at the toy's eight states) and lambda is near lambda_0
    assert -4.0 < stats["decay_min"] < -0.3 and 0.2 < stats["lambda"] < 0.9


@pytest.mark.parametrize("path", ["plain", "kernels"])
@pytest.mark.parametrize("case", list(CASES))
def test_every_leafs_gradient_agrees_with_the_references(case, path):
    *_, (_, gradients) = programs_side(case, path)
    _assert_gradients_agree(gradients, reference_side(case)["gradients"])
    assert all(float(jnp.max(jnp.abs(x))) > 0 for x in _leaves(gradients).values())


def test_the_window_is_in_the_windowed_layers():
    """With a window that covers the sequence S is whole attention (another
    model): the logits differ from the second window on and agree inside the
    first, where the two masks are the same."""
    cfg, _, params, batch = _setup()
    model = SambaY(sambay_debug(window=SEQ))
    with on_path("plain"), jax.default_matmul_precision("highest"):
        covered = jax.jit(lambda p: model.apply(p, batch[0]))(params)
    want = reference_side("two-readers")["logits"]
    assert float(jnp.max(jnp.abs(covered - want))) > 1e-2
    np.testing.assert_allclose(covered[:, : cfg.window], want[:, : cfg.window], atol=1e-4)


def test_both_readers_cotangents_of_the_handed_on_values_count():
    """``m``, ``K`` and ``V`` are read by TWO (G C) pairs, and what the first
    half receives for each is the SUM of the two readers' cotangents.  In the
    reference, with every reader given a copy of its own, reader ``r``'s
    cotangent is a hundredth of the sum or more and so is the sum less it: a
    program that kept one reader's alone, or the last one's, would miss every
    first-half leaf's gradient by far more than the limit they are held to
    above (``test_every_leafs_gradient_agrees_with_the_references``)."""
    cfg, _, params, batch = _setup()
    rc = reference_config(cfg)
    readers = cfg.runs[1]
    assert readers == 2

    def reference_loss(copies):
        """The reference's loss with reader ``r`` handed ``m + copies[0][r]``, ``K + copies[1][r]``, ``V + copies[2][r]``."""
        x = jnp.asarray(params["embed"], jnp.float32)[batch[0]]
        lam_0 = ref.lambda_0(rc)
        take = lambda run, kind, i: jax.tree_util.tree_map(lambda a: a[i], params[run][kind])  # noqa: E731
        x, _ = ref.layer(x, take("first", "M", 0), ref.scan_mixer, rc)
        x, _ = ref.layer(x, take("first", "S", 0), lambda h, w: ref.attention_mixer(h, w, lam_0[1], cfg.window, rc), rc)
        x, m = ref.layer(x, take("middle", "M", 0), ref.scan_mixer, rc)
        x, (k, v) = ref.layer(x, take("middle", "F", 0), lambda h, w: ref.attention_mixer(h, w, lam_0[3], None, rc), rc)
        for r in range(readers):
            dm, dk, dv = (c[r] for c in copies)
            x, _ = ref.layer(x, take("second", "G", r), lambda h, w: (ref.memory_mixer(h, w, m + dm), None), rc)
            x, _ = ref.layer(x, take("second", "C", r), lambda h, w: (ref.cross_mixer(h, w, lam_0[5 + 2 * r], k + dk, v + dv, rc), None), rc)
        x = ref.layer_norm(x, params["final_norm"], cfg.norm_eps)
        return jnp.mean(ref.head_nll(params, x, batch[1]))

    B, kv = 2, (2, SEQ, cfg.n_kv_heads, cfg.head_dim)
    zeros = (jnp.zeros((readers, B, SEQ, cfg.d_inner)), jnp.zeros((readers, *kv)), jnp.zeros((readers, *kv)))
    with jax.default_matmul_precision("highest"):
        assert float(jax.jit(reference_loss)(zeros)) == pytest.approx(float(reference_side("two-readers")["loss"]), abs=1e-6)
        by_reader = jax.jit(jax.grad(reference_loss))(zeros)
    for name, parts in zip("m K V".split(), by_reader):
        scale = float(jnp.max(jnp.abs(jnp.sum(parts, axis=0))))
        for r in range(readers):  # no reader is idle, and no reader alone is the sum
            assert float(jnp.max(jnp.abs(parts[r]))) > 1e-2 * scale, (name, r)
            assert float(jnp.max(jnp.abs(parts[r] - jnp.sum(parts, axis=0)))) > 1e-2 * scale, (name, r)


def test_the_tied_leafs_gradient_is_the_gathers_plus_the_heads():
    """``embed`` is read twice, by the gather and by the head: with the head
    given a copy of its own the two gradients add up to the tied leaf's."""
    _, model, params, batch = _setup()

    def split_loss(gathered, head):
        x, _ = model._trunk(dict(params, embed=gathered), batch[0])
        x = model._head_input(params, x)
        return jnp.mean(jax.nn.logsumexp(model._logits(head, x), axis=-1) - jnp.take_along_axis(model._logits(head, x), batch[1][..., None], axis=-1)[..., 0])

    with on_path("plain"), jax.default_matmul_precision("highest"):
        by_gather, by_head = jax.jit(jax.grad(split_loss, argnums=(0, 1)))(params["embed"], params["embed"])
    *_, (_, gradients) = programs_side("two-readers", "plain")
    whole = gradients["embed"]
    scale = float(jnp.max(jnp.abs(whole)))
    np.testing.assert_allclose(by_gather + by_head, whole, atol=1e-5 * scale + 1e-8)
    assert float(jnp.max(jnp.abs(by_gather))) > 1e-2 * scale and float(jnp.max(jnp.abs(by_head))) > 1e-2 * scale
    # the gather touches only the rows of the tokens the batch holds; the head touches every row
    absent = np.setdiff1d(np.arange(whole.shape[0]), np.asarray(batch[0]).ravel())
    assert absent.size and not np.any(np.asarray(by_gather)[absent]) and np.all(np.any(np.asarray(by_head)[absent] != 0, axis=1))


@pytest.mark.parametrize("kernel,launches", [
    ("selscan_fwd", 2), ("selscan_bwd", 2), ("flash_win_fwd", 1), ("flash_win_dq", 1), ("flash_win_dkv", 1),
    ("flash_fwd", 2), ("flash_dq", 2), ("flash_dkv", 2),
])
def test_what_a_rematerialised_layer_keeps_and_what_it_runs_again(kernel, launches):
    """Three scans' bodies, each traced once: the (M S) pair, the (M F) pair
    and the (G C) pair.  A layer keeps what its kernels made
    (``flash.KEPT_NAMES``, ``selscan.KEPT_NAMES``): a forward kernel run again
    in the backward pass would read twice these."""
    text = _gradients_jaxpr()
    assert text.count(f"name={kernel}\n") + text.count(f"name={kernel} ") == launches, kernel


def test_no_per_token_state_is_in_the_gradient_step():
    """Every token's ``[channels, states]`` state is what a scan by ``lax.scan``
    or ``associative_scan`` would hold in HBM (5.4 GB a layer at the cell's
    size): on the kernels' path no array of the gradient's program has a
    sequence axis beside a state's two."""
    cfg, _, _, _ = _setup()
    text, inner, states = _gradients_jaxpr(), cfg.d_inner, cfg.d_state
    assert f"f32[2,{SEQ // cfg.scan_chunk},{states},{inner}]" in text  # the chunk-start states are there
    for shape in (f"[2,{SEQ},{states},{inner}]", f"[2,{SEQ},{inner},{states}]", f"[{SEQ},2,{states},{inner}]", f"[{SEQ},2,{inner},{states}]"):
        assert shape not in text, shape


@functools.lru_cache(maxsize=None)
def _gradients_jaxpr():
    _, model, params, batch = _setup()
    return gradients_jaxpr(model, params, batch)


def test_the_blocked_head_equals_the_whole_one():
    cfg, _, params, batch = _setup()
    assert SEQ % cfg.head_block == 0 and cfg.head_block < SEQ

    def side(head_block):
        model = SambaY(sambay_debug(head_block=head_block))
        with on_path("plain"), jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(model.loss))(params, batch)

    (loss, gradients), (whole_loss, whole_gradients) = side(cfg.head_block), side(4 * SEQ)
    assert float(loss) == pytest.approx(float(whole_loss), abs=2e-6)
    _assert_gradients_agree(gradients, whole_gradients, rel=1e-4)
    assert float(side(24)[0]) == pytest.approx(float(whole_loss), abs=2e-6)  # a block that does not divide: whole, not wrong


def test_bfloat16_fails_the_limits():
    """The limits are tight enough: the same weights through a bfloat16 model
    are a hundred times outside the logits' limit."""
    _, _, params, batch = _setup()
    model = SambaY(sambay_debug(dtype=jnp.bfloat16))
    rounded = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2 else a, params)
    with on_path("plain"):
        logits = jax.jit(lambda p: model.apply(p, batch[0]))(rounded)
    assert float(jnp.max(jnp.abs(logits - reference_side("two-readers")["logits"]))) > 100 * 1e-4


def test_a_pattern_that_is_not_two_halves_is_refused():
    with pytest.raises(ValueError, match="pattern"):
        SambaY(sambay_debug(pattern="MSGCMF"))
    with pytest.raises(ValueError, match="published indices"):
        SambaY(sambay_debug(published_index=(0, 1, 2)))
    assert sambay_debug().runs == (1, 2) and SambaYConfig().runs == (8, 7)
    np.testing.assert_allclose(SambaYConfig().lambda_0()[[0, 1, 17]], [0.2, 0.8 - 0.6 * np.exp(-0.3), 0.8 - 0.6 * np.exp(-5.1)], rtol=1e-6)


def test_the_count_of_parameters_at_the_published_widths():
    """``num_params`` at the cell's eight layers and an eighth of the
    vocabulary (a quarter, ISSUE 63's cut, is 64,020,480 more), and whole;
    shapes alone, nothing is allocated."""
    cut = SambaY(SambaYConfig(vocab_size=25_008, pattern="MSMSMFGC", published_index=(0, 1, 2, 3, 16, 17, 18, 19)))
    assert cut.num_params() == 915_311_616 == 979_332_096 - 25_008 * 2560
    shapes = cut._shapes
    assert shapes["embed"].shape == (25_008, 2560) and "lm_head" not in shapes  # ONE leaf
    assert shapes["first"]["M"]["mixer"]["A_log"].shape == (2, 5120, 16) and shapes["first"]["M"]["mixer"]["w_x"].shape == (2, 5120, 192)
    assert shapes["middle"]["F"]["mixer"]["w_qkv"].shape == (1, 2560, 5120) and shapes["second"]["G"]["mixer"]["w_1"].shape == (1, 2560, 5120)
    whole = SambaY(SambaYConfig())
    assert round(whole.num_params() / 1e6) == 3853  # the published 3.8B
