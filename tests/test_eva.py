"""``models/eva.py`` and ``ops/flash_attention.py`` ``eva_attention`` against
the plain float32 reference (``ftbench/architectures/eva_reference.py``, which
imports nothing of the program) at toy widths: every slice's logits, ``loss``,
``objective`` and every leaf's gradient, the pooling's two learned vectors
named; a window that covers the sequence; causality across every boundary;
what the walk visits; what a rematerialised layer keeps.  Float32, seeded
weights, the CPU; the kernels in interpret mode where a case says so.

Tolerances, with their reasons.  Both sides are float32 with matrix products
at ``highest``; they differ in the ORDER of float32 additions: the kernels'
blocks with a running maximum against one softmax a row, one head matrix
against its slices.  Through the toy's layers that reads 1.0e-5 on logits of up
to 7 and 2.8e-6 of a leaf's largest gradient: limits of 5e-5 on the logits, 2e-5
on the losses (the harness's own tie) and 1e-3 of a leaf's largest gradient
(+1e-6).  bfloat16 matrices read 0.29 to 0.36 on the logits and ``mu`` left
out 0.58: both fail a thousand times over."""

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ftbench.architectures import eva_reference as ref
from torchft_tpu.models.eva import KERNEL_PATH, Eva, EvaConfig, eva_debug
from torchft_tpu.models.llama import Llama
from torchft_tpu.ops import flash_attention as flash

from tests._once import once_a_run
from tests._toys import gradients_jaxpr, on_path

SEQ = 64
CASES = {
    "two-windows-of-four-chunks": {},  # the toy: window 32, chunk 8
    "four-windows-of-eight-chunks": dict(window_size=16, chunk_size=2),
    "one-layer-a-chunk-a-window": dict(n_layers=1, window_size=16, chunk_size=16),
    "the-next-byte-alone": dict(n_pred_heads=1),
}


def reference_config(c: EvaConfig) -> dict:
    """The configuration file's keys for an ``EvaConfig``."""
    return dict(
        hidden_size=c.dim, num_attention_heads=c.n_heads, window_size=c.window_size, chunk_size=c.chunk_size,
        num_pred_heads=c.n_pred_heads, vocab_size=c.vocab_size, rope_theta=c.rope_theta, rms_norm_eps=c.norm_eps,
    )


@functools.lru_cache(maxsize=None)
def _params(**over):
    """The toy's parameters, made once a run of the tests (``init`` runs
    operation by operation: seconds of small compiles in every process that
    makes them)."""
    model = Eva(eva_debug(**over))

    def stir(path, p):
        """The norms' ``g`` starts at 0: a gradient is only tested where the
        leaf's value matters, so each gets values of its own (the same noise
        in every process: ``hash`` of a string is salted anew in each)."""
        name = getattr(path[-1], "key", "")
        noise = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(3), zlib.crc32(name.encode()) % 997), p.shape)
        return p + 0.1 * noise if name.endswith("_norm") else p

    return once_a_run(
        f"eva-params-{sorted(over.items())}", lambda: jax.jit(lambda key: jax.tree_util.tree_map_with_path(stir, model.init(key)))(jax.random.PRNGKey(0))
    )


def _setup(**over):
    """(config, a model of its own, the parameters, a batch): the model is
    the caller's alone, since what it traces depends on ``TORCHFT_FLASH``."""
    cfg = eva_debug(**over)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, SEQ)).astype(np.int32)
    return cfg, Eva(cfg), _params(**over), (jnp.asarray(tokens), jnp.asarray(np.roll(tokens, -1, axis=1)))


@functools.lru_cache(maxsize=None)
def reference_side(case):
    """The reference's logits, losses and gradients of a case, computed once
    a run for both of the program's paths."""
    cfg, _, params, batch = _setup(**CASES[case])
    rc = reference_config(cfg)

    def make():
        return dict(
            logits=jax.jit(lambda p: ref.slice_logits(p, batch[0], rc))(params),
            means=jax.jit(lambda p: ref.slice_means(p, batch, rc))(params),
            objective=jax.jit(jax.value_and_grad(lambda p: ref.objective(p, batch, rc)))(params),
            token_nll=jax.jit(lambda p: ref.token_nll(p, *batch, rc))(params),
        )

    return once_a_run(f"eva-reference-{case}", make)


@functools.lru_cache(maxsize=None)
def programs_side(case, path):
    """(model, every slice's logits, ``apply``'s logits, ``loss``,
    ((objective, (signal, summary)), gradients)) of a case on ``path``: ONE
    program, computed once a process for the two tests that read it."""
    _, model, params, batch = _setup(**CASES[case])

    def every(p, b):
        return model.apply_all(p, b[0]), model.apply(p, b[0]), model.loss(p, b), jax.value_and_grad(model.objective, has_aux=True)(p, b)

    with on_path(path):
        return (model, *jax.jit(every)(params, batch))


@pytest.fixture(params=["plain", "kernels"])
def path(request, monkeypatch):
    monkeypatch.setenv("TORCHFT_FLASH", "1" if request.param == "kernels" else "0")
    return request.param


def _leaves(tree):
    return {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("case", list(CASES))
def test_every_slices_logits_and_both_losses_agree_with_the_reference(case, path):
    cfg, _, _, batch = _setup(**CASES[case])
    want = reference_side(case)
    model, logits, first_slice, loss, ((objective, (signal, summary)), _) = programs_side(case, path)
    assert model.attention_path == (KERNEL_PATH if path == "kernels" else "plain: TORCHFT_FLASH=0")
    assert logits.shape == (2, SEQ, cfg.n_pred_heads, cfg.vocab_size) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want["logits"], atol=5e-5)
    # ``apply`` is slice 0, and ``loss`` the mean of its cross-entropy: the tie the benchmark holds
    np.testing.assert_allclose(first_slice, want["logits"][:, :, 0], atol=5e-5)
    loss = float(loss)
    assert loss == pytest.approx(float(want["means"][0]), abs=2e-5)
    assert loss == pytest.approx(float(jnp.mean(want["token_nll"])), abs=2e-5)
    assert float(objective) == pytest.approx(float(want["objective"][0]), abs=2e-5)
    assert float(objective) == pytest.approx(float(jnp.mean(want["means"])), abs=2e-5)
    # no state the optimizer does not own; the summary is the further slices' mean alone
    assert signal == []
    stats = model.summary_stats(np.asarray(summary))
    if cfg.n_pred_heads == 1:
        assert stats == {} and float(objective) == pytest.approx(loss, abs=1e-6)
    else:
        assert list(stats) == ["multibyte_nll"]
        assert stats["multibyte_nll"] == pytest.approx(float(jnp.mean(want["means"][1:])), abs=2e-5)


@pytest.mark.parametrize("case", list(CASES))
def test_every_leafs_gradient_agrees_with_the_reference(case, path):
    _, want_grads = reference_side(case)["objective"]
    (_, grads) = programs_side(case, path)[4]
    got, wanted = _leaves(grads), _leaves(want_grads)
    assert got.keys() == wanted.keys()
    # the pooling's two learned vectors take gradient from the summaries' keys alone
    assert {"['layers']['phi']", "['layers']['mu']"} <= got.keys()
    for name in got:
        scale = float(jnp.max(jnp.abs(wanted[name])))
        assert scale > 1e-7, name  # every leaf learns
        np.testing.assert_allclose(got[name], wanted[name], atol=1e-3 * scale + 1e-6, err_msg=name)


def test_a_position_without_the_label_is_left_out_and_not_wrapped(monkeypatch):
    """Slice ``m`` at ``t`` is of the byte at ``t + 1 + m`` = ``targets[t +
    m]``: the last ``m`` positions have none.  A change to the targets'
    FIRST ``m`` entries, which a wrap would read there, moves nothing."""
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    cfg, model, params, (tokens, targets) = _setup()
    x = model._trunk(params, tokens)
    means = model._slice_nll(params, x, targets, cfg.n_pred_heads)
    logp = jax.nn.log_softmax(model.apply_all(params, tokens), axis=-1)
    for m in range(cfg.n_pred_heads):
        by_hand = -jnp.mean(jnp.take_along_axis(logp[:, : SEQ - m, m], targets[:, m:, None], axis=-1))
        assert float(means[m]) == pytest.approx(float(by_hand), abs=1e-5)
    other = targets.at[:, 0].set((targets[:, 0] + 1) % cfg.vocab_size)
    moved = model._slice_nll(params, x, other, cfg.n_pred_heads) - means
    assert float(jnp.abs(moved[0])) > 1e-4 and float(jnp.max(jnp.abs(moved[1:]))) == 0.0


def _mixer_inputs(model, params, seq):
    """A layer's weights, a normed stream ``h`` and rope's angles."""
    cfg = model.config
    w = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(9), (2, seq, cfg.dim), jnp.float32)
    half = cfg.head_dim // 2
    freqs = 1.0 / (cfg.rope_theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(seq, dtype=jnp.float32)[None, :, None] * freqs
    return w, h, (jnp.cos(angles), jnp.sin(angles))


@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "kernels"])
@pytest.mark.parametrize("window", [SEQ, 2 * SEQ], ids=["the-sequence", "twice-the-sequence"])
def test_a_window_that_covers_the_sequence_is_causal_softmax_attention(window, kernels):
    """No summary is ever seen: the layer IS causal attention and equals
    ``flash_attention(causal=True)`` on the same q, k, v."""
    cfg, model, params, _ = _setup(window_size=window)
    w, h, rope = _mixer_inputs(model, params, SEQ)
    shape = (2, SEQ, cfg.n_heads, cfg.head_dim)
    q, k = (Llama._apply_rope((h @ w[n]).reshape(shape), *rope) for n in ("wq", "wk"))
    o = flash.flash_attention(q, k, (h @ w["wv"]).reshape(shape), causal=True, block_q=32, block_k=32, interpret=True)
    want = o.reshape(2, SEQ, -1) @ w["wo"]
    np.testing.assert_allclose(model._attention(h, w, rope, kernels), want, atol=2e-5)
    # ... and a shorter window is another function
    short = Eva(eva_debug(window_size=SEQ // 2))
    assert float(jnp.max(jnp.abs(short._attention(h, w, rope, kernels) - want))) > 1e-3


# byte j of a sequence of 64, window 32, chunk 8: a chunk's first and last position, a window's first and last
BOUNDARIES = {"chunk-first": 40, "chunk-last": 47, "window-first": 32, "window-last": 31, "the-first-byte": 0, "the-last-byte": 63}


@pytest.mark.parametrize("where", list(BOUNDARIES))
def test_no_output_before_a_changed_byte_moves(where, path):
    """Causality is exact: a summary holds its chunk's later keys, but no
    query sees a summary of its own window.  Every slice's logits before
    byte ``j`` stay bit for bit; those at ``j`` move, and so do those of the
    NEXT window, which see ``j`` through its chunk's summary alone."""
    cfg, _, params, (tokens, _) = _setup()
    j = BOUNDARIES[where]
    other = tokens.at[:, j].set((tokens[:, j] + 1) % cfg.vocab_size)
    run = _apply_all(path)
    base, changed = run(params, tokens), run(params, other)
    np.testing.assert_array_equal(changed[:, :j], base[:, :j])
    assert float(jnp.min(jnp.max(jnp.abs(changed[:, j] - base[:, j]), axis=(1, 2)))) > 1e-4
    if j < 32:  # the second window's rows see byte j through a summary
        assert float(jnp.min(jnp.max(jnp.abs(changed[:, 32:] - base[:, 32:]), axis=(2, 3)))) > 1e-7


@functools.lru_cache(maxsize=None)
def _apply_all(path):
    """The toy's ``apply_all`` compiled on ``path``, once for the six bytes."""
    _, model, params, (tokens, _) = _setup()
    with on_path(path):
        return jax.jit(model.apply_all).lower(params, tokens).compile()


def test_the_pooling_is_a_softmax_over_a_chunk_and_mu_is_added_after():
    cfg, model, params, _ = _setup()
    k, v = (jax.random.normal(jax.random.PRNGKey(n), (2, SEQ, cfg.n_heads, cfg.head_dim)) for n in (1, 2))
    phi, mu = params["layers"]["phi"][0], params["layers"]["mu"][0]
    chunks = lambda a: a.reshape(2, SEQ // cfg.chunk_size, cfg.chunk_size, cfg.n_heads, cfg.head_dim)  # noqa: E731
    # phi at 0: every position of a chunk weighs the same
    k_pooled, v_pooled = model._pool(k, v, jnp.zeros_like(phi), mu)
    np.testing.assert_allclose(k_pooled, chunks(k).mean(2) + mu, atol=1e-6)
    np.testing.assert_allclose(v_pooled, chunks(v).mean(2), atol=1e-6)  # mu is the keys' alone
    # a large phi picks the position whose key lies most along it
    k_sharp, _ = model._pool(k, v, 1e8 * phi, mu)
    best = jnp.argmax((chunks(k) * phi).sum(-1), axis=2)
    picked = jnp.take_along_axis(chunks(k), best[:, :, None, :, None], axis=2)[:, :, 0]
    np.testing.assert_allclose(k_sharp, picked + mu, atol=1e-4)
    want = ref.pool(k, v, phi, mu, cfg.chunk_size)
    for got, wanted in zip(model._pool(k, v, phi, mu), want):
        np.testing.assert_allclose(got, wanted, atol=1e-6)


def test_parameter_counts_of_the_published_sizes():
    """ISSUE 52's arithmetic: 821.4 M in four layers, 6.5 B in 32."""
    here = Eva(EvaConfig(n_layers=4))
    assert here.num_params() == 821_366_784
    layer = sum(int(np.prod(s.shape[1:])) for s in jax.tree_util.tree_leaves(here._shapes["layers"]))
    assert layer == 4 * 4096**2 + 3 * 4096 * 11_008 + 2 * 4096 + 2 * 32 * 128 == 202_391_552
    assert here._shapes["lm_head"].shape == (4096, 8 * 320) and here._shapes["embed"].shape == (320, 4096)
    assert here._shapes["layers"]["phi"].shape == here._shapes["layers"]["mu"].shape == (4, 32, 128)
    assert Eva(EvaConfig()).num_params() == 32 * layer + 320 * 4096 + 4096 * 2560 + 4096 == 6_488_330_240
    with pytest.raises(ValueError, match="a window holds whole chunks"):
        Eva(eva_debug(window_size=20))


def test_a_bfloat16_model_keeps_a_float32_stream_and_float32_logits(monkeypatch):
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    model = Eva(eva_debug(dtype=jnp.bfloat16))
    params = model.init(jax.random.PRNGKey(3))
    layers = params["layers"]
    assert params["embed"].dtype == layers["wq"].dtype == jnp.bfloat16
    # norms and the pooling's vectors are float32, as every vector here
    assert {layers[n].dtype for n in ("attn_norm", "mlp_norm", "phi", "mu")} == {jnp.dtype(jnp.float32)}
    # phi and mu start normal, clipped to [-1, 1], times the scores' scale; every g at 0
    assert float(jnp.max(jnp.abs(layers["phi"]))) <= model.config.head_dim ** -0.5 + 1e-6
    assert float(jnp.max(jnp.abs(layers["attn_norm"]))) == 0.0
    tokens = jnp.zeros((1, SEQ), jnp.int32)
    assert model._trunk(params, tokens).dtype == jnp.float32
    assert model.apply_all(params, tokens).dtype == jnp.float32
    with pytest.raises(ValueError, match="whole number of chunks"):
        model.apply(params, jnp.zeros((1, SEQ + 4), jnp.int32))


@pytest.mark.parametrize("kernel,count", [("eva_fwd", 1), ("eva_dq", 1), ("eva_dkv", 1), ("flash_fwd", 0)])
def test_what_a_rematerialised_layer_keeps_and_what_it_runs_again(kernel, count):
    """The layers are one scan, whose body is traced once, rematerialised
    but for what the forward kernel made (``flash.KEPT_NAMES``): a second
    ``eva_fwd`` in the body would read 2."""
    text = _gradients_jaxpr()
    assert text.count(f"name={kernel}\n") + text.count(f"name={kernel} ") == count, kernel


@functools.lru_cache(maxsize=None)
def _gradients_jaxpr():
    """Traced once for the four kernels' counts."""
    _, model, params, batch = _setup()
    return gradients_jaxpr(model, params, batch)


# ----------------------------------------------------------------------
# the walk under the second rule of liveness
# ----------------------------------------------------------------------


def _seen(seq, window, chunk, padding=0):
    """[seq, summaries + padding + seq]: which keys a query sees, position by position."""
    i = np.arange(seq)[:, None]
    c, j = np.arange(seq // chunk + padding)[None, :], np.arange(seq)[None, :]
    summaries = (c < seq // chunk) & (c * chunk // window < i // window)
    return np.concatenate([summaries, (j // window == i // window) & (j <= i)], axis=1)


@pytest.mark.parametrize(
    "seq,window,chunk,block_q,block_k",
    [(256, 64, 4, 16, 16), (256, 64, 2, 32, 16), (512, 128, 8, 32, 64), (256, 64, 16, 64, 8), (256, 128, 16, 32, 32)],
)
def test_the_walks_grids_hold_the_live_pairs_alone(seq, window, chunk, block_q, block_k):
    """A block is in the walk exactly if some query of its rows sees some key
    of its columns; no step is of a dead block, but for the one visit that
    writes the zeros of a key block NO row sees."""
    chunks = seq // chunk
    padding = -chunks % block_k
    rule = flash.Pooled(window, window // chunk, chunks + padding)
    nq, nk = seq // block_q, (chunks + padding + seq) // block_k
    live = flash._live_blocks(nq, nk, block_q, block_k, rule)
    by_position = _seen(seq, window, chunk, padding).reshape(nq, block_q, nk, block_k).any(axis=(1, 3))
    np.testing.assert_array_equal(live, by_position)
    rows = flash._walk(live)
    assert rows.steps == int(by_position.sum()) and bool(np.all(by_position[rows.q, rows.k]))
    # a row block's summary blocks come before its token blocks, each ascending
    for r in range(nq):
        assert list(rows.k[rows.q == r]) == sorted(rows.k[rows.q == r])
    steps, tables, _, _ = flash._key_launch(nq, nk, block_q, block_k, 1, rule, True)
    unseen = int((~by_position.any(axis=0)).sum())
    assert steps == (int(by_position.sum()) + unseen,) and len(tables) == 4


def test_the_cells_launches_walk_304_blocks_a_head_and_dkvs_tables_fit():
    """32,768 positions, windows of 2,048, chunks of 16, blocks of 512: 160
    token blocks (the windows' triangles) and 144 summary blocks a head,
    against 2,176 of a full causal walk; every key block is seen by some row,
    and the tables are a thousandth of what SMEM holds."""
    seq, window, chunk, block = 32_768, 2048, 16, 512
    rule = flash.Pooled(window, window // chunk, seq // chunk)
    args = (seq // block, (seq // chunk + seq) // block, block, block)
    live = flash._live_blocks(*args, rule)
    tokens, summaries = live[:, 4:], live[:, :4]
    assert int(tokens.sum()) == 16 * 10 and int(summaries.sum()) == 4 * (0 + 4 * 1 + 4 * 2 + 4 * 3 + 3 * 4)
    assert bool(live.any(axis=0).all()) and bool(live.any(axis=1).all())
    assert int(flash._live_blocks(64, 64, block, block, None).sum()) == 64 * 65 // 2 == 2080
    steps, tables, _, _ = flash._key_launch(*args, 1, rule, True)
    assert steps == (304,) and 4 * 304 * len(tables) < flash._TABLE_BYTES // 100
    # the live PAIRS: ISSUE 52's 65.0 M a head, half of them on summaries
    n_w = seq // window
    pairs = n_w * window * (window + 1) // 2 + window * (window // chunk) * n_w * (n_w - 1) // 2
    assert pairs == 33_570_816 + 31_457_280 == int(_seen(window, window, chunk).sum()) * n_w + 31_457_280


@pytest.mark.parametrize(
    "why,kwargs",
    [
        ("whole windows", dict(seq=48, window=32)),
        ("whole blocks", dict(seq=64, window=32, block_q=24)),
        ("as many a window", dict(seq=64, window=32, chunks=9)),
        ("share KV heads", dict(seq=64, window=32, pooled_heads=1)),
        ("whole number of positions", dict(seq=64, window=True)),
    ],
)
def test_the_entry_refuses_what_the_walk_cannot_hold(why, kwargs):
    seq, window = kwargs["seq"], kwargs["window"]
    q = jnp.zeros((1, seq, 2, 16))
    pooled = jnp.zeros((1, kwargs.get("chunks", seq // 8), kwargs.get("pooled_heads", 2), 16))
    with pytest.raises(ValueError, match=why):
        flash.eva_attention(q, q, q, pooled, pooled, window=window, block_q=kwargs.get("block_q", 16), block_k=16, interpret=True)
