"""``models/latent_moe.py`` against the plain float32 reference
(``ftbench/architectures/latent_moe_reference.py``, which imports nothing of
the program) at toy widths: a dense layer and three of experts, latent
attention with a query latent in every one, the multi-token-prediction module
ON at a weight of 0.3, selection biases that are not zero.  Every position's
next-token cross-entropy, the logits, the module's loss of every position,
the objective, the balance loss, the loads and every leaf's gradient, the
shared embedding and head among them; the sum of the experts' shares; the
float32 stream; what a rematerialised layer keeps.  Float32, seeded weights,
the CPU; the kernels in interpret mode where a case says so.

Tolerances, with their reasons.  Both sides are float32 with matrix products
at ``highest``; they differ in the ORDER of float32 additions: sorted rows
against masked experts, flash's blocks against one softmax a row, a scan over
stacked layers against a loop.  Through 5 layers that read 2e-5 on logits of
up to 4, 3e-6 on a position's cross-entropy and 1e-4 of a leaf's largest
gradient: limits of 3e-4 on the logits, 5e-5 on a position's cross-entropy
(either head), 2e-5 on the mean losses and the objective and 1e-3 of a leaf's
largest gradient (+1e-6).  bfloat16 anywhere (the stream, a product, the
logits) reads 2e-2 on a position's cross-entropy, a choice of experts that
differs above 1e-1, a dropped term (a latent's norm, rope, the shared expert,
the module's loss, a wrapped last position) at least 1e-3 on the objective:
all fail."""

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ftbench.architectures import latent_moe_reference as ref
from torchft_tpu.models.latent_moe import KERNEL_PATH, LatentMoE, LatentMoEConfig, latent_moe_debug
from torchft_tpu.parallel.moe import RoutedExperts, RoutedExpertsConfig

from tests._once import once_a_run
from tests._toys import gradients_jaxpr, program_side

SEQ = 64
MTP_WEIGHT = 0.3  # large enough that a dropped or wrapped module shows in the objective


def reference_config(c: LatentMoEConfig) -> dict:
    """The configuration file's keys for a ``LatentMoEConfig``."""
    return dict(
        num_hidden_layers=c.n_layers, first_k_dense_replace=c.first_k_dense, num_attention_heads=c.n_heads,
        qk_nope_head_dim=c.qk_nope_head_dim, qk_rope_head_dim=c.qk_rope_head_dim, v_head_dim=c.v_head_dim,
        kv_lora_rank=c.kv_lora_rank, q_lora_rank=c.q_lora_rank, rope_theta=c.rope_theta, rms_norm_eps=c.norm_eps,
        num_experts_per_tok=c.top_k, norm_topk_prob=c.norm_topk_prob, routed_scaling_factor=c.routed_scaling_factor,
        experts_held=list(c.experts_held), num_nextn_predict_layers=c.n_mtp,
        assumed=dict(balance_loss_weight=c.balance_loss_weight, mtp_loss_weight=c.mtp_loss_weight),
    )


@functools.lru_cache(maxsize=None)
def _params(**over):
    """The toy's parameters, made once a run of the tests (``init`` runs
    operation by operation, 10-20 s of small compiles in every process that
    makes them)."""
    model = LatentMoE(latent_moe_debug(**{"mtp_loss_weight": MTP_WEIGHT, **over}))

    def stir(path, p, is_state):
        """What ``init`` leaves at a constant gets values of its own: a bias
        of zero routes nothing, and a gradient is only tested where the
        leaf's value matters."""
        names = [getattr(k, "key", "") for k in path]
        # the same noise in every process: ``hash`` of a string is salted anew in each
        noise = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(3), zlib.crc32("/".join(map(str, names)).encode()) % 997), p.shape)
        if is_state:
            return 0.05 * noise
        return p + 0.1 * noise if names[-1].endswith("norm") else p

    def make():  # ONE program: ``init`` run operation by operation is 10-20 s of small compiles
        return jax.jit(lambda key: jax.tree_util.tree_map_with_path(stir, model.init(key), model.state_mask()))(jax.random.PRNGKey(0))

    return once_a_run(f"latent_moe-params-{sorted(over.items())}", make)


def _setup(**over):
    """(config, a model of its own, the parameters, a batch): the model is
    the caller's alone, since what it traces depends on ``TORCHFT_FLASH``."""
    cfg = latent_moe_debug(**{"mtp_loss_weight": MTP_WEIGHT, **over})
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, SEQ)).astype(np.int32)
    return cfg, LatentMoE(cfg), _params(**over), (jnp.asarray(tokens), jnp.asarray(np.roll(tokens, -1, axis=1)))


@pytest.fixture(scope="module")
def reference_side():
    """The reference's losses, logits and gradients, computed once a run for
    both of the program's paths."""
    cfg, _, params, batch = _setup()
    rc = reference_config(cfg)

    def make():
        # ONE program: two compiled the forward pass twice
        return jax.jit(lambda p: (ref.forward(p, *batch, rc, logits=True), *jax.value_and_grad(lambda p: ref.loss(p, batch, rc))(p)))(params)

    return once_a_run("latent_moe-reference", make)


@functools.lru_cache(maxsize=None)
def programs_side(path):
    """(model, logits, loss, ((objective, (signal, summary)), gradients)) of
    the toy on ``path``, computed once a process."""
    _, model, params, batch = _setup()
    return (model, *program_side(model, params, batch, path))


@pytest.fixture(params=["plain", "kernels"])
def path(request, monkeypatch):
    """Both ways through the mixers: plain ``jax.numpy`` (what the CPU takes)
    and the flash kernels in interpret mode (``TORCHFT_FLASH=1``)."""
    monkeypatch.setenv("TORCHFT_FLASH", "1" if request.param == "kernels" else "0")
    return request.param


def _leaves(tree):
    return {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _token_nll(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def test_every_positions_cross_entropy_and_the_tie_of_loss_to_apply(path, reference_side):
    """``apply``'s cross-entropy of every position is the reference's, and
    ``loss`` IS its mean with the module ON: the tie ``ftbench/harness.py``
    holds (``loss_tie`` <= 2e-5), which a loss that added the module's term
    would break by 0.3 x 6.2."""
    cfg, _, params, batch = _setup()
    want = reference_side[0]
    model, logits, loss, _ = programs_side(path)
    assert model.attention_path == (KERNEL_PATH if path == "kernels" else "plain: TORCHFT_FLASH=0")
    assert logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want["logits"], atol=3e-4)
    nll = _token_nll(logits, batch[1])
    np.testing.assert_allclose(nll, want["nll"], atol=5e-5)
    loss = float(loss)
    assert cfg.n_mtp == 1 and "mtp" in params
    assert loss == pytest.approx(float(jnp.mean(nll)), abs=2e-6)
    assert loss == pytest.approx(float(jnp.mean(want["nll"])), abs=2e-5)


def test_objective_module_balance_loads_and_every_gradient_agree_with_the_reference(path, reference_side):
    cfg, _, params, batch = _setup()
    want, want_total, want_grads = reference_side
    model, _, loss, ((objective, (signal, summary)), grads) = programs_side(path)
    assert model.attention_path == (KERNEL_PATH if path == "kernels" else "plain: TORCHFT_FLASH=0")
    # the objective: the cross-entropy, the module at its weight over positions 0..S-2, the balance loss
    mtp = float(jnp.mean(want["mtp_nll"]))
    assert want["mtp_nll"].shape == (2, SEQ - 1) and MTP_WEIGHT * mtp > 1.0  # there to be missed
    assert float(want["balance"]) > 1e-4
    assert float(want_total) == pytest.approx(float(jnp.mean(want["nll"])) + MTP_WEIGHT * mtp + float(want["balance"]), abs=1e-6)
    assert float(objective) == pytest.approx(float(want_total), abs=2e-5)
    assert float(objective) - float(loss) == pytest.approx(
        MTP_WEIGHT * mtp + float(want["balance"]), abs=2e-5
    )
    # the signal: every router's load, in the order of the state leaves, the module's last
    loads = np.concatenate([np.asarray(s).reshape(-1, cfg.num_experts) for s in signal])
    np.testing.assert_array_equal(loads, np.stack([np.asarray(x) for x in want["loads"]]))
    assert [np.asarray(s).shape for s in signal] == [(3, 16), (16,)]
    # the summary: a row a router and then the module's mean loss
    stats = model.summary_stats(np.asarray(summary))
    first, held = cfg.experts_held
    assert stats["rows_here"] == [float(load[first : first + held].sum()) for load in want["loads"]]
    assert stats["load_max"] == [float(load[first : first + held].max()) for load in want["loads"]]
    assert stats["buffer_rows"] == [float(batch[0].size * cfg.top_k)] * 4  # toy: the buffer is every pair, one pass
    assert stats["mtp_nll"] == pytest.approx(mtp, abs=2e-5)
    assert sorted(stats) == ["buffer_rows", "load_max", "load_mean", "mtp_nll", "rows_here"]
    # every leaf's gradient, the shared embedding and head (two heads' gradients each) among them
    got, wanted = _leaves(grads), _leaves(want_grads)
    assert got.keys() == wanted.keys() and "['embed']" in got and "['lm_head']" in got and "['mtp']['proj']" in got
    for name in got:
        if name.endswith("['bias']"):
            assert float(jnp.max(jnp.abs(got[name]))) == 0.0, name  # no gradient moves a selection bias
            continue
        scale = float(jnp.max(jnp.abs(wanted[name])))
        assert scale > 1e-7, name  # every leaf learns
        np.testing.assert_allclose(got[name], wanted[name], atol=1e-3 * scale + 1e-6, err_msg=name)


def test_the_modules_loss_of_every_position_and_the_last_position_left_out(monkeypatch, reference_side):
    """The module's cross-entropy position by position (what the chip check
    ``ftbench/tests/mtp_forward_check.py`` compares), and that the LAST
    position, which has no token after next, is left out of the mean and not
    wrapped around to the sequence's first target."""
    from torchft_tpu.models.latent import mtp_token_nll

    monkeypatch.setenv("TORCHFT_FLASH", "0")
    cfg, model, params, batch = _setup()
    want = reference_side[0]
    kept = jax.jit(model.mtp_token_nll)(params, batch)
    assert kept.shape == (2, SEQ - 1)
    np.testing.assert_allclose(kept, want["mtp_nll"], atol=5e-5)
    # the shared definition gives every position, the last one's label wrapped around
    x, _, _, kernels = model._trunk(params, batch[0])
    nll, load, _ = mtp_token_nll(
        params["mtp"], params["embed"], x, batch[1], layer=lambda z, w: model._block(z, w, "moe", kernels),
        head_nll=lambda z, norm, labels: model._token_nll(params["lm_head"], z, norm, labels),
        norm_eps=cfg.norm_eps, dtype=cfg.dtype,
    )
    assert nll.shape == (2, SEQ)
    np.testing.assert_allclose(nll[:, :-1], kept, atol=1e-6)
    np.testing.assert_array_equal(load, want["loads"][-1])
    (_, (_, summary)), _ = programs_side("plain")[3]  # ``objective``, as the step's program has it
    reported = model.summary_stats(np.asarray(summary))["mtp_nll"]
    assert reported == pytest.approx(float(jnp.mean(nll[:, :-1])), abs=1e-6)
    assert abs(float(jnp.mean(nll)) - reported) > 1e-3  # the wrapped mean is another number


def test_a_model_without_the_module_has_no_such_leaves_and_no_such_field(monkeypatch):
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    cfg, model, params, batch = _setup(n_mtp=0)
    assert "mtp" not in params
    rc = reference_config(cfg)
    objective, (signal, summary) = jax.jit(model.objective)(params, batch)
    want = jax.jit(lambda p: ref.forward(p, *batch, rc))(params)
    assert want["mtp_nll"] is None and len(want["loads"]) == 3
    assert float(objective) == pytest.approx(float(jax.jit(lambda p: ref.loss(p, batch, rc))(params)), abs=2e-5)
    assert [np.asarray(s).shape for s in signal] == [(3, 16)]
    assert sorted(model.summary_stats(np.asarray(summary))) == ["buffer_rows", "load_max", "load_mean", "rows_here"]
    with pytest.raises(ValueError, match="one prediction module at most"):
        LatentMoE(latent_moe_debug(n_mtp=2))


def test_layers_are_stacked_by_kind_and_the_mixer_has_a_query_latent_and_no_gate():
    cfg = LatentMoEConfig()  # the published sizes
    assert cfg.groups() == [("dense", 1), ("moe", 39)]
    cut = latent_moe_debug()
    assert cut.groups() == [("dense", 1), ("moe", 3)]
    params = LatentMoE(cut).init(jax.random.PRNGKey(0))
    dense, moe = params["groups"]
    assert dense["mixer"]["w_q_a"].shape == (1, 64, 48) and moe["mixer"]["w_q_b"].shape == (3, 48, 2 * 48)
    assert sorted(moe["mixer"]) == ["kv_norm", "q_norm", "w_kv_a", "w_kv_b", "w_q_a", "w_q_b", "wo"]  # no wq, no w_gate
    assert moe["mixer"]["w_kv_a"].shape == (3, 64, 32 + 16) and moe["mixer"]["w_kv_b"].shape == (3, 32, 2 * (32 + 32))
    assert "router" not in dense["ffn"] and moe["ffn"]["router"].shape == (3, 64, 16)
    # the module: a projection of the pair, one expert layer of its own, its own norms; no embedding or head of its own
    assert sorted(params["mtp"]) == ["enorm", "final_norm", "hnorm", "layer", "proj"]
    assert params["mtp"]["proj"].shape == (128, 64) and params["mtp"]["layer"]["ffn"]["bias"].shape == (16,)
    # stacked runs are the same layers: the second of three layers is not the first
    assert float(jnp.max(jnp.abs(moe["mixer"]["w_q_a"][0] - moe["mixer"]["w_q_a"][1]))) > 0


def test_parameter_counts_of_the_published_sizes():
    """ISSUE 49's arithmetic: 894.6 M on one chip's share of seven layers and the module."""
    here = LatentMoE(LatentMoEConfig(n_layers=7, experts_held=(0, 16), vocab_size=16_160))
    assert here.num_params() == 894_625_536
    count = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(tree))  # noqa: E731
    shapes = here._shapes
    attention = 2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048  # 3.15 + 9.44 + 1.18 + 4.19 + 8.39 M
    assert attention == 26_345_472 and count(shapes["groups"][0]["mixer"]) == attention + 1536 + 512  # and the latents' norms
    assert count(shapes["groups"][0]) == attention + 2048 + 3 * 2048 * 7168 + 2 * 2048  # layer 0: 70.4 M
    expert_layer = attention + 2048 + 17 * 3 * 2048 * 768 + 2048 * 256 + 256 + 2 * 2048  # 16 held and the shared one, router, bias
    assert count(shapes["groups"][1]) == 6 * expert_layer and expert_layer == 107_092_224
    assert count(shapes["mtp"]) == expert_layer + 2 * 2048 * 2048 + 3 * 2048  # the module: 115.5 M
    assert count(shapes["embed"]) == count(shapes["lm_head"]) == 16_160 * 2048


RC = dict(num_experts_per_tok=4, norm_topk_prob=True, routed_scaling_factor=2.5, assumed=dict(balance_loss_weight=1e-4))


def _experts(held):
    return RoutedExperts(
        RoutedExpertsConfig(
            dim=32, expert_hidden=24, num_experts=32, experts_held=held, top_k=4, routed_scaling_factor=2.5,
            shared_hidden=24, balance_loss_weight=1e-4, dtype=jnp.float32,
        )
    )


def test_sixteen_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The guide's share test: what the sixteen chips that share a layer's
    experts each compute (two of 32 a chip here), the shared expert (which
    every chip computes alike) counted once, adds up to the uncut
    reference's whole layer."""
    w = _experts((0, 32)).init(jax.random.PRNGKey(4))
    w["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(7), (32,))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, uncut_load, uncut_balance = ref.moe_layer(x, w, RC, (0, 32))
        shared_part = ref.swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"])
    total, rows = jnp.zeros_like(x), 0.0
    for share in range(16):
        first = 2 * share
        mine = dict(w, **{k: w[k][first : first + 2] for k in ("w_gate", "w_up", "w_down")})
        out, load, balance = _experts((first, 2)).apply(mine, x)
        np.testing.assert_array_equal(load, uncut_load)  # every chip routes over all 32 alike
        assert float(balance) == pytest.approx(float(uncut_balance), abs=1e-8)
        total, rows = total + out, rows + float(load[first : first + 2].sum())
    np.testing.assert_allclose(total - 15 * shared_part, uncut, atol=3e-5)
    assert rows == 48 * 4  # every (token, choice) pair landed on exactly one share


def test_a_bfloat16_model_keeps_a_float32_stream_routes_on_it_and_never_rounds_a_logit(monkeypatch):
    """The residual stream is float32 whatever the matrices' dtype, the
    module's stream too, every router reads its float32 norm (PERF.md section
    6, PR 33), and a logit is the product's float32 sum."""
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    model = LatentMoE(latent_moe_debug(dtype=jnp.bfloat16))
    params = model.init(jax.random.PRNGKey(3))
    assert params["embed"].dtype == jnp.bfloat16 and params["groups"][1]["ffn"]["router"].dtype == jnp.float32
    seen, streams = [], []
    real, block = model.moe.apply, model._block
    monkeypatch.setattr(model.moe, "apply", lambda w, x, *a: seen.append(x.dtype) or real(w, x, *a))
    monkeypatch.setattr(model, "_block", lambda x, *a: streams.append(x.dtype) or block(x, *a))
    tokens = jnp.zeros((1, SEQ), jnp.int32)
    jax.jit(model.objective)(params, (tokens, tokens))
    # a stacked run is traced once: the dense layer, the run of experts, the module's layer
    assert streams == [jnp.float32] * 3 and seen == [jnp.float32] * 2
    logits = jax.jit(model.apply)(params, tokens)
    assert logits.dtype == jnp.float32
    # a float32 sum of bfloat16 products holds more than bfloat16's eight bits
    assert float(jnp.max(jnp.abs(logits - logits.astype(jnp.bfloat16).astype(jnp.float32)))) > 0


@pytest.mark.parametrize("kernel,count", [("flash_fwd", 3), ("flash_dq", 3), ("flash_dkv", 3)])
def test_what_a_rematerialised_layer_keeps_and_what_it_runs_again(kernel, count):
    """Every layer is rematerialised, the module's too, and every one keeps
    what flash made (``flash.KEPT_NAMES``: ``o`` and one number a row, so
    that all eight fit at the published widths), the stacked run of expert
    layers and the two single layers (the dense one, the module's) alike:
    ``flash_fwd`` stands once in each of the three traced bodies, where the
    single layers' second run read 1 + 2 + 2 = 5; the backward kernels once a
    body."""
    text = _gradients_jaxpr()
    assert text.count(f"name={kernel}\n") + text.count(f"name={kernel} ") == count, kernel


@functools.lru_cache(maxsize=None)
def _gradients_jaxpr():
    """Traced once for the three kernels' counts."""
    _, model, params, batch = _setup()
    return gradients_jaxpr(model, params, batch)
