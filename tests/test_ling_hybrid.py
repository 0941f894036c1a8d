"""``models/ling_hybrid.py`` against the plain float32 reference
(``ftbench/architectures/ling_hybrid_reference.py``) at toy size on the
CPU: 6 layers, ONE period of the published pattern (dense+KDA, 4 KDA expert
layers, MLA: every kind of layer, each run compiled once; the preset's
seventh layer, a KDA expert layer again, is a fourth run to compile and no
kind the six lack), the multi-token-prediction module at weight 0.3, a
selection bias that is not zero.

Tolerances, with their reasons.  Both sides are float32 with matrix products
at ``highest``; they differ in the ORDER of float32 additions: the chunked
delta rule against the per-token recurrence, sorted rows against masked
experts, a scan over stacked layers against a loop.  Through the preset's 7
layers (unit-length q and k, RMS norms, decays down to exp(-5) a token) that read
3e-4 on logits of up to 5 (6e-5 of their size) and 1e-4 of a leaf's largest
gradient, 2.5e-3 for ``a_log`` whose whole gradient is 1e-2: limits of 1e-3
absolute on logits, 1e-5 on the losses and 5e-3 of a leaf's largest
gradient (+1e-5).  bfloat16 anywhere reads 1e-1 on the logits, a choice of
experts that differs reads above 1e-1, a dropped term (the shared expert,
a gate, the MTP loss) at least 1e-2: all fail.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ftbench.architectures import ling_hybrid_reference as ref
from torchft_tpu.models.ling_hybrid import KERNEL_PATH, LingHybrid, LingHybridConfig, ling_debug

from tests._once import once_a_run
from tests._toys import program_side


def reference_config(c: LingHybridConfig) -> dict:
    """The configuration file's keys for a ``LingHybridConfig``."""
    return dict(
        num_hidden_layers=c.n_layers, layer_group_size=c.layer_group_size,
        first_k_dense_replace=c.first_k_dense, num_attention_heads=c.n_heads, head_dim=c.head_dim,
        kda_lower_bound=c.kda_lower_bound, rms_norm_eps=c.norm_eps,
        qk_nope_head_dim=c.qk_nope_head_dim, qk_rope_head_dim=c.qk_rope_head_dim,
        v_head_dim=c.v_head_dim, kv_lora_rank=c.kv_lora_rank, rope_theta=c.rope_theta,
        n_group=c.n_group, topk_group=c.topk_group, num_experts_per_tok=c.top_k,
        norm_topk_prob=c.norm_topk_prob, routed_scaling_factor=c.routed_scaling_factor,
        experts_held=list(c.experts_held),
        expert_swiglu_limit_list=list(c.expert_swiglu_limits) or [0] * c.n_layers,
        share_expert_swiglu_limit_list=list(c.shared_swiglu_limits) or [0] * c.n_layers,
        num_nextn_predict_layers=c.n_mtp, mtp_loss_scaling_factor=c.mtp_loss_weight,
        assumed=dict(balance_loss_weight=c.balance_loss_weight),
    )


@functools.lru_cache(maxsize=None)
def _params():
    """The toy's parameters, made once a run of the tests (``init`` runs
    operation by operation, 15 s of small compiles in every process that
    makes them, and again inside another matmul precision)."""
    model = LingHybrid(ling_debug(n_layers=6, n_mtp=1, mtp_loss_weight=0.3))

    def stirred(key):
        # a selection bias that is not zero: at init it is, and then it routes nothing
        return jax.tree_util.tree_map(
            lambda p, is_state: 0.05 * jax.random.normal(jax.random.PRNGKey(3), p.shape) if is_state else p,
            model.init(key), model.state_mask(),
        )

    def make():  # ONE program: ``init`` run operation by operation is 15 s of small compiles
        return jax.jit(stirred)(jax.random.PRNGKey(0))

    return once_a_run("ling_hybrid-params", make)


def _setup():
    """(config, a model of its own, the parameters, a batch): the model is
    the caller's alone, since what it traces depends on ``TORCHFT_FLASH``."""
    cfg = ling_debug(n_layers=6, n_mtp=1, mtp_loss_weight=0.3)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 64)).astype(np.int32)
    return cfg, LingHybrid(cfg), _params(), (tokens, np.roll(tokens, -1, axis=1))


@pytest.fixture(scope="module")
def reference_side():
    """The reference's logits, losses and gradients, computed once a run for
    both of the program's paths."""
    cfg, _, params, batch = _setup()
    rc = reference_config(cfg)

    def make():
        with jax.default_matmul_precision("highest"):
            # ONE program: two compiled the forward pass twice
            return jax.jit(lambda p: (ref.forward(p, *batch, rc, logits=True), *jax.value_and_grad(lambda p: ref.loss(p, batch, rc))(p)))(params)

    return once_a_run("ling_hybrid-reference", make)


@pytest.fixture(params=["plain", "kernels"])
def path(request, monkeypatch):
    """Both ways through the mixers: plain ``jax.numpy`` (what the CPU
    takes) and the Pallas kernels in interpret mode (``TORCHFT_FLASH=1``)."""
    if request.param == "kernels":
        monkeypatch.setenv("TORCHFT_FLASH", "1")
    else:
        monkeypatch.delenv("TORCHFT_FLASH", raising=False)
    return request.param


# The toy and the reference's side are made once a run, 20 s each on a loaded
# worker: each by a test of its own, so that the comparison below, which
# compiles the program, is not three costs in one test.


def test_every_selection_bias_of_the_toy_routes():
    _, model, params, _ = _setup()
    mask = jax.tree_util.tree_leaves(model.state_mask())
    biases = [p for p, is_state in zip(jax.tree_util.tree_leaves(params), mask) if is_state]
    assert len(biases) == 3  # a stacked run of expert layers a leaf (KDA's four, MLA's one), and the MTP module's layer
    assert all(np.abs(np.asarray(b)).reshape(-1, b.shape[-1]).max(axis=-1).min() > 0 for b in biases)


def test_the_references_every_term_is_there_to_be_missed(reference_side):
    want, want_objective, want_grads = reference_side
    nll, mtp_nll = float(np.mean(want["nll"])), float(np.mean(want["mtp_nll"]))
    assert 0.3 * mtp_nll > 1.0 and float(want["balance"]) > 1e-4
    assert float(want_objective) > nll + 0.3 * mtp_nll  # and the balance loss
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree_util.tree_leaves(want_grads))


def test_logits_loss_and_every_gradient_agree_with_the_reference(path, reference_side):
    cfg, model, params, batch = _setup()
    want, want_objective, want_grads = reference_side
    with jax.default_matmul_precision("highest"):
        logits, loss, ((objective, (signal, _)), grads) = program_side(model, params, batch, path)
        assert (model.attention_path == KERNEL_PATH) == (path == "kernels")
        assert path == "kernels" or model.attention_path.startswith("plain: ")
        assert float(jnp.max(jnp.abs(logits - want["logits"]))) < 1e-3
        # the loss: cross-entropy and the MTP term at 0.3; the objective adds the balance loss
        want_loss = float(jnp.mean(want["nll"]) + 0.3 * jnp.mean(want["mtp_nll"]))
        assert float(loss) == pytest.approx(want_loss, abs=1e-5)
        assert 0.3 * float(jnp.mean(want["mtp_nll"])) > 1.0  # the MTP term is there to be missed
        assert float(objective) == pytest.approx(float(want_objective), abs=1e-5)
        assert float(want["balance"]) > 1e-4
    # the signal: every router's load, in the order of the state leaves
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(s).reshape(-1, cfg.num_experts) for s in signal]),
        np.stack([np.asarray(x) for x in want["loads"]]),
    )
    for (where, got), wanted in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(want_grads)
    ):
        limit = 5e-3 * float(jnp.max(jnp.abs(wanted))) + 1e-5
        assert float(jnp.max(jnp.abs(got - wanted))) < limit, jax.tree_util.keystr(where)
    # no gradient moves a bias
    for g, is_state in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(model.state_mask())):
        assert not is_state or float(jnp.max(jnp.abs(g))) == 0.0


def test_pattern_comes_from_the_two_declared_numbers():
    cfg = LingHybridConfig()  # the published sizes
    kinds = cfg.kinds()
    assert [i for i, k in enumerate(kinds) if k[0] == "mla"] == [5, 11, 17, 23, 29, 35, 41]
    assert [k[1] for k in kinds[:3]] == ["dense", "dense", "moe"]
    assert sum(depth for _, depth in cfg.groups()) == 42
    assert [(k[0], k[1], d) for k, d in ling_debug().groups()] == [
        ("kda", "dense", 1), ("kda", "moe", 4), ("mla", "moe", 1), ("kda", "moe", 1)
    ]
    # a clamp that differs cuts a run of layers in two
    clamped = ling_debug(expert_swiglu_limits=(0, 0, 0, 4, 4, 4, 4))
    assert [(k[0], k[2], d) for k, d in clamped.groups()] == [
        ("kda", 0.0, 1), ("kda", 0.0, 2), ("kda", 4.0, 2), ("mla", 4.0, 1), ("kda", 4.0, 1)
    ]


def test_parameter_counts_of_the_published_sizes():
    """ISSUE 29's arithmetic, from the shapes ``init`` would make."""
    count = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(tree))  # noqa: E731
    model = LingHybrid(LingHybridConfig(experts_held=(0, 16), vocab_size=19_648, n_layers=7, first_k_dense=1))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert count(shapes) == model.num_params() == 1_105_151_936
    assert count(shapes["groups"][0]["mixer"]) == 52_646_048  # a KDA mixer (layer 0, stacked once)
    assert count(shapes["groups"][2]["mixer"]) == 31_965_696  # the MLA mixer (one layer, stacked once)


def test_bias_goes_up_for_the_idle_and_down_for_the_busy():
    model = LingHybrid(ling_debug())
    bias = [jnp.zeros((2, 16)), jnp.zeros((16,))]
    load = [jnp.tile(jnp.arange(16.0), (2, 1)), jnp.full((16,), 3.0).at[0].set(5.0)]
    new = model.advance_state(bias, load)
    np.testing.assert_allclose(np.asarray(new[0][0]), 1e-3 * np.sign(7.5 - np.arange(16.0)))
    assert float(new[1][0]) == pytest.approx(-1e-3) and float(new[1][1]) == pytest.approx(1e-3)
    stats = model.summary_stats(np.asarray(model.moe.route_summary(load, 64)))
    assert stats["rows_here"] == [22.0, 22.0, 12.0] and stats["load_max"] == [7.0, 7.0, 3.0]
    assert stats["buffer_rows"] == [256.0] * 3  # 64 tokens, 4 each: the buffer is every pair, one pass
    # 2,048 tokens: a uniform router sends 2,048 rows here, the buffer is 2,560: two passes, two, one
    stats = model.summary_stats(np.asarray(model.moe.route_summary([200 * x for x in load], 2048)))
    assert stats["rows_here"] == [4400.0, 4400.0, 2400.0] and stats["buffer_rows"] == [5120.0, 5120.0, 2560.0]
