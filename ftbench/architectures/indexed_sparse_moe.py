"""The architecture ``indexed_sparse_moe``:
``torchft_tpu.models.indexed_sparse_moe.IndexedSparseMoE`` (Keye-VL-2.0-30B-A3B's
language model, ``model_type`` ``KeyeVL2``: grouped-query attention over the
2,048 keys a learned index picks for every query, the index's own loss, and
128-way softmax routing over the experts this chip holds; no vision tower).

The benchmark's adapter, never a second implementation: the model is the
program's, the plain reference is ``indexed_sparse_moe_reference.py`` beside
this file (it imports nothing of the program), and the counting of
parameters, operations and bytes is ``dsa_flops`` below, ``flops`` at the end of the file,
which ``step_mfu_pct`` and ``moe_gmm_roofline`` find through the cell's
architecture and ``dsa_index_roofline`` and ``dsa_attn_roofline`` call.  ``ftbench/README.md``, "An architecture", says
what the harness asks of a file like this one.

``model.loss`` is the next-token cross-entropy, which is what
``reference_agrees`` ties to ``model.apply``; a training step differentiates
``model.objective`` (that loss, the index's loss and the routers' balance
loss), and ``tests/test_indexed_sparse_moe.py`` holds all three, and every
gradient, to the reference.
"""

from __future__ import annotations

from typing import Any, Dict

from ftbench.architectures import indexed_sparse_moe_reference as reference

# the value ``model.attention_path`` may have on the chip: every layer by the
# ``dsa_*`` kernels and the experts by the grouped kernel; a plain path
# (dense [S, S] arrays) fails the run
KERNEL_PATHS = {"dsa"}

# what ``--rehearse`` lays over the configuration on the CPU: two layers at
# toy widths, 16 keys a query of up to 128
TOY = dict(
    config=dict(
        hidden_size=64,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        rope_scaling=dict(mrope_section=[2, 2, 4], rope_type="default", type="default"),
        sa_config=dict(
            indexer_head_dim=8, indexer_num_heads=2, indexer_num_kv_heads=1, kv_chunk_size=32,
            q_chunk_size=32, topk=16,
        ),
        moe_intermediate_size=32,
        router_experts=8,
        num_experts=4,
        num_local_experts=4,
        experts_held=[2, 4],
        num_experts_per_tok=2,
        num_hidden_layers=2,
        vocab_size=512,
        torch_dtype="float32",
    ),
    seq_len=128,
)

# ``reference_agrees`` (README.md, "How `correct` is decided"): the program's
# differences from the float32 reference have to stay COARSE_RATIO_K times
# under those of the same program on the float8_e4m3fn copy of its weights.
# Read on the chip at 16,384 positions and the published widths (PERF.md
# section 6, PR 33; ``tests/calibrate_forward_check.py --workload
# keye2-ws1-seq16k`` over three seeds and the cell's own runs, every one
# another seed: thirteen seeds in all): the sound program's ratio read
# 5.48 to 6.25; the control, the same program on an int8 copy with a
# scale a channel, read 1.99 to 2.01, the plain reference on that copy 1.99
# to 2.03 (three seeds each), and the e4m3 copy itself reads 1.  K = 3.4 was
# fixed from the first four readings (the geometric mean of 2.03 and 5.66)
# BEFORE the nine runs that then all met it; with their lowest, 5.48, it
# keeps 1.61 times of room on the sound side and 1.67 on the control's;
# ``llama`` has 3.05 and 1.28 times, ``ling_hybrid`` 1.37 and 1.42.
# The program's first form read 2.79 to 3.03 against a control of 2.06: its
# residual stream was bfloat16, and twenty sums a token at unit scale, each
# rounded, were a third of the e4m3 copy's whole error.  The stream is
# float32 now, and the router reads its float32 norm (which 8 of 128
# experts a token takes is a step function of what the router reads: in
# bfloat16 that alone held the ratio at 4.5).  What is left, 0.010 of a nat
# a token, is again mostly step functions of bfloat16 operands: the keys
# the index picks (99.77 % of them the reference's:
# ``scripts/indexed_selection_agreement.py``) and the experts chosen
# downstream of a layer output that differs.  K belongs to the ``init`` that
# ships and is read again with it (with every matrix at 1 / sqrt(fan_in),
# where attention weighs more in a logit, the first form read 3.75 to 5.73
# against 2.55).
READ_SOUND_LOW, READ_SOUND_HIGH, READ_CONTROL_HIGH = 5.48, 6.25, 2.03
COARSE_RATIO_K = 3.4


def model_config(config: Dict[str, Any]) -> Any:
    import jax.numpy as jnp

    from torchft_tpu.models.indexed_sparse_moe import IndexedSparseMoEConfig

    sa, assumed = config["sa_config"], config["assumed"]
    return IndexedSparseMoEConfig(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        mrope_section=tuple(config["rope_scaling"]["mrope_section"]),
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"],
        index_loss_weight=assumed["index_loss_weight"],
        num_experts=config["router_experts"],
        experts_held=tuple(config["experts_held"]),
        top_k=config["num_experts_per_tok"],
        expert_hidden=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        balance_loss_weight=assumed["balance_loss_weight"],
        norm_eps=config["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["torch_dtype"]],
    )


def model(config: Dict[str, Any]) -> Any:
    from torchft_tpu.models.indexed_sparse_moe import IndexedSparseMoE

    if config["experts_held"][1] != config["num_experts"] or config["num_local_experts"] != config["num_experts"]:
        raise ValueError("num_experts and num_local_experts count the experts held: experts_held = [first, num_experts]")
    if config["sa_config"]["indexer_num_kv_heads"] != 1:
        raise ValueError("the index has one key head")
    return IndexedSparseMoE(model_config(config))


def shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes ``dsa_flops`` counts from, and what the readers find under
    ``sources["shapes"]``."""
    sa = config["sa_config"]
    return dict(
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"],
        expert_hidden=config["moe_intermediate_size"],
        router_experts=config["router_experts"],
        experts_held=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        vocab_size=config["vocab_size"],
    )


def token_nll(host_params: Any, tokens: Any, targets: Any, config: Dict[str, Any]) -> Any:
    return reference.token_nll(host_params, tokens, targets, config)


def num_params(config: Dict[str, Any]) -> int:
    return model(config).num_params()


def vocab(config: Dict[str, Any]) -> int:
    """The batch's token ids are drawn below it: the slice of the vocabulary held."""
    return config["vocab_size"]


class dsa_flops:
    """Operations and bytes from ``shapes(config)``.  Everything counted is
    what the mathematics NEEDS: the index scores every causal pair once, the
    attention and the index's loss touch the PICKED pairs alone (not the
    blocks a masked kernel walks), nothing recomputed is credited; so a
    share of a peak made from it can only read low."""

    @staticmethod
    def is_mine(s: Dict[str, Any]) -> bool:
        """Whether a cell's shapes are this architecture's."""
        return "index_topk" in (s or {})

    @staticmethod
    def causal_pairs(seq: int) -> float:
        return seq * (seq + 1) / 2.0

    @staticmethod
    def picked_pairs(s: Dict[str, Any], seq: int) -> float:
        """``sum_t min(t + 1, topk)`` of one sequence."""
        k = min(s["index_topk"], seq)
        return k * (k + 1) / 2.0 + (seq - k) * k

    @staticmethod
    def matmul_params_touched(s: Dict[str, Any]) -> float:
        """Matrix-product parameters ONE TOKEN passes through here: the
        attention's and the index's projections and the router whole, the
        routed experts by the share of a token's ``top_k`` choices that fall
        on the experts held, and the head.  The embedding is a gather."""
        d, hd = s["dim"], s["head_dim"]
        attn = 2 * d * s["n_heads"] * hd + 2 * d * s["n_kv_heads"] * hd
        index = d * s["index_heads"] * s["index_head_dim"] + d * s["index_head_dim"] + d * s["index_heads"]
        routed = s["top_k"] * s["experts_held"] / s["router_experts"] * 3 * d * s["expert_hidden"]
        return s["n_layers"] * (attn + index + d * s["router_experts"] + routed) + d * s["vocab_size"]

    @staticmethod
    def index_step(s: Dict[str, Any], rows: float, seq: int, itemsize: int = 2):
        """(operations, bytes) of the index's scoring of one step, all
        layers: ``2 J DI`` a causal pair, once (the selection is kept
        through the backward pass); the index's q, k and weights read and a
        bit a pair written."""
        j, di = s["index_heads"], s["index_head_dim"]
        flops = 2.0 * j * di * dsa_flops.causal_pairs(seq) * rows
        nbytes = rows * (seq * (j * di + di) * itemsize + seq * j * 4 + seq * seq / 8.0)
        return s["n_layers"] * flops, s["n_layers"] * nbytes

    @staticmethod
    def attn_step(s: Dict[str, Any], rows: float, seq: int, itemsize: int = 2):
        """(operations, bytes) of the attention over the picked keys of one
        step, all layers: forward QK^T and PV, backward dP, dV, dQ and dK,
        each ``2 D`` a head and picked pair; q, k, v, o forward and q, k, v,
        o, do, dq, dk, dv backward, the bits read three times."""
        d, h, kv = s["head_dim"], s["n_heads"], s["n_kv_heads"]
        flops = 6.0 * 2.0 * d * h * dsa_flops.picked_pairs(s, seq) * rows
        elements = rows * seq * d * ((2 * h + 2 * kv) + (4 * h + 4 * kv))
        return s["n_layers"] * flops, s["n_layers"] * (elements * itemsize + 3 * rows * seq * seq / 8.0)

    @staticmethod
    def index_loss_step(s: Dict[str, Any], rows: float, seq: int) -> float:
        """Operations of ``L_I`` and its gradient of one step, all layers,
        a picked pair: the head-mean needs QK^T again (``2 D`` a head), the
        index's scores once more forward and twice backward (the pass
        runs once a step and layer since PR 34; the second run before it
        was never credited)."""
        per_pair = 2.0 * s["head_dim"] * s["n_heads"] + 3 * 2.0 * s["index_heads"] * s["index_head_dim"]
        return s["n_layers"] * per_pair * dsa_flops.picked_pairs(s, seq) * rows

    @staticmethod
    def gmm_step(s: Dict[str, Any], rows_here: float, itemsize: int = 2):
        """(operations, bytes) of the grouped products of one step, all
        layers, for ``rows_here`` (token, choice) pairs a layer on the
        experts held: three products forward and six backward of ``2 D F`` a
        row; the held experts' weights read forward and backward and their
        gradients written; the rows in and out of every product."""
        d, f = s["dim"], s["expert_hidden"]
        flops = 9.0 * 2.0 * d * f * rows_here
        weights = 3.0 * s["experts_held"] * 3 * d * f * itemsize
        rows = 3.0 * rows_here * (2 * d + 2 * 2 * f) * itemsize
        return s["n_layers"] * flops, s["n_layers"] * (weights + rows)

    @staticmethod
    def train_flops_per_token(s: Dict[str, Any], seq: int) -> float:
        """Forward and backward: 6 a matrix-product parameter a token
        touches, the index's scoring, the attention over the picked keys
        and the index's loss as above."""
        index, _ = dsa_flops.index_step(s, 1.0, seq)
        attn, _ = dsa_flops.attn_step(s, 1.0, seq)
        return 6.0 * dsa_flops.matmul_params_touched(s) + (index + attn + dsa_flops.index_loss_step(s, 1.0, seq)) / seq


# the ONE name the folded readers find the class by (``step_mfu_pct``, and where
# it has the method ``moe_gmm_roofline`` and ``flash_roofline``: ``sources["architecture"].flops``;
# README.md, "An architecture")
flops = dsa_flops
