"""The architecture ``ling_hybrid``: ``torchft_tpu.models.ling_hybrid.LingHybrid``
(Ling-3.0-flash, ``model_type`` ``bailing_hybrid``: KDA and latent-attention
layers five to one, a dense layer first, then 512-way sigmoid routing with a
selection bias over the experts this chip holds, one shared expert).

The benchmark's adapter, never a second implementation: the model is the
program's, the plain reference is ``ling_hybrid_reference.py`` beside this
file (it imports nothing of the program), and the counting of parameters,
operations and bytes is ``ling_flops`` below, ``flops`` at the end of the file,
which ``step_mfu_pct``, ``moe_gmm_roofline`` and ``flash_roofline`` find through
the cell's architecture and ``kda_roofline`` calls.  ``ftbench/README.md``, "An architecture", says
what the harness asks of a file like this one.

``model.loss`` is the next-token cross-entropy, which is what
``reference_agrees`` ties to ``model.apply``; a training step differentiates
``model.objective``, that loss and the routers' balance loss (6e-4 at
``init``), and ``tests/test_ling_hybrid.py`` holds both, and every gradient,
to the reference.
"""

from __future__ import annotations

from typing import Any, Dict

from ftbench.architectures import ling_hybrid_reference as reference

# the values ``model.attention_path`` may have on the chip: every KDA layer
# by the chunked kernels, every MLA layer by the flash kernels, the experts
# by the grouped kernel; a plain path fails the run
KERNEL_PATHS = {"kda+flash"}

# what ``--rehearse`` lays over the configuration on the CPU: the published
# pattern (7 layers: dense, 4 KDA expert layers, MLA, KDA) at toy widths
TOY = dict(
    config=dict(
        hidden_size=64,
        intermediate_size=128,
        num_attention_heads=2,
        num_key_value_heads=2,
        head_dim=32,
        qk_nope_head_dim=32,
        qk_rope_head_dim=16,
        qk_head_dim=48,
        rotary_dim=16,
        v_head_dim=32,
        kv_lora_rank=32,
        moe_intermediate_size=32,
        moe_shared_expert_intermediate_size=32,
        router_experts=16,
        num_experts=4,
        experts_held=[4, 4],
        n_group=4,
        topk_group=2,
        num_experts_per_tok=4,
        vocab_size=512,
        torch_dtype="float32",
    ),
    seq_len=128,
)

# ``reference_agrees`` (README.md, "How `correct` is decided"): the program's
# differences from the float32 reference have to stay COARSE_RATIO_K times
# under those of the same program on the float8_e4m3fn copy of its weights.
# Read on the chip (PERF.md section 6, PR 29): the sound program's ratio read
# 5.21 to 6.11 over 36 readings (twelve seeds of ``tests/
# calibrate_forward_check.py --workload ling3flash-ws1-seq8k``, twenty-one
# runs of the cell, three with the selection bias drawn from the seed:
# ``scripts/ling_bias_forward_check.py``); the control, the same program on
# an int8 copy with a scale a channel, read 2.40 to 2.60, the plain
# reference on that copy 2.48 to 2.67, and the e4m3 copy itself reads 1.
# K = 3.8 keeps the worst sound seed 1.37 times inside the limit and the
# nearest control 1.42 times outside.  The room is narrower than ``llama``'s
# (11.9 against 3.05): here a token's error also holds the experts a router
# chose otherwise in bfloat16 than in float32, which the coarse copy's
# larger errors do not grow with.
READ_SOUND_LOW, READ_SOUND_HIGH, READ_CONTROL_HIGH = 5.21, 6.11, 2.67
COARSE_RATIO_K = 3.8


def model_config(config: Dict[str, Any]) -> Any:
    import jax.numpy as jnp

    from torchft_tpu.models.ling_hybrid import LingHybridConfig

    assumed = config["assumed"]
    return LingHybridConfig(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        head_dim=config["head_dim"],
        first_k_dense=config["first_k_dense_replace"],
        layer_group_size=config["layer_group_size"],
        dense_hidden=config["intermediate_size"],
        expert_hidden=config["moe_intermediate_size"],
        shared_hidden=config["moe_shared_expert_intermediate_size"] * config["num_shared_experts"],
        num_experts=config["router_experts"],
        experts_held=tuple(config["experts_held"]),
        top_k=config["num_experts_per_tok"],
        n_group=config["n_group"],
        topk_group=config["topk_group"],
        routed_scaling_factor=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        conv_kernel=config["short_conv_kernel_size"],
        kda_lower_bound=float(config["kda_lower_bound"]),
        norm_eps=config["rms_norm_eps"],
        expert_swiglu_limits=tuple(config["expert_swiglu_limit_list"]),
        shared_swiglu_limits=tuple(config["share_expert_swiglu_limit_list"]),
        n_mtp=config["num_nextn_predict_layers"],
        mtp_loss_weight=float(config["mtp_loss_scaling_factor"]),
        bias_update_rate=assumed["bias_update_rate"],
        balance_loss_weight=assumed["balance_loss_weight"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["torch_dtype"]],
    )


def model(config: Dict[str, Any]) -> Any:
    from torchft_tpu.models.ling_hybrid import LingHybrid

    if config["experts_held"][1] != config["num_experts"]:
        raise ValueError("num_experts counts the experts held: experts_held = [first, num_experts]")
    return LingHybrid(model_config(config))


def shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes ``ling_flops`` counts from, and what the readers find
    under ``sources["shapes"]``."""
    kinds = reference.layer_kinds(config)
    return dict(
        dim=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        head_dim=config["head_dim"],
        qk_head_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        kv_lora_rank=config["kv_lora_rank"],
        dense_hidden=config["intermediate_size"],
        expert_hidden=config["moe_intermediate_size"],
        shared_hidden=config["moe_shared_expert_intermediate_size"] * config["num_shared_experts"],
        router_experts=config["router_experts"],
        experts_held=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        vocab_size=config["vocab_size"],
        conv_kernel=config["short_conv_kernel_size"],
        n_kda=sum(1 for mixer, _ in kinds if mixer == "kda"),
        n_mla=sum(1 for mixer, _ in kinds if mixer == "mla"),
        n_dense=sum(1 for _, ffn in kinds if ffn == "dense"),
        n_moe=sum(1 for _, ffn in kinds if ffn == "moe"),
    )


def token_nll(host_params: Any, tokens: Any, targets: Any, config: Dict[str, Any]) -> Any:
    return reference.token_nll(host_params, tokens, targets, config)


def num_params(config: Dict[str, Any]) -> int:
    return model(config).num_params()


def vocab(config: Dict[str, Any]) -> int:
    """The batch's token ids are drawn below it: the slice of the vocabulary held."""
    return config["vocab_size"]


class ling_flops:
    """Operations and bytes from ``shapes(config)``.  Everything counted is
    what the mathematics NEEDS (causal attention halved, the recurrence of
    the delta rule and not its chunked form, no recomputation), so a share
    of a peak made from it can only read low."""

    @staticmethod
    def is_mine(s: Dict[str, Any]) -> bool:
        """Whether a cell's shapes are this architecture's."""
        return "n_kda" in (s or {})

    @staticmethod
    def matmul_params_touched(s: Dict[str, Any]) -> float:
        """Matrix-product parameters ONE TOKEN passes through here: the
        mixers, the dense layer, routers and shared experts whole, the
        routed experts by the share of a token's ``top_k`` choices that
        fall on the experts held, and the head.  The embedding is a gather."""
        d, h = s["dim"], s["n_heads"]
        inner = h * s["head_dim"]
        kda = 4 * d * inner + inner * d + 2 * d * h
        mla = (
            d * h * s["qk_head_dim"]
            + d * (s["kv_lora_rank"] + s["qk_rope_head_dim"])
            + s["kv_lora_rank"] * h * (s["qk_head_dim"] - s["qk_rope_head_dim"] + s["v_head_dim"])
            + h * s["v_head_dim"] * d
            + d * h
        )
        expert = 3 * d * s["expert_hidden"]
        routed = s["top_k"] * s["experts_held"] / s["router_experts"] * expert
        moe = d * s["router_experts"] + 3 * d * s["shared_hidden"] + routed
        return (
            s["n_kda"] * kda + s["n_mla"] * mla + s["n_dense"] * 3 * d * s["dense_hidden"]
            + s["n_moe"] * moe + d * s["vocab_size"]
        )

    @staticmethod
    def kda_step(s: Dict[str, Any], rows: float, seq: int, itemsize: int = 2):
        """(operations, bytes) of the delta rule of one step, forward and
        backward, all KDA layers: the recurrence's ``6 dk dv`` a token and
        head forward (the state times k, the rank-one update, the read-out;
        the decay's multiply not counted) and twice that backward; q, k, g,
        v and o once each way."""
        dk = dv = s["head_dim"]
        tokens_heads = rows * seq * s["n_heads"]
        flops = 3.0 * 6.0 * dk * dv * tokens_heads
        nbytes = 2.0 * tokens_heads * (3 * dk + 2 * dv) * itemsize
        return s["n_kda"] * flops, s["n_kda"] * nbytes

    @staticmethod
    def mla_flash_step(s: Dict[str, Any], rows: float, seq: int, itemsize: int = 2):
        """(operations, bytes) of the causal attention of one step, all MLA
        layers: forward QK^T (192) and PV (128), backward dP, dV (128) and
        dQ, dK (192), each ``2 S S D`` a head halved by the mask (the
        recomputed scores are the kernel's choice and not credited); q, k,
        v, o forward and q, k, v, o, do, dq, dk, dv backward."""
        qk, dv, h = s["qk_head_dim"], s["v_head_dim"], s["n_heads"]
        flops = (3 * qk + 3 * dv) * 2.0 * seq * seq * h * rows * 0.5
        elements = rows * seq * h * ((2 * qk + 2 * dv) + (4 * qk + 4 * dv))
        return s["n_mla"] * flops, s["n_mla"] * float(elements * itemsize)

    # the common name of the launches ``flash_fwd``/``_dq``/``_dkv``'s need (``flash_roofline``)
    flash_step = mla_flash_step

    @staticmethod
    def gmm_step(s: Dict[str, Any], rows_here: float, itemsize: int = 2):
        """(operations, bytes) of the grouped products of one step, all
        expert layers, for ``rows_here`` (token, choice) pairs a layer on
        the experts held: three products forward and six backward of ``2 D
        F`` a row; the held experts' weights read forward and backward and
        their gradients written; the rows in and out of every product."""
        d, f = s["dim"], s["expert_hidden"]
        flops = 9.0 * 2.0 * d * f * rows_here
        weights = 3.0 * s["experts_held"] * 3 * d * f * itemsize
        rows = 3.0 * rows_here * (2 * d + 2 * 2 * f) * itemsize
        return s["n_moe"] * flops, s["n_moe"] * (weights + rows)

    @staticmethod
    def train_flops_per_token(s: Dict[str, Any], seq: int) -> float:
        """Forward and backward: 6 a matrix-product parameter a token
        touches, the delta rule and the causal attention as above."""
        kda, _ = ling_flops.kda_step(s, 1.0, seq)
        mla, _ = ling_flops.mla_flash_step(s, 1.0, seq)
        return 6.0 * ling_flops.matmul_params_touched(s) + (kda + mla) / seq


# the ONE name the folded readers find the class by (``step_mfu_pct``, and where
# it has the method ``moe_gmm_roofline`` and ``flash_roofline``: ``sources["architecture"].flops``;
# README.md, "An architecture")
flops = ling_flops
