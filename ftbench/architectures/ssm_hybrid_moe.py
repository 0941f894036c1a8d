"""The architecture ``ssm_hybrid_moe``:
``torchft_tpu.models.ssm_hybrid_moe.SsmHybridMoE``
(NVIDIA-Nemotron-3-Nano-30B-A3B, ``model_type`` ``nemotron_h``: layers that are
ONE mixer each from a pattern string, a Mamba-2 state-space layer, 32-over-2
grouped-query attention, or 128-way sigmoid routing with a selection bias
over the squared-ReLU experts this chip holds, one shared expert).

The benchmark's adapter, never a second implementation: the model is the
program's, the plain reference is ``ssm_hybrid_moe_reference.py`` beside this
file (it imports nothing of the program), and the counting of parameters,
operations and bytes is ONE object, ``ssm_flops`` below, ``flops`` at the end of the file, which
``step_mfu_pct``, ``moe_gmm_roofline`` and ``flash_roofline`` find through the
cell's architecture and ``ssd_roofline`` calls.  ``ftbench/README.md``, "An architecture", says
what the harness asks of a file like this one.

``model.loss`` is the next-token cross-entropy, which is what
``reference_agrees`` ties to ``model.apply``; a training step differentiates
``model.objective``, that loss and the routers' balance loss, and
``tests/test_ssm_hybrid_moe.py`` holds both, and every gradient, to the
reference.
"""

from __future__ import annotations

from typing import Any, Dict

from ftbench.architectures import ssm_hybrid_moe_reference as reference

# the value ``model.attention_path`` may have on the chip: every state-space
# layer by the scan kernels, the attention layer by the flash kernels, the
# experts by the grouped kernel; a plain path fails the run
KERNEL_PATHS = {"ssd+flash"}

# what ``--rehearse`` lays over the configuration on the CPU: the cell's own
# pattern (MEMEM*EME) at toy widths
TOY = dict(
    config=dict(
        hidden_size=64,
        mamba_num_heads=4,
        mamba_head_dim=16,
        ssm_state_size=16,
        n_groups=2,
        chunk_size=16,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        moe_intermediate_size=32,
        moe_shared_expert_intermediate_size=48,
        router_experts=16,
        n_routed_experts=4,
        experts_held=[4, 4],
        num_experts_per_tok=4,
        vocab_size=512,
        torch_dtype="float32",
    ),
    seq_len=128,
)

# ``reference_agrees`` (README.md, "How `correct` is decided"): the program's
# differences from the float32 reference have to stay COARSE_RATIO_K times
# under those of the same program on the float8_e4m3fn copy of its weights.
# Read on the chip at 16,384 positions and the published widths (PERF.md
# section 6, PR 35): the sound program's ratio read 4.87 to 6.55 over thirty
# seeds, all distinct (twelve of ``tests/calibrate_forward_check.py --workload
# nemotron3nano-ws1-seq16k``, eighteen runs of the cell; the weights are the
# seed's, so the rate a run trains at does not enter); the control, the same
# program on an int8 copy with a scale a channel, read 1.98 to 2.15 over the
# twelve, the plain reference on that copy 2.09 to 2.16 (four seeds), and the
# e4m3 copy itself reads 1.  K = 3.24, the geometric mean of 4.87 and 2.16,
# keeps the worst sound seed 1.50 times inside the limit and the nearest
# control 1.50 times outside (``indexed_sparse_moe`` 1.61 and 1.67,
# ``ling_hybrid`` 1.37 and 1.42, ``llama`` 3.05 and 1.28).  The residual
# stream is float32 and the router reads its float32 norm from the first
# run on (PR 33's cure, taken before it was needed); what is left, 0.012 to
# 0.016 of a nat a token, is mostly a step function of bfloat16 operands:
# which 6 of 128 experts a token takes downstream of a layer output that
# differs, and squared activations that double a relative error.
READ_SOUND_LOW, READ_SOUND_HIGH, READ_CONTROL_HIGH = 4.87, 6.55, 2.16
COARSE_RATIO_K = 3.24


def model_config(config: Dict[str, Any]) -> Any:
    import jax.numpy as jnp

    from torchft_tpu.models.ssm_hybrid_moe import SsmHybridMoEConfig

    assumed = config["assumed"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return SsmHybridMoEConfig(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        pattern=config["hybrid_override_pattern"],
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"],
        ssm_state=config["ssm_state_size"],
        ssm_groups=config["n_groups"],
        conv_kernel=config["conv_kernel"],
        chunk=config["chunk_size"],
        time_step_min=config["time_step_min"],
        time_step_max=config["time_step_max"],
        time_step_floor=config["time_step_floor"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        num_experts=config["router_experts"],
        experts_held=tuple(config["experts_held"]),
        top_k=config["num_experts_per_tok"],
        expert_hidden=config["moe_intermediate_size"],
        shared_hidden=config["moe_shared_expert_intermediate_size"] * config["n_shared_experts"],
        routed_scaling_factor=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"],
        bias_update_rate=assumed["bias_update_rate"],
        balance_loss_weight=assumed["balance_loss_weight"],
        norm_eps=config["layer_norm_epsilon"],
        dtype=dtypes[config["torch_dtype"]],
    )


def model(config: Dict[str, Any]) -> Any:
    from torchft_tpu.models.ssm_hybrid_moe import SsmHybridMoE

    if config["experts_held"][1] != config["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held: experts_held = [first, n_routed_experts]")
    if len(config["hybrid_override_pattern"]) != config["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern has a character a layer of num_hidden_layers")
    if (config["n_group"], config["topk_group"], config["mlp_hidden_act"]) != (1, 1, "relu2"):
        raise ValueError("built for one group of experts (n_group 1, topk_group 1) of the form relu2")
    return SsmHybridMoE(model_config(config))


def shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes ``ssm_flops`` counts from, and what the readers find under
    ``sources["shapes"]``."""
    kinds = reference.layer_kinds(config)
    return dict(
        dim=config["hidden_size"],
        n_ssm=kinds.count("ssm"),
        n_attention=kinds.count("attention"),
        n_moe=kinds.count("experts"),
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"],
        ssm_state=config["ssm_state_size"],
        ssm_groups=config["n_groups"],
        chunk=config["chunk_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        expert_hidden=config["moe_intermediate_size"],
        shared_hidden=config["moe_shared_expert_intermediate_size"] * config["n_shared_experts"],
        router_experts=config["router_experts"],
        experts_held=config["n_routed_experts"],
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


class ssm_flops:
    """Operations and bytes from ``shapes(config)``.  Everything counted is
    what the mathematics NEEDS: the scan's products in their chunked form
    with the causal half of a chunk's square alone, causal attention halved,
    two products an expert, nothing recomputed credited; so a share of a peak
    made from it can only read low."""

    @staticmethod
    def is_mine(s: Dict[str, Any]) -> bool:
        """Whether a cell's shapes are this architecture's."""
        return "n_ssm" in (s or {})

    @staticmethod
    def matmul_params_touched(s: Dict[str, Any]) -> float:
        """Matrix-product parameters ONE TOKEN passes through here: the
        mixers' projections, routers and shared experts whole, the routed
        experts by the share of a token's ``top_k`` choices that fall on the
        experts held, and the head.  The embedding is a gather."""
        d = s["dim"]
        inner = s["ssm_heads"] * s["ssm_head_dim"]
        ssm = d * (2 * inner + 2 * s["ssm_groups"] * s["ssm_state"] + s["ssm_heads"]) + inner * d
        attention = 2 * d * s["n_heads"] * s["head_dim"] + 2 * d * s["n_kv_heads"] * s["head_dim"]
        routed = s["top_k"] * s["experts_held"] / s["router_experts"] * 2 * d * s["expert_hidden"]
        moe = d * s["router_experts"] + 2 * d * s["shared_hidden"] + routed
        return s["n_ssm"] * ssm + s["n_attention"] * attention + s["n_moe"] * moe + d * s["vocab_size"]

    @staticmethod
    def ssd_step(s: Dict[str, Any], rows: float, seq: int, itemsize: int = 2):
        """(operations, bytes) of the scan of one step, forward and backward,
        all state-space layers.  Forward, a token: ``C B^T`` once a GROUP and
        the masked product with ``dt x`` once a head, over the ``(chunk + 1) /
        2`` tokens of the chunk that lie before it, and the two products with
        the state (read-out and update, ``2 N P`` each); backward twice that.
        Bytes: ``dt x``, ``B``, ``C`` and the running log decay (float32, in
        its two layouts) read and ``y`` and every chunk's starting state
        (float32) written forward; all of those and ``dy`` read and the four
        cotangents written backward."""
        h, p, n, g, c = s["ssm_heads"], s["ssm_head_dim"], s["ssm_state"], s["ssm_groups"], min(s["chunk"], seq)
        tokens = rows * seq
        forward = h * (p * (c + 1) + 4 * n * p) + g * n * (c + 1)
        operands = (h * p + 2 * g * n) * itemsize + 2 * h * 4  # dt x, B, C, g twice
        states = h * p * n * 4 / c
        nbytes = (operands + h * p * itemsize + states) + (operands + states + h * p * itemsize + operands)
        return s["n_ssm"] * 3.0 * forward * tokens, s["n_ssm"] * float(nbytes * tokens)

    @staticmethod
    def flash_step(s: Dict[str, Any], rows: float, seq: int, itemsize: int = 2):
        """(operations, bytes) of the causal attention of one step, all
        attention layers: forward QK^T and PV, backward dP, dV, dQ and dK,
        each ``2 S S D`` a query head halved by the mask (the recomputed
        scores are the kernel's choice and not credited); q, k, v, o forward
        and q, k, v, o, do, dq, dk, dv backward, k and v at their own heads."""
        d, h, kv = s["head_dim"], s["n_heads"], s["n_kv_heads"]
        flops = 6.0 * 2.0 * seq * seq * d * h * rows * 0.5
        elements = rows * seq * d * ((2 * h + 2 * kv) + (4 * h + 4 * kv))
        return s["n_attention"] * flops, s["n_attention"] * float(elements * itemsize)

    @staticmethod
    def gmm_step(s: Dict[str, Any], rows_here: float, itemsize: int = 2):
        """(operations, bytes) of the grouped products of one step, all
        expert layers, for ``rows_here`` (token, choice) pairs a layer on the
        experts held: TWO products forward and four backward of ``2 D F`` a
        row; the held experts' two matrices read forward and backward and
        their gradients written; the rows in and out of every product."""
        d, f = s["dim"], s["expert_hidden"]
        flops = 6.0 * 2.0 * d * f * rows_here
        weights = 3.0 * s["experts_held"] * 2 * d * f * itemsize
        rows = 3.0 * rows_here * (2 * d + 2 * f) * itemsize
        return s["n_moe"] * flops, s["n_moe"] * (weights + rows)

    @staticmethod
    def train_flops_per_token(s: Dict[str, Any], seq: int) -> float:
        """Forward and backward: 6 a matrix-product parameter a token
        touches, the scan's products in their chunked form and the causal
        attention as above."""
        scan, _ = ssm_flops.ssd_step(s, 1.0, seq)
        attention, _ = ssm_flops.flash_step(s, 1.0, seq)
        return 6.0 * ssm_flops.matmul_params_touched(s) + (scan + attention) / seq


# the ONE name the folded readers find the class by (``step_mfu_pct``, and where
# it has the method ``moe_gmm_roofline`` and ``flash_roofline``: ``sources["architecture"].flops``;
# README.md, "An architecture")
flops = ssm_flops
