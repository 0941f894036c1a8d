"""The architecture ``prerouted_moe``:
``torchft_tpu.models.prerouted_moe.PreroutedMoE`` (SmallThinker-21BA3B-Instruct,
``model_name`` ``smallthinker_21b_instruct``: experts routed from the layer's
INPUT, before its attention; 64-way softmax routing, 6 a token, over the ReGLU
experts this chip holds, no shared expert and no dense layer; attention of two
kinds from two published lists, every earlier position with NO position
encoding or a window of 4,096 with rope, one to three, at 28 query heads over
4; two norms a layer).

The benchmark's adapter, never a second implementation: the model is the
program's, the plain reference is ``prerouted_moe_reference.py`` beside this
file (it imports nothing of the program), and the counting of parameters,
operations and bytes is ONE object, ``prerouted_flops`` below, ``flops`` at the
end of the file, which ``step_mfu_pct``, ``moe_gmm_roofline`` and
``flash_roofline`` (the GLOBAL layer's) find through the cell's architecture
and ``prerouted_win_flash_roofline`` (the windowed layers') calls through
``layer_metrics/_prerouted.py``.  ``ftbench/README.md``, "An architecture",
says what the harness asks of a file like this one.

``model.loss`` is the next-token cross-entropy, which is what
``reference_agrees`` ties to ``model.apply``, and it IS what a training step
differentiates (``model.objective``: no auxiliary loss);
``tests/test_prerouted_moe.py`` holds it, the logits and every gradient, the
routers' included, to the reference.
"""

from __future__ import annotations

from typing import Any, Dict

from ftbench.architectures import prerouted_moe_reference as reference

# the value ``model.attention_path`` may have on the chip: every windowed
# layer by the flash kernels that WALK the window's blocks, the global layer
# by the flash kernels, the experts by the grouped kernel; a path that masks
# a full walk, or a plain path, has another name and fails the run
KERNEL_PATHS = {"flash_win+flash"}

# what ``--rehearse`` lays over the configuration on the CPU: the cell's own
# four layers at toy widths, SEVEN query heads to a key head as published, the
# window SHORTER than the sequence
TOY = dict(
    config=dict(
        hidden_size=64,
        num_attention_heads=7,
        num_key_value_heads=1,
        head_dim=16,
        sliding_window_size=48,
        moe_ffn_hidden_size=32,
        router_experts=16,
        moe_num_primary_experts=8,
        experts_held=[0, 8],
        moe_num_active_primary_experts=3,
        vocab_size=512,
        torch_dtype="float32",
    ),
    seq_len=128,
)

# ``reference_agrees`` (README.md, "How `correct` is decided"): the program's
# differences from the float32 reference have to stay COARSE_RATIO_K times
# under those of the same program on the float8_e4m3fn copy of its weights.
# Read on the chip at 16,384 positions and the published widths, on the tree
# that ships (PERF.md section 6, PR 67, ``chiprun_out/pr67/``): the sound
# program's ratio read 6.34 to 8.22 over twenty seeds, all distinct (twelve of
# ``ftbench/tests/calibrate_forward_check.py --workload smallthinker-ws1-seq16k``,
# 6.42 to 7.67; eight runs of the cell, 6.34 to 8.22; the weights are the
# seed's, so the rate a run trains at does not enter); the control, the same
# program on an int8 copy with a scale a channel, read 1.78 to 1.96 over the
# twelve, the plain reference on that copy 1.78 to 1.90 (four seeds), and the
# e4m3 copy itself reads 1.  K = 3.55, the geometric mean of 6.42 and 1.96 (the
# calibration's two), keeps the worst sound seed of the twenty 1.79 times inside
# the limit and the nearest control 1.81 times outside (``windowed_moe`` 1.80 and 1.83,
# ``ssm_hybrid_moe`` 1.50 and 1.50, ``llama`` 3.05 and 1.28).  The residual
# stream is float32 and the router reads its float32 norm from the first run
# on; what is left is 0.011 to 0.013 of a nat a token for the program and
# 0.084 to 0.087 for the e4m3 copy.  Both readings stand lower than Trinity's
# 9 to 10.7 and 2.6 to 2.7; why was NOT measured (the likeliest cause: every
# one of the four layers is an expert layer with no shared expert and no dense
# part beside the 6 of 64 a softmax router picks, so a rounding that flips a
# token's sixth expert moves its whole feed-forward part, in the program and
# in the int8 copy alike).
READ_SOUND_LOW, READ_SOUND_HIGH, READ_CONTROL_HIGH = 6.34, 8.22, 1.96
COARSE_RATIO_K = 3.55


def model_config(config: Dict[str, Any]) -> Any:
    import jax.numpy as jnp

    from torchft_tpu.models.prerouted_moe import PreroutedMoEConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return PreroutedMoEConfig(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        sliding_window_layout=tuple(config["sliding_window_layout"]),
        rope_layout=tuple(config["rope_layout"]),
        sliding_window=config["sliding_window_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        num_experts=config["router_experts"],
        experts_held=tuple(config["experts_held"]),
        top_k=config["moe_num_active_primary_experts"],
        expert_hidden=config["moe_ffn_hidden_size"],
        norm_eps=config["rms_norm_eps"],
        dtype=dtypes[config["torch_dtype"]],
    )


def model(config: Dict[str, Any]) -> Any:
    from torchft_tpu.models.prerouted_moe import PreroutedMoE

    if config["experts_held"][1] != config["moe_num_primary_experts"]:
        raise ValueError("moe_num_primary_experts counts the experts held: experts_held = [first, moe_num_primary_experts]")
    if not len(config["rope_layout"]) == len(config["sliding_window_layout"]) == config["num_hidden_layers"]:
        raise ValueError("rope_layout and sliding_window_layout have an entry a layer of num_hidden_layers")
    if not (config["moe_primary_router_apply_softmax"] and config["norm_topk_prob"]):
        raise ValueError("built for a softmax router whose chosen weights are normalised")
    if config["rope_scaling"] is not None or config["tie_word_embeddings"]:
        raise ValueError("built for plain rope and an untied head")
    return PreroutedMoE(model_config(config))


def shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes ``prerouted_flops`` counts from, and what the readers find
    under ``sources["shapes"]``."""
    kinds = reference.layer_kinds(config)
    return dict(
        dim=config["hidden_size"],
        n_prerouted_windowed=sum(windowed for windowed, _ in kinds),
        n_prerouted_global=sum(not windowed for windowed, _ in kinds),
        window=config["sliding_window_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        expert_hidden=config["moe_ffn_hidden_size"],
        router_experts=config["router_experts"],
        experts_held=config["moe_num_primary_experts"],
        top_k=config["moe_num_active_primary_experts"],
        vocab_size=config["vocab_size"],
    )


def token_nll(host_params: Any, tokens: Any, targets: Any, config: Dict[str, Any]) -> Any:
    return reference.token_nll(host_params, tokens, targets, config)


def num_params(config: Dict[str, Any]) -> int:
    return model(config).num_params()


def vocab(config: Dict[str, Any]) -> int:
    """The batch's token ids are drawn below it: the slice of the vocabulary held."""
    return config["vocab_size"]


class prerouted_flops:
    """Operations and bytes from ``shapes(config)``.  Everything counted is
    what the mathematics NEEDS: under a window the LIVE pairs alone and never
    the blocks a kernel walks, the causal half of the global layer, three
    products an expert, nothing recomputed credited; so a share of a peak
    made from it can only read low."""

    @staticmethod
    def is_mine(s: Dict[str, Any]) -> bool:
        """Whether a cell's shapes are this architecture's."""
        return "n_prerouted_windowed" in (s or {})

    @staticmethod
    def live_pairs(seq: int, window: Any = None) -> float:
        """The (query, key) pairs a head attends over: ``S W - W (W - 1) / 2``
        under a window of ``W`` (the first ``W - 1`` rows see fewer), which at
        ``W >= S`` is causal attention's ``S (S + 1) / 2``."""
        w = seq if window is None else min(window, seq)
        return seq * w - w * (w - 1) / 2.0

    @staticmethod
    def matmul_params_touched(s: Dict[str, Any]) -> float:
        """Matrix-product parameters ONE TOKEN passes through here: q and o,
        k and v, the router whole, the routed experts by the share of a
        token's ``top_k`` choices that fall on the experts held, and the head.
        The embedding is a gather."""
        d = s["dim"]
        attention = 2 * d * s["n_heads"] * s["head_dim"] + 2 * d * s["n_kv_heads"] * s["head_dim"]
        routed = s["top_k"] * s["experts_held"] / s["router_experts"] * 3 * d * s["expert_hidden"]
        layers = s["n_prerouted_windowed"] + s["n_prerouted_global"]
        return layers * (attention + d * s["router_experts"] + routed) + d * s["vocab_size"]

    @staticmethod
    def _flash(s: Dict[str, Any], rows: float, seq: int, layers: int, window: Any, itemsize: int):
        d, h, kv = s["head_dim"], s["n_heads"], s["n_kv_heads"]
        # forward QK^T and PV, backward dP, dV, dQ and dK: 2 D a pair each
        flops = 6.0 * 2.0 * prerouted_flops.live_pairs(seq, window) * d * h * rows
        # q, k, v, o forward and q, k, v, o, do, dq, dk, dv backward, k and v at their own heads
        elements = rows * seq * d * ((2 * h + 2 * kv) + (4 * h + 4 * kv))
        return layers * flops, layers * float(elements * itemsize)

    @staticmethod
    def win_flash_step(s: Dict[str, Any], rows: float, seq: int, itemsize: int = 2):
        """(operations, bytes) of the WINDOWED layers' attention of one step,
        forward and backward: the live pairs alone (the recomputed scores and
        the dead part of an edge block are the kernels' choice and not
        credited)."""
        return prerouted_flops._flash(s, rows, seq, s["n_prerouted_windowed"], s["window"], itemsize)

    @staticmethod
    def flash_step(s: Dict[str, Any], rows: float, seq: int, itemsize: int = 2):
        """The same of the GLOBAL layers, whose launches are ``flash_fwd``,
        ``flash_dq`` and ``flash_dkv`` and the only ones of those names: the
        causal half."""
        return prerouted_flops._flash(s, rows, seq, s["n_prerouted_global"], None, itemsize)

    @staticmethod
    def gmm_step(s: Dict[str, Any], rows_here: float, itemsize: int = 2):
        """(operations, bytes) of the grouped products of one step, all
        layers, for ``rows_here`` (token, choice) pairs a layer on the
        experts held: THREE products forward and six backward of ``2 D F`` a
        row; the held experts' three matrices read forward and backward and
        their gradients written; the rows in and out of every product."""
        d, f = s["dim"], s["expert_hidden"]
        layers = s["n_prerouted_windowed"] + s["n_prerouted_global"]
        flops = 9.0 * 2.0 * d * f * rows_here
        weights = 3.0 * s["experts_held"] * 3 * d * f * itemsize
        rows = 3.0 * rows_here * (3 * d + 3 * f) * itemsize
        return layers * flops, layers * (weights + rows)

    @staticmethod
    def train_flops_per_token(s: Dict[str, Any], seq: int) -> float:
        """Forward and backward: 6 a matrix-product parameter a token
        touches, and attention over the live pairs as above."""
        windowed, _ = prerouted_flops.win_flash_step(s, 1.0, seq)
        full, _ = prerouted_flops.flash_step(s, 1.0, seq)
        return 6.0 * prerouted_flops.matmul_params_touched(s) + (windowed + full) / seq


# the ONE name the folded readers find the class by (``step_mfu_pct``,
# ``moe_gmm_roofline`` and ``flash_roofline``: ``sources["architecture"].flops``;
# README.md, "An architecture")
flops = prerouted_flops
