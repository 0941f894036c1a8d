"""The architecture ``sambay``: ``torchft_tpu.models.sambay.SambaY``
(Phi-4-mini-flash-reasoning, ``model_type`` ``phi4flash``: a first half of
selective-scan layers with a decay for every (channel, state) pair and
differential-attention layers under a window of 512 and whole, and a second
half whose gated memory units and cross-attention layers read ONE scan's output
and ONE layer's keys and values; LayerNorm, a tied head, no position encoding).

The benchmark's adapter, never a second implementation: the model is the
program's, the plain reference is ``sambay_reference.py`` beside this file (it
imports nothing of the program), and the counting of parameters, operations and
bytes is ONE object, ``sambay_flops`` below, ``flops`` at the end of the file, which
``step_mfu_pct`` finds through the cell's architecture and ``selscan_roofline`` and
``sambay_flash_roofline`` call through ``layer_metrics/_sambay.py`` (its
``flash_step`` counts the windowed launches too: the cell is on no list of
``flash_roofline``).  ``ftbench/README.md``, "An architecture",
says what the harness asks of a file like this one.
"""

from __future__ import annotations

from typing import Any, Dict

from ftbench.architectures import sambay_reference as reference

# the value ``model.attention_path`` may have on the chip: every scan by
# ``selscan_fwd`` / ``selscan_bwd``, every attention by ``flash_*`` or
# ``flash_win_*``; the plain path has another name and fails the run
KERNEL_PATHS = {"selscan+flash"}

# what ``--rehearse`` lays over the configuration on the CPU: the cell's own
# eight layers at toy widths
TOY = dict(
    config=dict(
        hidden_size=64,
        intermediate_size=128,
        num_attention_heads=8,
        num_key_value_heads=4,
        sliding_window=32,
        vocab_size=512,
        torch_dtype="float32",
        mamba_d_state=8,
        mamba_dt_rank=8,
    ),
    seq_len=128,
)

# ``reference_agrees`` (README.md, "How `correct` is decided"): the program's
# differences from the float32 reference have to stay COARSE_RATIO_K times
# under those of the same program on the float8_e4m3fn copy of its weights.
# Read on the chip at 16,384 positions, the published widths and an eighth of
# the vocabulary (PERF.md section 6, PR 63, ``chiprun_out/pr63/cal2.out``): the
# sound program's ratio read 12.03 to 12.29 over twelve seeds, all distinct, of
# ``tests/calibrate_forward_check.py --workload phi4miniflash-ws1-seq16k`` (the
# cell's own nine runs, further seeds, 12.10 to 12.30, and
# ``tests/sambay_forward_check.py`` at 2,048 positions, six seeds, 11.71 to
# 12.46 on the cross-entropies and 12.15 to 12.28 on the LOGITS themselves; the weights are
# the seed's, so the rate a run trains at does not enter); the control, the
# same program on an int8 copy with a scale a channel, read 3.07 to 3.18 over
# the twelve, the plain reference on that copy 3.17 to 3.26 (four seeds), and
# the e4m3 copy itself reads 1.  K = 6.3 keeps the worst sound reading 1.86
# times inside the limit and the nearest control 1.93 times outside (at a
# quarter of the vocabulary, which this PR first measured, the readings were
# 11.96 to 12.61 and 3.08 to 3.27: the head is one layer of nine).  The sound
# ratio is Mistral's (11.9 to 13.2): a dense model of eight layers with no
# router downstream of a rounding; the scan's float32 state and the two
# softmaxes' difference add nothing that shows.
READ_SOUND_LOW, READ_SOUND_HIGH, READ_CONTROL_HIGH = 11.71, 12.46, 3.26
COARSE_RATIO_K = 6.3


def _assumed(config: Dict[str, Any], key: str) -> Any:
    """A scan size ``config.json`` does not carry: ``assumed``'s, unless a
    rehearsal laid a toy's over the configuration under the same name."""
    return config.get(key, config["assumed"][key])


def model_config(config: Dict[str, Any]) -> Any:
    import jax.numpy as jnp

    from torchft_tpu.models.sambay import SambaYConfig

    return SambaYConfig(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        ffn_hidden=config["intermediate_size"],
        window=config["sliding_window"],
        pattern=config["layer_pattern"],
        published_index=tuple(config["layer_index"]),
        d_state=_assumed(config, "mamba_d_state"),
        d_conv=_assumed(config, "mamba_d_conv"),
        expand=_assumed(config, "mamba_expand"),
        dt_rank=_assumed(config, "mamba_dt_rank"),
        norm_eps=config["layer_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["torch_dtype"]],
    )


def model(config: Dict[str, Any]) -> Any:
    from torchft_tpu.models.sambay import SambaY

    if (config["model_type"], config["hidden_act"], config["mb_per_layer"]) != ("phi4flash", "silu", 2):
        raise ValueError("built for model_type phi4flash with SwiGLU and a scan every second layer (mb_per_layer 2)")
    if not config["tie_word_embeddings"] or config["mlp_bias"] or config["lm_head_bias"]:
        raise ValueError("built for a tied head and no bias in the SwiGLU or the head")
    if len(config["layer_pattern"]) != config["num_hidden_layers"] or len(config["layer_index"]) != config["num_hidden_layers"]:
        raise ValueError("layer_pattern and layer_index spell num_hidden_layers layers")
    return SambaY(model_config(config))


def shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes ``sambay_flops`` counts from, and what the readers find under
    ``sources["shapes"]``."""
    pattern = config["layer_pattern"]
    return dict(
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        ffn_hidden=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        window=config["sliding_window"],
        scan_inner=_assumed(config, "mamba_expand") * config["hidden_size"],
        scan_state=_assumed(config, "mamba_d_state"),
        scan_dt_rank=_assumed(config, "mamba_dt_rank"),
        n_scan=pattern.count("M"), diff_windowed=pattern.count("S"), diff_full=pattern.count("F"),
        n_memory=pattern.count("G"), diff_cross=pattern.count("C"),
    )


def token_nll(host_params: Any, tokens: Any, targets: Any, config: Dict[str, Any]) -> Any:
    return reference.token_nll(host_params, tokens, targets, config)


def num_params(config: Dict[str, Any]) -> int:
    return model(config).num_params()


def vocab(config: Dict[str, Any]) -> int:
    """The batch's token ids are drawn below it: the rows of the vocabulary
    held (the first eighth in the cell's configuration)."""
    return config["vocab_size"]


class sambay_flops:
    """Operations and bytes from ``shapes(config)``, of the WHOLE step.
    Everything counted is what the mathematics NEEDS: a layer's matrices once,
    the recurrence's own operations and never a chunk's second forward, under
    the window the LIVE pairs alone, the causal half of a whole layer, heads of
    ``d`` for q and k and ``2 d`` for v as they are (no padding of a head to
    128 credited), nothing recomputed credited; so a share of a peak made from
    it can only read low."""

    # operations a (token, channel, state) of the recurrence: forward dt A,
    # exp, a h, (dt u) B, +, C h, + (7); backward C dy + G', dy h and its sum,
    # G (dt u) and its sum, G B and its sum, G h a, its A and dt products and
    # their two sums, a G (15).  The states inside a chunk made again are the
    # kernel's choice and not credited
    SCAN_OPS = 22.0
    # bytes a (token, channel): u, dt in and y out forward; u, dt, dy in and
    # du, ddt out backward, u, y and their cotangents in bfloat16, dt float32
    SCAN_BYTES = (2 + 4 + 2) + (2 + 4 + 2 + 2 + 4)

    @staticmethod
    def is_mine(s: Dict[str, Any]) -> bool:
        """Whether a cell's shapes are this architecture's."""
        return "scan_inner" in (s or {})

    @staticmethod
    def live_pairs(seq: int, window: Any = None) -> float:
        """The (query, key) pairs a head attends over: ``S W - W (W - 1) / 2``
        under a window of ``W``, which at ``W >= S`` is ``S (S + 1) / 2``."""
        w = seq if window is None else min(window, seq)
        return seq * w - w * (w - 1) / 2.0

    @staticmethod
    def matmul_params_touched(s: Dict[str, Any]) -> float:
        """Matrix-product parameters ONE TOKEN passes through in a step: every
        layer's SwiGLU, a scan layer's W_in, W_x, W_dt and W_out, an attention
        layer's W_qkv and W_o, a memory unit's W_1 and W_2, a cross layer's W_q
        and W_o, and the head.  The embedding is a gather, the convolution four
        taps a channel."""
        d, inner, kv = s["dim"], s["scan_inner"], s["n_kv_heads"] * s["head_dim"]
        scan = d * 2 * inner + inner * (s["scan_dt_rank"] + 2 * s["scan_state"]) + s["scan_dt_rank"] * inner + inner * d
        attention = d * (d + 2 * kv) + d * d
        return (
            s["n_layers"] * 3 * d * s["ffn_hidden"] + s["n_scan"] * scan + (s["diff_windowed"] + s["diff_full"]) * attention
            + s["n_memory"] * 2 * d * inner + s["diff_cross"] * 2 * d * d + d * s["vocab_size"]
        )

    @staticmethod
    def selscan_step(s: Dict[str, Any], rows: float, seq: int):
        """(operations, bytes) of the scans of one step, forward and backward."""
        cells = s["n_scan"] * rows * seq * s["scan_inner"]
        return sambay_flops.SCAN_OPS * s["scan_state"] * cells, float(sambay_flops.SCAN_BYTES) * cells

    @staticmethod
    def flash_step(s: Dict[str, Any], rows: float, seq: int, itemsize: int = 2):
        """(operations, bytes) of the attention of one step, forward and
        backward: the windowed layers' live pairs, the whole and the cross
        layers' causal half, every query head against keys of ``d`` and values
        of ``2 d``."""
        d, h, kv = s["head_dim"], s["n_heads"], s["n_kv_heads"]
        pairs = s["diff_windowed"] * sambay_flops.live_pairs(seq, s["window"]) + (s["diff_full"] + s["diff_cross"]) * sambay_flops.live_pairs(seq)
        # forward QK^T (2 d) and PV (4 d), backward dP and dV (4 d each), dQ and dK (2 d each), a pair and query head
        flops = 2.0 * (3 * d + 3 * 2 * d) * pairs * h * rows
        # q (d) and o (2 d) forward, q, o, do, dq backward, a query head; k (d)
        # and V (2 d, read once for k1 and once for k2) forward, and again with
        # their gradients backward, a key head
        launches = s["diff_windowed"] + s["diff_full"] + s["diff_cross"]
        elements = launches * rows * seq * (h * (3 * d + 8 * d) + kv * (3 * d + 2 * 3 * d))
        return flops, float(elements * itemsize)

    @staticmethod
    def train_flops_per_token(s: Dict[str, Any], seq: int) -> float:
        """Forward and backward of the whole step: 6 a matrix-product parameter
        a token touches, attention over the live pairs, the recurrence."""
        return (
            6.0 * sambay_flops.matmul_params_touched(s)
            + (sambay_flops.flash_step(s, 1.0, seq)[0] + sambay_flops.selscan_step(s, 1.0, seq)[0]) / seq
        )


# the ONE name the folded readers find the class by (``step_mfu_pct``, and where
# it has the method ``moe_gmm_roofline`` and ``flash_roofline``: ``sources["architecture"].flops``;
# README.md, "An architecture")
flops = sambay_flops
