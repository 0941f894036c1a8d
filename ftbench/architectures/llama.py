"""The architecture ``llama``: ``torchft_tpu.models.llama.Llama`` (pre-norm
RMSNorm, rotary embedding, grouped-query causal attention, SwiGLU, no bias,
untied head), which is Mistral-7B-v0.3's block too.

The benchmark's adapter, never a second implementation: the model is the
program's, the plain reference is ``ftbench/reference.py`` and the counting
of parameters, operations and bytes is ``ftbench/flops.py``, both as they
stand.  ``ftbench/README.md``, "An architecture", says what the harness asks
of a file like this one; it asks nothing else of a model.
"""

from __future__ import annotations

from typing import Any, Dict

from ftbench import flops as counting, reference

# the values ``model.attention_path`` may have on the chip (the check
# ``attention_flash``): a naive path fails the run
KERNEL_PATHS = {"flash"}

# what ``--rehearse`` lays over the configuration on the CPU
TOY = dict(
    config=dict(
        hidden_size=64,
        intermediate_size=128,
        num_attention_heads=4,
        num_key_value_heads=2,
        vocab_size=512,
        torch_dtype="float32",
    ),
    seq_len=128,
)

# ``reference_agrees`` (README.md, "How `correct` is decided"): the program's
# differences from the float32 reference have to stay COARSE_RATIO_K times
# under those of the same program on the float8_e4m3fn copy of its weights.
# At seeded weights the logits are of order one, and the ratio read 11.89 to
# 13.21 on the chip over 46 readings of the two Mistral configurations.  The
# control, the same program on an int8 copy with a scale a channel (the
# finest 8-bit path), read 2.75 to 2.97, the plain reference on that copy 2.85
# to 3.05, and the e4m3 copy itself reads 1 (PERF.md section 6, PR 25).  K
# keeps the worst sound seed three times in, as ISSUE 25 asks (K <= 11.89 /
# 3), and every control out.  At the weights a window ENDS with (one batch
# overfitted, logits of tens) the program's own rounding of its logits to
# bfloat16 grows with them and the ratio fell to 5.4: no K held there, which
# is why the state compared is the seeded one.  Read again for another
# architecture with ``tests/calibrate_forward_check.py --workload <cell>``.
COARSE_RATIO_K = 3.9


def model_config(config: Dict[str, Any]) -> Any:
    import jax.numpy as jnp

    from torchft_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        ffn_hidden=config["intermediate_size"],
        rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"],
        max_seq_len=config["max_position_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["torch_dtype"]],
    )


def model(config: Dict[str, Any]) -> Any:
    from torchft_tpu.models.llama import Llama

    return Llama(model_config(config))


def shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes ``flops.py`` and ``reference.py`` count from, and what the
    readers find under ``sources["shapes"]``."""
    return dict(
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        ffn_hidden=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"],
    )


def token_nll(host_params: Any, tokens: Any, targets: Any, config: Dict[str, Any]) -> Any:
    return reference.token_nll(host_params, tokens, targets, shapes(config))


def num_params(config: Dict[str, Any]) -> int:
    return counting.num_params(shapes(config))


def vocab(config: Dict[str, Any]) -> int:
    """The batch's token ids are drawn below it."""
    return config["vocab_size"]


class llama_flops:
    """``ftbench/flops.py``'s counting under the three names every
    architecture's class has: what the folded readers call
    (``sources["architecture"].flops``)."""

    @staticmethod
    def is_mine(s: Dict[str, Any]) -> bool:
        """Whether a cell's shapes are this architecture's."""
        return "rope_theta" in (s or {}) and "ffn_hidden" in s

    @staticmethod
    def train_flops_per_token(s: Dict[str, Any], seq: int) -> float:
        return counting.train_flops_per_token(s, seq)

    @staticmethod
    def flash_step(s: Dict[str, Any], rows: float, seq: int):
        """(operations, bytes) of the causal attention of one step on ONE chip's
        ``rows`` sequences (a group of several chips shares its rows out)."""
        return counting.flash_step_flops(s, rows, seq), counting.flash_step_bytes(s, rows, seq)


# the ONE name the folded readers find the class by (``step_mfu_pct`` and
# ``flash_roofline``; README.md, "An architecture"); ``ftbench/flops.py`` is
# ``counting`` here
flops = llama_flops
