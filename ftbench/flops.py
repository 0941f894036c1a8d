"""Peaks of the chips, and operations and bytes from shapes.

The peak table and the FLOPs-per-token arithmetic are copies of
``bench.py``'s (``_TPU_PEAKS``, the 6N + 12*L*dim*S rule); the originals are
listed in PERF.md for a later PR to delete.
"""

from __future__ import annotations

from typing import Dict

# per chip, keyed by jax's device_kind.  Source: Google Cloud documentation,
# "TPU v5e" system architecture page (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """An unknown kind is an error: a guessed peak makes a wrong share."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks known for device_kind {device_kind!r}; add it to "
            "ftbench/flops.py PEAKS with its source"
        )
    return PEAKS[device_kind]


def matmul_params(cfg: Dict) -> int:
    """Parameters that are matrix multiplications: all but the embedding
    table (a gather) and the norms.  The head projection counts."""
    dim, hd = cfg["dim"], cfg["dim"] // cfg["n_heads"]
    attn = dim * cfg["n_heads"] * hd * 2 + 2 * dim * cfg["n_kv_heads"] * hd
    ffn = 3 * dim * cfg["ffn_hidden"]
    return cfg["n_layers"] * (attn + ffn) + dim * cfg["vocab_size"]


def num_params(cfg: Dict) -> int:
    dim = cfg["dim"]
    norms = cfg["n_layers"] * 2 * dim + dim
    return matmul_params(cfg) + dim * cfg["vocab_size"] + norms


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward and backward: 6 per matmul parameter, and the attention score
    and value matmuls 12*L*dim*S (the full square, as PaLM's MFU counts it;
    causal masking halves what a kernel must do, not what the model is
    credited with).  Recomputed work is not counted."""
    return 6.0 * matmul_params(cfg) + 12.0 * cfg["n_layers"] * cfg["dim"] * seq


def mfu_pct(tokens_per_s_per_chip: float, cfg: Dict, seq: int, device_kind: str) -> float:
    return (
        100.0
        * tokens_per_s_per_chip
        * train_flops_per_token(cfg, seq)
        / peaks(device_kind)["bf16_flops"]
    )


def flash_step_flops(cfg: Dict, batch: int, seq: int) -> float:
    """Operations the causal attention of one training step NEEDS on one
    replica: forward 2 matmuls (QK^T, PV), backward 4 (dP, dV, dQ, dK; the
    recomputation of P is the kernel's choice and not credited), each
    2*S*S*D per head, halved by the causal mask."""
    hd = cfg["dim"] // cfg["n_heads"]
    per_matmul = 2.0 * seq * seq * hd * cfg["n_heads"] * batch * 0.5
    return cfg["n_layers"] * 6.0 * per_matmul


def flash_step_bytes(cfg: Dict, batch: int, seq: int, itemsize: int = 2) -> float:
    """Bytes the attention of one step must move at the least: forward reads
    q, k, v and writes o; backward reads q, k, v, o, do and writes dq, dk,
    dv (the lse rows are small and left out)."""
    hd = cfg["dim"] // cfg["n_heads"]
    q = batch * seq * cfg["n_heads"] * hd * itemsize
    kv = batch * seq * cfg["n_kv_heads"] * hd * itemsize
    fwd = 2 * q + 2 * kv
    bwd = 4 * q + 2 * kv + 2 * kv
    return cfg["n_layers"] * float(fwd + bwd)


def roofline_pct(flops: float, nbytes: float, seconds: float, device_kind: str) -> Dict[str, float]:
    """The least time the chip could take over the time it took, and which
    of the two bounds it."""
    p = peaks(device_kind)
    t_flops, t_bytes = flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"]
    return {
        "pct": 100.0 * max(t_flops, t_bytes) / seconds,
        "bound": "compute" if t_flops >= t_bytes else "memory",
    }
