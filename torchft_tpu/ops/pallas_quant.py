"""Pallas TPU kernels: fused rowwise int8 / fp8 quantize/dequantize.

The reference fuses fp8 quantization into triton kernels so quantized
collectives never materialize intermediate float copies
(``torchft/quantization.py:44-686``, CUDA; fp8e4nv on SM90+, int8 fallback
``quantization.py:30-41``).  The TPU equivalent lives here: gradients are
quantized ON DEVICE before leaving HBM, so the host (and then DCN) moves
1-byte payload + f32 rowwise scales — ~4x fewer bytes off-chip, which is
the dominant cost of the replica-dimension sync.

Two wire kinds, matching the host format (``torchft_tpu/quantization.py``):

- ``int8``: scale = absmax/127, uniform grid;
- ``fp8``: float8_e4m3fn, scale = absmax/448 — more dynamic range within a
  row at the cost of non-uniform spacing (the reference's format).

Layout: flat float input viewed as rows of ``row_size`` (last row padded);
``row_size`` is a multiple of 128 (lane width) and rows are processed in
blocks of 32 sublanes to satisfy 1-byte tiling ((32, 128) min tile).

Off-TPU the same math runs as plain jnp (still jittable) — Pallas on CPU is
interpreter-only, so tests exercise the jnp path plus ``interpret=True``
equivalence on tiny shapes.  On TPU, fp8 Mosaic support depends on the
chip generation; a one-shot compile probe (:func:`pallas_verdict`) sends
fp8 to the jnp path (still fused device code, XLA-compiled) when the kernel
can't lower, and says so once at WARNING.
"""

from __future__ import annotations

import functools
import logging
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)

ROW_SIZE = 1024  # multiple of the 128-lane width
BLOCK_ROWS = 32  # 1-byte min tile sublane count

INT8 = "int8"
FP8 = "fp8"
FP8_MAX = 448.0  # float8_e4m3fn max magnitude


def _wire_jnp_dtype(kind: str):
    if kind == INT8:
        return jnp.int8
    if kind == FP8:
        return jnp.float8_e4m3fn
    raise ValueError(f"unknown wire kind {kind!r}")


def _pad_to_rows(flat: jax.Array, row_size: int) -> Tuple[jax.Array, int]:
    n = flat.shape[0]
    rows = max(1, -(-n // row_size))
    # pad rows to a BLOCK_ROWS multiple so the grid divides evenly
    rows = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    padded = jnp.zeros((rows * row_size,), dtype=jnp.float32)
    padded = padded.at[:n].set(flat.astype(jnp.float32))
    return padded.reshape(rows, row_size), rows


def _quant_math(x: jax.Array, kind: str = INT8) -> Tuple[jax.Array, jax.Array]:
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    if kind == INT8:
        scale = absmax / 127.0
        safe = jnp.where(scale > 0, scale, 1.0)
        q = jnp.clip(jnp.round(x / safe), -127, 127).astype(jnp.int8)
    else:
        scale = absmax / FP8_MAX
        safe = jnp.where(scale > 0, scale, 1.0)
        q = jnp.clip(x / safe, -FP8_MAX, FP8_MAX).astype(
            _wire_jnp_dtype(kind)
        )
    return q, scale


def _quant_kernel(x_ref, q_ref, s_ref, *, kind: str):
    x = x_ref[:].astype(jnp.float32)
    q, scale = _quant_math(x, kind)
    q_ref[:] = q
    s_ref[:] = scale


def _dequant_kernel(q_ref, s_ref, out_ref):
    out_ref[:] = q_ref[:].astype(jnp.float32) * s_ref[:]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# kind -> None when Mosaic compiles all three kernels on this chip, else the
# compiler's message.  Filled once per process by :func:`pallas_verdict`.
_VERDICTS: Dict[str, Optional[str]] = {}
_VERDICT_LOCK = threading.Lock()


def _probe_compile(kind: str) -> Optional[str]:
    """Compile the quantize store, the structurally different reduce
    ([w, rows, R] wire loads + multiply) and the dequant load-with-multiply
    for ``kind``: each can fail independently, and all three dispatchers
    share the verdict."""
    wire = _wire_jnp_dtype(kind)
    x = jax.ShapeDtypeStruct((BLOCK_ROWS * ROW_SIZE,), jnp.float32)
    qs = jax.ShapeDtypeStruct((2, BLOCK_ROWS, ROW_SIZE), wire)
    sc = jax.ShapeDtypeStruct((2, BLOCK_ROWS, 1), jnp.float32)
    try:
        jax.jit(
            functools.partial(
                _pallas_quantize, row_size=ROW_SIZE, kind=kind, interpret=False
            )
        ).lower(x).compile()
        jax.jit(
            functools.partial(_pallas_reduce, kind=kind, interpret=False)
        ).lower(qs, sc).compile()
        jax.jit(functools.partial(_pallas_dequant, interpret=False)).lower(
            jax.ShapeDtypeStruct(qs.shape[1:], wire),
            jax.ShapeDtypeStruct(sc.shape[1:], jnp.float32),
        ).compile()
    except Exception as e:  # noqa: BLE001 — the verdict carries the message
        return f"{type(e).__name__}: {e}"
    return None


def pallas_verdict(kind: str) -> Optional[str]:
    """Can this chip's Mosaic compile the ``kind`` kernels?  None when it
    can, else the compiler's message (fp8 conversion support varies by TPU
    generation).  Probed once per process on a TPU backend and logged once
    at WARNING when negative; published only AFTER every probe finishes
    (under a lock), so concurrent collectives never see a provisional
    answer."""
    with _VERDICT_LOCK:
        if kind not in _VERDICTS:
            _VERDICTS[kind] = message = _probe_compile(kind)
            if message is not None:
                logger.warning(
                    "Pallas %s quantization kernels do not compile on %s: %s",
                    kind,
                    jax.devices()[0].device_kind,
                    message,
                )
        return _VERDICTS[kind]


def _use_pallas(kind: str, interpret: bool) -> bool:
    """Dispatch shared by the three public entry points.  Off TPU the jnp
    math is the only compiled path the backend has.  On TPU a negative
    verdict sends fp8 to XLA-compiled jnp (logged by :func:`pallas_verdict`
    — an older chip generation, not a bug); int8 lowers on every generation,
    so a negative int8 verdict is a kernel bug and the caller fails."""
    if interpret:
        return True
    if not _on_tpu():
        return False
    message = pallas_verdict(kind)
    if message is not None and kind == INT8:
        raise RuntimeError(
            f"Pallas int8 quantization kernels failed to compile: {message}"
        )
    return message is None


def _pallas_quantize(
    x2d_flat: jax.Array, row_size: int, kind: str, interpret: bool
) -> Tuple[jax.Array, jax.Array]:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x, rows = _pad_to_rows(x2d_flat, row_size)
    grid = (rows // BLOCK_ROWS,)
    return pl.pallas_call(
        functools.partial(_quant_kernel, kind=kind),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (BLOCK_ROWS, row_size), lambda i: (i, 0), memory_space=pltpu.VMEM
            )
        ],
        out_specs=[
            pl.BlockSpec(
                (BLOCK_ROWS, row_size), lambda i: (i, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec((BLOCK_ROWS, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, row_size), _wire_jnp_dtype(kind)),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("row_size", "kind", "interpret"))
def quantize_rowwise_device(
    flat: jax.Array,
    row_size: int = ROW_SIZE,
    kind: str = INT8,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """flat float [n] → (wire payload [rows, row_size], f32 scales
    [rows, 1]).

    Jittable; on TPU runs as a fused Pallas kernel (one HBM read, 1-byte +
    scales write), elsewhere — or when the chip can't lower the wire dtype
    — as plain jnp.
    """
    if not _use_pallas(kind, interpret):
        x, _rows = _pad_to_rows(flat, row_size)
        return _quant_math(x, kind)
    return _pallas_quantize(flat, row_size, kind, interpret)


def _reduce_kernel(qs_ref, s_ref, q_ref, out_s_ref, *, kind: str):
    # dequant-sum-requant in one VMEM-resident pass (the reference's
    # fused_reduce_fp8, torchft/quantization.py:638): qs [w, B, R] wire,
    # scales [w, B, 1] f32 -> requantized (q [B, R], scales [B, 1])
    total = jnp.sum(
        qs_ref[:].astype(jnp.float32) * s_ref[:], axis=0
    )
    q, scale = _quant_math(total, kind)
    q_ref[:] = q
    out_s_ref[:] = scale


def _pallas_reduce(
    qs: jax.Array, scales: jax.Array, kind: str, interpret: bool
) -> Tuple[jax.Array, jax.Array]:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w, rows, row_size = qs.shape
    # rows were padded to BLOCK_ROWS by the quantizer; guard anyway
    assert rows % BLOCK_ROWS == 0, rows
    grid = (rows // BLOCK_ROWS,)
    return pl.pallas_call(
        functools.partial(_reduce_kernel, kind=kind),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (w, BLOCK_ROWS, row_size),
                lambda i: (0, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (w, BLOCK_ROWS, 1), lambda i: (0, i, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (BLOCK_ROWS, row_size), lambda i: (i, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec((BLOCK_ROWS, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, row_size), _wire_jnp_dtype(kind)),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qs, scales)


@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def reduce_quantized_device(
    qs: jax.Array,
    scales: jax.Array,
    kind: str = INT8,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused dequant-sum-requant of ``w`` quantized contributions ON DEVICE:
    qs wire [w, rows, row_size], scales f32 [w, rows, 1] → (wire [rows,
    row_size], f32 [rows, 1]) of the float32 sum.

    The host ships w 1-byte shards in, gets one 1-byte shard back — float32
    never crosses the PCIe/HBM boundary, which is the point of the
    reference's in-kernel reduce.  Off-TPU the same math runs as jnp.
    """
    if scales.ndim == 2:
        scales = scales[:, :, None]
    if not _use_pallas(kind, interpret):
        total = jnp.sum(qs.astype(jnp.float32) * scales, axis=0)
        return _quant_math(total, kind)
    return _pallas_reduce(qs, scales, kind, interpret)


def _pallas_dequant(
    q: jax.Array, scales: jax.Array, interpret: bool
) -> jax.Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, row_size = q.shape
    grid = (rows // BLOCK_ROWS,)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (BLOCK_ROWS, row_size), lambda i: (i, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec((BLOCK_ROWS, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (BLOCK_ROWS, row_size), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((rows, row_size), jnp.float32),
        interpret=interpret,
    )(q, scales)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def dequantize_rowwise_device(
    q: jax.Array, scales: jax.Array, n: int, interpret: bool = False
) -> jax.Array:
    """(wire [rows, row_size], f32 [rows, 1]) → float32 [n].  The wire kind
    is carried by ``q.dtype``."""
    kind = INT8 if q.dtype == jnp.int8 else FP8
    if not _use_pallas(kind, interpret):
        out = q.astype(jnp.float32) * scales
        return out.reshape(-1)[:n]
    out = _pallas_dequant(q, scales, interpret)
    return out.reshape(-1)[:n]


# int8-named surface (round-1 API), kept for callers and parity docs
def quantize_int8_rowwise_device(
    flat: jax.Array, row_size: int = ROW_SIZE, interpret: bool = False
) -> Tuple[jax.Array, jax.Array]:
    return quantize_rowwise_device(flat, row_size, INT8, interpret)


def dequantize_int8_rowwise_device(
    q: jax.Array, scales: jax.Array, n: int, interpret: bool = False
) -> jax.Array:
    return dequantize_rowwise_device(q, scales, n, interpret)
