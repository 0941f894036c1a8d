"""Scale dress-rehearsal: validate pod-scale configs without the pod.

The reference claims Llama-3 8B/70B fault-tolerant HSDP at cluster scale
(``/root/reference/README.md:62-69``) but has no way to check a config
short of burning the cluster.  On TPU the XLA compilation model lets us do
better: ``jax.jit(...).trace(...).lower(lowering_platforms=("tpu",))`` over
a :class:`jax.sharding.AbstractMesh` traces and SPMD-partitions the REAL
train step for the REAL pod shape on any host, with zero devices — the
full v5p-256 70B program is validated (tracing, sharding propagation,
divisibility, collective layout) in seconds on a CPU box.

What :func:`rehearse` checks per config:

1. **Axis divisibility** — every sharded parameter dim must divide by the
   product of the mesh axes on it (a violation compiles into padded
   shards or fails partitioning at cluster bring-up time).
2. **HBM fit** — per-device bytes for params + grads + optimizer state
   (sharding-aware, optimizer leaves inherit their param's spec exactly
   like ``hsdp.sharded_opt_init``) + a documented activation estimate,
   against the chip's HBM capacity.
3. **Lowering** — the HSDP grad step and optax update step actually
   trace + SPMD-lower for the TPU platform over the abstract mesh.

Run ``python -m torchft_tpu.parallel.rehearsal`` to print the BASELINE
config 2/3/5 table (the one recorded in ``docs/SCALE_REHEARSAL.md``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AbstractMesh, NamedSharding, PartitionSpec as P

# Per-chip HBM capacity (bytes).  v5p: 95 GB HBM2e per chip; v5e: 16 GB;
# v4: 32 GB; v6e: 32 GB.  Source: public TPU system documentation.
CHIP_HBM_BYTES: Dict[str, float] = {
    "v5p": 95e9,
    "v5e": 16e9,
    "v4": 32e9,
    "v6e": 32e9,
}


@dataclass
class RehearsalReport:
    name: str
    mesh_axes: Dict[str, int]
    n_devices: int
    chip: str
    ok: bool
    divisibility_errors: List[str] = field(default_factory=list)
    bytes_per_device: Dict[str, float] = field(default_factory=dict)
    hbm_bytes: float = 0.0
    hbm_frac: float = 0.0
    lowered_grad: bool = False
    lowered_update: bool = False
    remat: str = "none"
    error: Optional[str] = None

    def summary(self) -> str:
        gb = {k: f"{v / 1e9:.1f}" for k, v in self.bytes_per_device.items()}
        status = "OK" if self.ok else "FAIL"
        return (
            f"{self.name}: {status} mesh={self.mesh_axes} "
            f"({self.n_devices} {self.chip} chips) "
            f"GB/device: params={gb.get('params')} grads={gb.get('grads')} "
            f"opt={gb.get('opt_state')} acts~={gb.get('activations_est')} "
            f"total={gb.get('total')} of {self.hbm_bytes / 1e9:.0f} "
            f"({self.hbm_frac:.0%})"
            + (f" error={self.error}" if self.error else "")
            + (
                f" divisibility={self.divisibility_errors}"
                if self.divisibility_errors
                else ""
            )
        )


def _axes_of(spec_entry: Any) -> Tuple[str, ...]:
    """Mesh axes named by one PartitionSpec dim entry (str | tuple | None)."""
    if spec_entry is None:
        return ()
    if isinstance(spec_entry, str):
        return (spec_entry,)
    return tuple(spec_entry)


def _leaf_report(
    path: str,
    shape: Tuple[int, ...],
    itemsize: int,
    spec: P,
    mesh_axes: Dict[str, int],
    errors: List[str],
) -> float:
    """Per-device bytes for one leaf; records divisibility violations."""
    denom = 1
    for d, entry in enumerate(spec):
        factor = 1
        for axis in _axes_of(entry):
            factor *= mesh_axes.get(axis, 1)
        if factor > 1:
            if d >= len(shape) or shape[d] % factor:
                errors.append(
                    f"{path}: dim {d} ({shape[d] if d < len(shape) else '?'})"
                    f" not divisible by {entry}={factor}"
                )
                continue
            denom *= factor
    return float(np.prod(shape)) * itemsize / denom


def _spec_tree(model: Any) -> Any:
    return model.param_specs()


def _opt_specs(
    params_shapes: Any, param_specs: Any, tx: Any
) -> Tuple[Any, Any]:
    """(opt_state eval_shapes, opt_state PartitionSpecs).  Leaves mirroring
    a parameter (matched by the shared ``hsdp.match_param_by_suffix`` rule)
    inherit its spec; the rest replicate."""
    from torchft_tpu.parallel.hsdp import match_param_by_suffix

    param_paths = {
        tuple(p): (tuple(l.shape), s)
        for (p, l), s in zip(
            jax.tree_util.tree_flatten_with_path(params_shapes)[0],
            jax.tree_util.tree_leaves(
                param_specs, is_leaf=lambda x: isinstance(x, P)
            ),
        )
    }
    opt_shapes = jax.eval_shape(tx.init, params_shapes)

    def _spec_for(path, leaf):
        spec = match_param_by_suffix(path, leaf.shape, param_paths)
        return spec if spec is not None else P()

    leaves, treedef = jax.tree_util.tree_flatten_with_path(opt_shapes)
    specs = jax.tree_util.tree_unflatten(
        treedef, [_spec_for(p, l) for p, l in leaves]
    )
    return opt_shapes, specs


def _activation_estimate(
    config: Any, batch: int, seq: int, mesh_axes: Dict[str, int]
) -> float:
    """Rough per-device activation bytes for the train step.

    With per-layer remat (``config.remat``) the backward keeps (a) the
    residual stream at every layer boundary (``n_layers × B_loc × S_loc ×
    dim``, bf16) and (b) one layer's recompute working set (qkv/o
    projections + ffn intermediates).  Without remat, every layer's
    intermediates stay live for the backward.  Logits (``B_loc × S_loc ×
    vocab_loc``, fp32) dominate the loss head either way.  Assumes flash
    attention (no materialized ``B×H×S×S`` score matrices).  This is an
    estimate — treat < 80% HBM as "fits".
    """
    # batch shards over BOTH dp and fsdp (see ``Llama.batch_specs``)
    bp = mesh_axes.get("dp", 1) * mesh_axes.get("fsdp", 1)
    sp = mesh_axes.get("sp", 1)
    tp = mesh_axes.get("tp", 1)
    b_loc = max(1, batch // bp)
    s_loc = max(1, seq // sp)
    bf16 = 2
    L = config.n_layers
    boundaries = L * b_loc * s_loc * config.dim * bf16
    qkv = 4 * b_loc * s_loc * (config.n_heads // tp) * config.head_dim * bf16
    ffn = 3 * b_loc * s_loc * (config.ffn_hidden // tp) * bf16
    logits = b_loc * s_loc * (config.vocab_size // tp) * 4
    # per remat policy (Llama.effective_remat_mode — the remat_mode knob,
    # not just the legacy bool): which per-layer tensors stay live for the
    # backward vs one recompute working set
    mode = getattr(config, "effective_remat_mode", None) or (
        "layer" if getattr(config, "remat", False) else "none"
    )
    live = {
        "none": L * (qkv + ffn),
        "layer": 2 * (qkv + ffn),
        "attn": L * ffn + 2 * qkv,  # attention side recomputed
        "ffn": L * qkv + 2 * ffn,  # FFN side recomputed
    }[mode]
    return float(boundaries + live + logits)


def rehearse(
    model: Any,
    tx: Any,
    mesh_axes: Dict[str, int],
    batch: int,
    seq: int,
    name: str = "config",
    chip: str = "v5p",
    lower: bool = True,
) -> RehearsalReport:
    """Validate one (model, optimizer, mesh, workload) config abstractly."""
    n_devices = int(np.prod(list(mesh_axes.values())))
    report = RehearsalReport(
        name=name,
        mesh_axes=dict(mesh_axes),
        n_devices=n_devices,
        chip=chip,
        ok=False,
        hbm_bytes=CHIP_HBM_BYTES[chip],
        remat=getattr(model.config, "effective_remat_mode", "none"),
    )
    cfg = model.config
    errors = report.divisibility_errors

    # batch/seq divisibility over data axes (batch shards over dp × fsdp)
    bp = mesh_axes.get("dp", 1) * mesh_axes.get("fsdp", 1)
    if batch % bp:
        errors.append(f"batch {batch} % dp*fsdp {bp}")
    if seq % mesh_axes.get("sp", 1):
        errors.append(f"seq {seq} % sp {mesh_axes['sp']}")

    params_shapes = jax.eval_shape(
        lambda k: model.init(k), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    param_specs = _spec_tree(model)

    # params + grads, sharding-aware
    p_leaves = list(
        zip(
            [
                "/".join(str(getattr(k, "key", k)) for k in p)
                for p, _ in jax.tree_util.tree_flatten_with_path(params_shapes)[0]
            ],
            jax.tree_util.tree_leaves(params_shapes),
            jax.tree_util.tree_leaves(
                param_specs, is_leaf=lambda x: isinstance(x, P)
            ),
        )
    )
    params_b = sum(
        _leaf_report(
            path, tuple(l.shape), l.dtype.itemsize, spec, mesh_axes, errors
        )
        for path, l, spec in p_leaves
    )
    opt_shapes, opt_specs = _opt_specs(params_shapes, param_specs, tx)
    opt_errors: List[str] = []
    opt_b = sum(
        _leaf_report(
            "opt", tuple(l.shape), l.dtype.itemsize, spec, mesh_axes, opt_errors
        )
        for l, spec in zip(
            jax.tree_util.tree_leaves(opt_shapes),
            jax.tree_util.tree_leaves(
                opt_specs, is_leaf=lambda x: isinstance(x, P)
            ),
        )
    )
    acts_b = _activation_estimate(cfg, batch, seq, mesh_axes)
    total = params_b * 2 + opt_b + acts_b  # grads mirror params
    report.bytes_per_device = {
        "params": params_b,
        "grads": params_b,
        "opt_state": opt_b,
        "activations_est": acts_b,
        "total": total,
    }
    report.hbm_frac = total / report.hbm_bytes

    if lower and not errors:
        import os

        prev_mesh = getattr(model, "mesh", None)
        prev_env = os.environ.get("TORCHFT_FLASH_PLATFORM")
        try:
            mesh = AbstractMesh(
                tuple(mesh_axes.values()), tuple(mesh_axes.keys())
            )
            # lower the program that will RUN on the pod: attach the mesh
            # and assume the TPU platform so kernel dispatch picks the
            # sharded Mosaic flash path, not the host's naive fallback
            model.mesh = mesh
            os.environ["TORCHFT_FLASH_PLATFORM"] = "tpu"
            params_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s),
                param_specs,
                is_leaf=lambda x: isinstance(x, P),
            )
            params_in = jax.tree_util.tree_map(
                lambda l, sh: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sh),
                params_shapes,
                params_sh,
            )
            tok_spec, _ = model.batch_specs()
            tok = jax.ShapeDtypeStruct(
                (batch, seq), jnp.int32, sharding=NamedSharding(mesh, tok_spec)
            )

            def _grad(params, b):
                return jax.value_and_grad(model.loss)(params, b)

            jax.jit(
                _grad,
                out_shardings=(NamedSharding(mesh, P()), params_sh),
            ).trace(params_in, (tok, tok)).lower(lowering_platforms=("tpu",))
            report.lowered_grad = True

            import optax

            opt_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s),
                opt_specs,
                is_leaf=lambda x: isinstance(x, P),
            )
            opt_in = jax.tree_util.tree_map(
                lambda l, sh: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sh),
                opt_shapes,
                opt_sh,
            )

            def _update(params, opt_state, grads):
                updates, opt_state = tx.update(grads, opt_state, params)
                return optax.apply_updates(params, updates), opt_state

            jax.jit(_update).trace(params_in, opt_in, params_in).lower(
                lowering_platforms=("tpu",)
            )
            report.lowered_update = True
        except Exception as e:  # noqa: BLE001 — the report IS the output
            report.error = f"{type(e).__name__}: {e}"
        finally:
            model.mesh = prev_mesh
            if prev_env is None:
                os.environ.pop("TORCHFT_FLASH_PLATFORM", None)
            else:
                os.environ["TORCHFT_FLASH_PLATFORM"] = prev_env

    report.ok = bool(
        not errors
        and not report.error
        and report.hbm_frac < 0.8
        and (not lower or (report.lowered_grad and report.lowered_update))
    )
    return report


def baseline_reports(lower: bool = True) -> List[RehearsalReport]:
    """BASELINE.json configs 2/3/5, with per-replica-group meshes.

    Device-count convention: "v5p-N" is read as N *chips* (one jax device
    per chip, megacore); the per-group mesh is total chips / replica
    groups.  Sequence length 8192 (Llama-3 native).
    """
    import dataclasses

    import optax

    from torchft_tpu.models.llama import Llama, llama3_8b, llama3_70b

    tx = optax.adamw(3e-4)
    reports = []
    # per-layer remat is how these configs actually run (and what
    # _activation_estimate models) — the lowered program must match the
    # HBM verdict, so rehearse the remat'd step, not the default
    remat = lambda cfg: dataclasses.replace(cfg, remat=True)  # noqa: E731
    # config 2: FT-DDP 8B, 4 replica groups on v5p-32 → 8 chips/group.
    # "DDP" inside a group = model replicated per chip won't fit 8B+Adam on
    # 95 GB alongside activations at batch 8; the TPU-native reading of
    # per-group DDP is fsdp-only sharding (pure ZeRO, no TP) — still one
    # allreduce-equivalent per step, params sharded.
    m8 = Llama(remat(llama3_8b()))
    reports.append(
        rehearse(
            m8, tx, {"dp": 1, "fsdp": 8, "tp": 1}, batch=8, seq=8192,
            name="config2_8b_ddp_v5p32_4groups", lower=lower,
        )
    )
    # config 3: HSDP 8B, v5p-64, 4 groups → 16 chips/group: fsdp=8 × tp=2
    reports.append(
        rehearse(
            m8, tx, {"dp": 1, "fsdp": 8, "tp": 2}, batch=16, seq=8192,
            name="config3_8b_hsdp_v5p64_4groups", lower=lower,
        )
    )
    # config 5: 70B HSDP, v5p-256, 4 groups → 64 chips/group: fsdp=16 × tp=4
    m70 = Llama(remat(llama3_70b()))
    reports.append(
        rehearse(
            m70, tx, {"dp": 1, "fsdp": 16, "tp": 4}, batch=16, seq=8192,
            name="config5_70b_hsdp_v5p256_4groups", lower=lower,
        )
    )
    return reports


def quant_kernel_reports() -> List[Dict[str, Any]]:
    """Lowering-level proof for the device quant kernels (round-4 verdict
    item 9), the twin of the flash-kernel check above: trace + TPU-lower
    every Pallas kernel in ``ops/pallas_quant`` — quantize, fused
    dequant-sum-requant reduce, dequantize — for both wire kinds.  Mosaic
    serializes into the lowered module, so a kernel whose program Mosaic
    cannot EXPRESS fails here on any host; whether a given chip generation
    can COMPILE the fp8 conversion ops still needs metal, which is what the
    runtime probe ``pallas_quant.pallas_verdict`` covers (reference twin:
    ``torchft/quantization.py:531-686``)."""
    import functools

    from torchft_tpu.ops import pallas_quant as pq

    rows: List[Dict[str, Any]] = []
    for kind in (pq.INT8, pq.FP8):
        wire = pq._wire_jnp_dtype(kind)
        cases = (
            (
                "quantize",
                functools.partial(
                    pq._pallas_quantize,
                    row_size=pq.ROW_SIZE,
                    kind=kind,
                    interpret=False,
                ),
                (
                    jax.ShapeDtypeStruct(
                        (pq.BLOCK_ROWS * pq.ROW_SIZE,), jnp.float32
                    ),
                ),
            ),
            (
                "reduce",
                functools.partial(pq._pallas_reduce, kind=kind, interpret=False),
                (
                    jax.ShapeDtypeStruct((2, pq.BLOCK_ROWS, pq.ROW_SIZE), wire),
                    jax.ShapeDtypeStruct((2, pq.BLOCK_ROWS, 1), jnp.float32),
                ),
            ),
            (
                "dequantize",
                functools.partial(pq._pallas_dequant, interpret=False),
                (
                    jax.ShapeDtypeStruct((pq.BLOCK_ROWS, pq.ROW_SIZE), wire),
                    jax.ShapeDtypeStruct((pq.BLOCK_ROWS, 1), jnp.float32),
                ),
            ),
        )
        for name, fn, args in cases:
            row: Dict[str, Any] = {"kernel": name, "kind": kind}
            try:
                jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
                row["lowered"] = True
            except Exception as e:  # noqa: BLE001 — the report IS the output
                row["lowered"] = False
                row["error"] = f"{type(e).__name__}: {e}"
            rows.append(row)
    return rows


def main() -> None:
    # the rehearsal is device-free: pin the CPU backend so tracing never
    # takes a chip — model code probes ``jax.default_backend()`` for kernel
    # dispatch during trace
    jax.config.update("jax_platforms", "cpu")
    for r in baseline_reports():
        print(r.summary())
    for row in quant_kernel_reports():
        status = "ok" if row["lowered"] else f"FAIL ({row.get('error')})"
        print(f"quant kernel {row['kernel']}[{row['kind']}]: {status}")


if __name__ == "__main__":
    main()
