"""Degraded-mode re-lowering: keep a wounded replica contributing.

Today a single dead device fails its whole replica group; Nonuniform
Tensor Parallelism (arxiv 2504.06095) and SPARe (arxiv 2603.00357) show
that re-shaping the inner parallelism onto the survivors turns cliff-edge
fleet shrink into graceful capacity decay.  This module is the in-replica
half of that design (the fleet half — capacity-weighted outer reduce,
data-shard rescale, the lighthouse's wound→swap→evict ladder — lives in
``manager.py`` / ``collectives.py`` / ``data.py`` / ``lighthouse.py``):

1. :func:`plan_surviving` — pick the best tp×fsdp×pp×ep layout for the
   surviving device count.  Candidates are every factorization of every
   ``m <= n_surviving`` (most devices first); when a model is given each
   candidate is dry-run through the existing :mod:`rehearsal` layer
   (divisibility + sharding-aware HBM fit, optional abstract-mesh
   lowering) and the first plan that
   rehearses clean wins.  The plan's ``capacity`` fraction
   (``devices_used / original_devices``) is exactly what the Manager
   advertises on the wire-v5 capacity tail.
2. :func:`relower_hsdp_trainer` — apply a plan to a live
   :class:`~torchft_tpu.parallel.hsdp.HSDPTrainer`-shaped object: rebuild
   the mesh on the survivors, ``device_put`` params and optimizer state
   into the new layout (the reshard), and recompile the grad/update
   steps.  Call between ``Manager.begin_relower()`` and
   ``Manager.complete_relower(plan.capacity)`` so a crash mid-reshard can
   never vote commit.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)

# Axes a degraded re-lower may redistribute over, innermost-preference
# order: fsdp first (parameter sharding buys back the HBM the lost device
# held), then tp, then ep/pp.  ``dp``/``sp`` follow the chosen plan only
# when the original mesh used them; the default planner leaves them at 1.
RELOWER_AXES: Tuple[str, ...] = ("fsdp", "tp", "ep", "pp")


@dataclass(frozen=True)
class DegradedPlan:
    """One surviving-device layout: the mesh axes to re-lower onto, how
    many devices it uses, and the capacity fraction to advertise."""

    mesh_axes: Dict[str, int] = field(default_factory=dict)
    devices_used: int = 0
    original_devices: int = 0
    report: Optional[Any] = None  # RehearsalReport when a model was given

    @property
    def capacity(self) -> float:
        if self.original_devices <= 0:
            return 1.0
        return self.devices_used / self.original_devices


def _factorizations(m: int, axes: Sequence[str]) -> List[Dict[str, int]]:
    """Every assignment of factors of ``m`` to ``axes`` (product == m)."""
    if not axes:
        return [{}] if m == 1 else []
    head, rest = axes[0], axes[1:]
    out: List[Dict[str, int]] = []
    f = 1
    while f <= m:
        if m % f == 0:
            for tail in _factorizations(m // f, rest):
                out.append({head: f, **tail})
        f += 1
    return out


def surviving_layouts(
    n_surviving: int, axes: Sequence[str] = RELOWER_AXES
) -> List[Dict[str, int]]:
    """Candidate layouts for a wounded replica, best-first: most devices
    used, then the most fsdp (parameter sharding buys back the dead
    device's HBM share), then the flattest split.  Deterministic — every
    observer ranks the same plan first."""
    candidates: List[Dict[str, int]] = []
    for m in range(n_surviving, 0, -1):
        candidates.extend(_factorizations(m, axes))

    def _key(layout: Dict[str, int]) -> tuple:
        used = 1
        for v in layout.values():
            used *= v
        return (
            -used,
            -layout.get("fsdp", 1),
            -layout.get("tp", 1),
            tuple(sorted(layout.items())),
        )

    return sorted(candidates, key=_key)


def plan_surviving(
    n_surviving: int,
    original_devices: int,
    model: Any = None,
    tx: Any = None,
    batch: int = 8,
    seq: int = 2048,
    chip: str = "v5p",
    axes: Sequence[str] = RELOWER_AXES,
    lower: bool = False,
) -> DegradedPlan:
    """Pick the best layout for ``n_surviving`` of ``original_devices``
    devices.

    With a ``model`` (and ``tx``), each candidate is validated through
    :func:`torchft_tpu.parallel.rehearsal.rehearse` — axis divisibility
    and the sharding-aware HBM estimate must pass (plus abstract-mesh
    lowering when ``lower=True``); the first candidate that rehearses
    clean wins.  Without a model the structural ranking alone decides
    (the drill / thread-plane path).  Raises when no layout fits — the
    caller should then let the replica die normally (eviction beats
    training on a layout that cannot hold the model)."""
    if n_surviving < 1:
        raise ValueError(
            f"no surviving devices to re-lower onto ({n_surviving})"
        )
    if n_surviving > original_devices:
        raise ValueError(
            f"survivors ({n_surviving}) exceed the original device count "
            f"({original_devices})"
        )
    candidates = surviving_layouts(n_surviving, axes)
    if model is None:
        layout = candidates[0]
        used = 1
        for v in layout.values():
            used *= v
        return DegradedPlan(
            mesh_axes=dict(layout),
            devices_used=used,
            original_devices=original_devices,
        )
    from torchft_tpu.parallel.rehearsal import rehearse

    last_report = None
    for layout in candidates:
        used = 1
        for v in layout.values():
            used *= v
        report = rehearse(
            model,
            tx,
            dict(layout),
            batch=batch,
            seq=seq,
            name=f"degraded_{used}of{original_devices}",
            chip=chip,
            lower=lower,
        )
        last_report = report
        if report.ok:
            return DegradedPlan(
                mesh_axes=dict(layout),
                devices_used=used,
                original_devices=original_devices,
                report=report,
            )
    raise RuntimeError(
        "no surviving-device layout rehearses clean for "
        f"{n_surviving}/{original_devices} devices (last: "
        f"{last_report.summary() if last_report else 'none'})"
    )


def chaos_device_loss() -> int:
    """Process-plane chaos injection (``chaos.Failure.DEVICE_LOSS``): how
    many of this replica's devices "died" before startup, from
    ``TORCHFT_CHAOS_DEVICE_LOSS`` in the group's spawn env.  0 when the
    knob is unset — the normal case."""
    from torchft_tpu import knobs

    return max(0, knobs.get_int("TORCHFT_CHAOS_DEVICE_LOSS", 0))


def startup_surviving_devices(devices: Sequence[Any]) -> List[Any]:
    """Apply the process-plane device-loss chaos knob at startup: the last
    N devices are treated as dead (at least one always survives).  Workers
    that build their mesh from this list come up wounded and should plan
    via :func:`plan_surviving` + advertise ``plan.capacity``."""
    lost = chaos_device_loss()
    devices = list(devices)
    if lost <= 0:
        return devices
    survivors = max(1, len(devices) - lost)
    logger.warning(
        "chaos: %d of %d devices lost before startup — coming up wounded",
        len(devices) - survivors,
        len(devices),
    )
    return devices[:survivors]


def reshard_params(params: Any, specs: Any, mesh: Any) -> Any:
    """``device_put`` a param tree into its PartitionSpec layout on a new
    (smaller) mesh — the reshard half of a re-lower.  Values are moved,
    never recomputed: the wounded replica keeps exactly the state it had,
    only the placement changes."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.tree_util.tree_map(
        lambda leaf, sh: jax.device_put(leaf, sh), params, shardings
    )


def _reshard_opt_state(opt_state: Any, params: Any, mesh: Any) -> Any:
    """Reshard optimizer state onto ``mesh``: params-mirroring leaves
    (momentum, Adam mu/nu — matched by the shared suffix+shape rule)
    inherit their freshly-placed param's sharding, everything else
    replicates — the same rule ``hsdp.sharded_opt_init`` pins at init."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from torchft_tpu.parallel.hsdp import match_param_by_suffix

    params_paths = {
        tuple(path): (tuple(leaf.shape), leaf.sharding)
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
        if isinstance(leaf, jax.Array)
    }

    def _place(path: Tuple, leaf: Any) -> Any:
        sharding = match_param_by_suffix(
            path, getattr(leaf, "shape", ()), params_paths
        )
        if sharding is None:
            sharding = NamedSharding(mesh, P())
        return jax.device_put(leaf, sharding)

    leaves, treedef = jax.tree_util.tree_flatten_with_path(opt_state)
    return jax.tree_util.tree_unflatten(
        treedef, [_place(p, leaf) for p, leaf in leaves]
    )


def relower_hsdp_trainer(
    trainer: Any,
    surviving_devices: Sequence[Any],
    plan: Optional[DegradedPlan] = None,
) -> DegradedPlan:
    """Re-lower a live HSDP trainer onto ``surviving_devices``.

    ``trainer`` is anything HSDPTrainer-shaped: ``model`` / ``tx`` /
    ``mesh`` / ``holder`` (params + opt_state) plus the compiled
    ``_grad_step`` / ``_update_step`` slots.  Sequencing contract: call
    ``manager.begin_relower()`` first and ``manager.complete_relower(
    plan.capacity)`` after this returns — a crash anywhere in between
    reads as "never voted commit"."""
    from torchft_tpu.parallel.hsdp import (
        fsdp_shardings,
        make_grad_step,
        make_update_step,
    )
    from torchft_tpu.parallel.mesh import make_mesh

    original = int(trainer.mesh.devices.size)
    if plan is None:
        plan = plan_surviving(
            len(surviving_devices), original_devices=original
        )
    if plan.devices_used > len(surviving_devices):
        raise ValueError(
            f"plan needs {plan.devices_used} devices, only "
            f"{len(surviving_devices)} survive"
        )
    new_mesh = make_mesh(
        devices=list(surviving_devices)[: plan.devices_used],
        **plan.mesh_axes,
    )
    params_specs = trainer.model.param_specs()
    trainer.holder["params"] = reshard_params(
        trainer.holder["params"], params_specs, new_mesh
    )
    trainer.holder["opt_state"] = _reshard_opt_state(
        trainer.holder["opt_state"], trainer.holder["params"], new_mesh
    )
    trainer.mesh = new_mesh
    # recompile for the new layout (fsdp_shardings re-attaches the mesh to
    # the model as a side effect — both step builders funnel through it)
    fsdp_shardings(trainer.model, new_mesh)
    trainer._grad_step = make_grad_step(trainer.model, new_mesh)
    trainer._update_step = make_update_step(trainer.model, trainer.tx, new_mesh)
    logger.warning(
        "re-lowered onto %d/%d devices (%s) — capacity %.3f",
        plan.devices_used,
        plan.original_devices or original,
        plan.mesh_axes,
        plan.capacity,
    )
    return plan
