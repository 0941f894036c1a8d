"""HSDP composition: FSDP/TP over ICI inside a replica × FT-DDP over DCN.

The reference composes FSDP2 ``fully_shard`` inside each replica with a
torchft allreduce hook on the replica dimension
(``fsdp_test.py:55-73``, torchtitan per ``README.md:62-69``).  The jax-native
equivalent:

- **inner**: parameters/optimizer state sharded with ``NamedSharding`` over
  the replica group's mesh axes (``fsdp``/``tp``); XLA SPMD inserts the
  all-gathers/reduce-scatters over ICI.
- **outer**: after the compiled grad step, the Manager averages gradients
  across replica groups host-side over DCN — the replica count never enters
  the compiled program, so elastic membership can't trigger recompilation
  (SURVEY.md §7 hard part 1).

Multi-host note: when a replica group spans hosts (one process per host,
``group_rank`` = host index), gradients are non-fully-addressable jax
Arrays.  ``ddp._host_contribution`` ships only this host's unique
addressable shards over the per-``group_rank`` DCN ring (host h of every
replica group addresses the same logical region, so shard-local averaging
is exact) and rebuilds results with
``jax.make_array_from_single_device_arrays`` — the global array is never
materialized on one host.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchft_tpu.ddp import ft_allreduce
from torchft_tpu.manager import Manager
from torchft_tpu.obs.flight import FlightEvent
from torchft_tpu.obs.spans import part, span as obs_span


def fsdp_shardings(
    model: Any, mesh: Mesh
) -> Tuple[Any, Any]:
    """(param shardings, batch shardings) for a model exposing
    ``param_specs()`` / ``batch_specs()`` (e.g. :class:`models.llama.Llama`).

    Also attaches ``mesh`` to the model (last call wins): every HSDP entry
    point (``shard_init``/``make_grad_step``/``HSDPTrainer``) funnels
    through here, and the model's attention needs the mesh to dispatch the
    shard_map flash variant instead of silently taking the naive path.
    Consequence: one model object serves one mesh at a time — rebuild (or
    re-enter through this function) when the mesh changes, and don't drive
    a shared model over two meshes concurrently."""
    model.mesh = mesh
    param_specs = model.param_specs()
    params_sh = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        param_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    tok_spec, tgt_spec = model.batch_specs()
    batch_sh = (NamedSharding(mesh, tok_spec), NamedSharding(mesh, tgt_spec))
    return params_sh, batch_sh


def shard_init(model: Any, key: jax.Array, mesh: Mesh) -> Any:
    """Initialize params directly into their HSDP layout (jit + out_shardings
    so big models never materialize unsharded)."""
    params_sh, _ = fsdp_shardings(model, mesh)
    with mesh:
        return jax.jit(model.init, out_shardings=params_sh)(key)


def _reports(model: Any) -> bool:
    """Whether the model gives its own account of a step.  Such a model has
    ``objective(params, batch) -> (scalar, (signal, summary))``: the scalar
    a training step differentiates, the step's signal for every leaf of
    ``state_mask()`` (an empty list for a model without one) and
    ``summary``, a few float32 numbers made on the device inside the
    compiled gradient step (the experts' loads layer by layer, the index's
    loss: whatever ``summary_stats(summary)`` turns into the detail of a
    committed step's MOE_ROUTE flight event).  They come to the host WITH the
    loss, in one small array.  ``Llama`` has none of this and keeps
    ``loss``."""
    return hasattr(model, "objective")


def _state_mask(model: Any) -> Optional[List[bool]]:
    """Which leaves of ``params`` (in flatten order) are state the optimizer
    does not own, or None for a model that declares none.  A model with such
    state (``models/ling_hybrid.py``: the routers' selection biases) has
    ``state_mask()`` and ``advance_state(state, signal)`` and reports
    (:func:`_reports`): its ``objective`` gives the signal."""
    if not hasattr(model, "state_mask"):
        return None
    return jax.tree_util.tree_leaves(model.state_mask())


def _state_leaves(tree: Any, mask: List[bool]) -> List[Any]:
    """The state leaves of a params-shaped ``tree``, in flatten order."""
    return [x for x, is_state in zip(jax.tree_util.tree_leaves(tree), mask) if is_state]


def _with_state(tree: Any, mask: List[bool], new: List[Any]) -> Any:
    """``tree`` with its state leaves replaced by ``new``."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    fresh = iter(new)
    return jax.tree_util.tree_unflatten(
        treedef, [next(fresh) if is_state else x for x, is_state in zip(leaves, mask)]
    )


def make_grad_step(
    model: Any, mesh: Mesh
) -> Callable[[Any, Any], Tuple[jax.Array, Any]]:
    """Compile ``(params, batch) → (loss, grads)`` with grads sharded like
    params (the FSDP reduce-scatter happens inside via XLA SPMD).

    For a model that reports (:func:`_reports`) the first result is a
    float32 vector: the model's ``objective`` and then its ``summary``, so
    that both reach the host in one transfer.  Where it has state the
    optimizer does not own, each state leaf's slot in ``grads`` (its
    gradient is stopped, so the slot is free) carries the step's signal for
    that leaf: it passes the replica-dimension average with the gradients,
    which keeps replicas bit-equal."""
    params_sh, batch_sh = fsdp_shardings(model, mesh)
    mask = _state_mask(model)

    def _step(params: Any, batch: Any) -> Tuple[jax.Array, Any]:
        if not _reports(model):
            return jax.value_and_grad(model.loss)(params, batch)
        (loss, (signal, summary)), grads = jax.value_and_grad(model.objective, has_aux=True)(params, batch)
        with part("head"):  # the step's account of itself, beside the losses
            if mask is not None:
                grads = _with_state(grads, mask, [s.astype(jnp.float32) for s in signal])
            report = jnp.concatenate([loss.reshape(1), summary.reshape(-1)]).astype(jnp.float32)
        return report, grads

    with mesh:
        return jax.jit(
            _step,
            in_shardings=(params_sh, batch_sh),
            out_shardings=(NamedSharding(mesh, P()), params_sh),
        )


def match_param_by_suffix(
    path: Tuple, shape: Tuple[int, ...], params_paths: Dict[Tuple, Tuple]
) -> Any:
    """Find the parameter entry whose key-path is a suffix of ``path`` with
    a matching shape — optax embeds the params tree verbatim in every
    params-mirroring opt-state subtree (momentum, Adam mu/nu, ...), so the
    suffix+shape rule maps an opt-state leaf back to its parameter.
    ``params_paths``: ``{path-tuple: (shape-tuple, value)}``; returns the
    matched value or None.  Shared by :func:`sharded_opt_init` (value =
    sharding) and ``parallel.rehearsal`` (value = PartitionSpec)."""
    path = tuple(path)
    for start in range(len(path)):
        hit = params_paths.get(path[start:])
        if hit is not None and hit[0] == tuple(shape):
            return hit[1]
    return None


def sharded_opt_init(tx: Any, params: Any) -> Any:
    """Initialize optimizer state with correct shardings on multi-host.

    ``jax.jit(tx.init)(params)`` is NOT sharding-safe: optimizer-state
    leaves depend only on param *shapes*, so XLA dead-code-eliminates the
    value dependence and is free to pick arbitrary (e.g. single-device)
    output layouts — on a multi-host mesh that makes heal/update layouts
    diverge between hosts.  This pins every params-mirroring leaf (momentum,
    Adam mu/nu, ...) to its param's sharding, matched by key-path suffix
    (optax embeds the params tree verbatim in those subtrees), and
    replicates everything else (step counts etc.).
    """
    params_paths = {
        tuple(path): (tuple(leaf.shape), leaf.sharding)
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
        if isinstance(leaf, jax.Array)
    }
    mesh = None
    for _shape, sharding in params_paths.values():
        if isinstance(sharding, NamedSharding):
            mesh = sharding.mesh
            break

    shapes = jax.eval_shape(tx.init, params)

    def _sharding_for(path: Tuple, shape_struct: Any) -> Any:
        sharding = match_param_by_suffix(
            path, shape_struct.shape, params_paths
        )
        if sharding is not None:
            return sharding
        if mesh is not None:
            return NamedSharding(mesh, P())  # replicated (counts, scalars)
        return None

    leaves_with_paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out_shardings = jax.tree_util.tree_unflatten(
        treedef, [_sharding_for(p, s) for p, s in leaves_with_paths]
    )
    return jax.jit(tx.init, out_shardings=out_shardings)(params)


def make_update_step(
    model: Any, tx: Any, mesh: Mesh
) -> Callable[[Any, Any, Any], Tuple[Any, Any]]:
    """Compile the optax update with params/grads/opt_state in HSDP layout."""
    import optax

    params_sh, _ = fsdp_shardings(model, mesh)
    mask = _state_mask(model)

    @part("optimizer")  # the whole program is one part (``obs/spans.py``)
    def _update(params: Any, opt_state: Any, grads: Any) -> Tuple[Any, Any]:
        if mask is not None:
            # the state leaves' slots hold the averaged signal, not a
            # gradient: the optimizer sees zeros there (its moments stay zero)
            # and what it would do to those leaves (weight decay) is thrown away
            state, signal = _state_leaves(params, mask), _state_leaves(grads, mask)
            grads = _with_state(grads, mask, [jnp.zeros_like(x) for x in state])
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if mask is not None:
            params = _with_state(params, mask, model.advance_state(state, signal))
        return params, opt_state

    with mesh:
        return jax.jit(_update, donate_argnums=(0, 1))


class HSDPTrainer:
    """Fault-tolerant HSDP training driver (BASELINE config 3).

    Per step: quorum (async, overlapped) → compiled grad step (FSDP/TP over
    ICI) → replica-dim gradient average (Manager over DCN) → commit-gated
    compiled update.

    **Why the DCN ring sits on the per-step critical path.** The commit
    vote must fence every in-flight collective (a late failure after the
    vote would commit unaveraged gradients — ``Manager.should_commit``;
    the reference synchronizes its accelerator stream at the same point,
    ``torchft/manager.py:888-893``), the update needs the averaged
    gradients, and the next forward needs the update.  Overlapping the
    ring with the next step's compute therefore requires either stale
    gradients or unfenced commits — both unsound for per-step DDP.  The
    framework's levers instead:

    - ``quantize_outer=True``: the int8 wire format (native host kernels +
      windowed wire/reduce pipelining, ``collectives.py``) cuts ring bytes
      4x and round-2 wall time ~2.4x.  Every replica applies the identical
      requantized stream, so replicas stay bit-identical; accuracy vs the
      f32 ring is rowwise-int8 (the reference ships fp8 outer syncs with
      the same caveat).
    - bucket-level pipelining inside ``allreduce_pytree``: D2H of bucket
      k+1 overlaps the ring of bucket k.
    - for delay-tolerant training, :class:`~torchft_tpu.local_sgd.DiLoCo`
      (and streaming fragments) moves the outer sync fully off the
      critical path with its τ-delay worker — that is the sanctioned
      ring/compute-overlap mode, as in the reference.
    """

    def __init__(
        self,
        model: Any,
        tx: Any,
        mesh: Mesh,
        manager: Manager,
        key: Optional[jax.Array] = None,
        params: Optional[Any] = None,
        quantize_outer: bool = False,
    ) -> None:
        self.model = model
        self.tx = tx
        self.mesh = mesh
        self.manager = manager
        self.quantize_outer = quantize_outer
        if params is None:
            assert key is not None, "need key or params"
            params = shard_init(model, key, mesh)
        with mesh:
            opt_state = sharded_opt_init(tx, params)
        self.holder: Dict[str, Any] = {"params": params, "opt_state": opt_state}
        self._grad_step = make_grad_step(model, mesh)
        self._update_step = make_update_step(model, tx, mesh)
        self._state_mask = _state_mask(model)

        manager.register_state_dict_fn(
            "hsdp", self._load_state, self._save_state
        )

    def _save_state(self) -> Dict[str, Any]:
        return dict(self.holder)

    def _load_state(self, state: Dict[str, Any]) -> None:
        # restore placement: healing delivers host arrays (or per-shard
        # ShardedHostArray bundles from a multi-host sender); put them back
        # into the HSDP layout of the existing values
        from torchft_tpu.ddp import restore_tree_like

        self.holder["params"] = restore_tree_like(
            state["params"], self.holder["params"]
        )
        self.holder["opt_state"] = restore_tree_like(
            state["opt_state"], self.holder["opt_state"]
        )

    def relower(
        self, surviving_devices: Any, plan: Any = None
    ) -> Any:
        """Degraded-mode re-lower onto the surviving devices (device loss
        WITHOUT replica death): rebuild the mesh, reshard params +
        optimizer state, recompile the steps, and fence the commit vote
        across the transition via ``Manager.begin_relower`` /
        ``complete_relower`` — a crash mid-reshard reads as "never voted
        commit".  Returns the applied
        :class:`~torchft_tpu.parallel.degraded.DegradedPlan` (whose
        ``capacity`` the manager now advertises on the wire-v5 tail)."""
        from torchft_tpu.parallel.degraded import relower_hsdp_trainer

        self.manager.begin_relower()
        plan = relower_hsdp_trainer(self, surviving_devices, plan)
        self.manager.complete_relower(plan.capacity)
        return plan

    def train_step(self, batch: Any) -> Tuple[float, bool]:
        """One fault-tolerant step; returns (loss, committed)."""
        self.manager.start_quorum()
        # the two dispatches, named so that a trace says which of them the
        # device was waiting on
        with obs_span("tpuft/step/grad"):
            loss, grads = self._grad_step(self.holder["params"], batch)
        mask = self._state_mask
        if mask is None or not self.quantize_outer:
            grads = ft_allreduce(
                self.manager, grads, should_quantize=self.quantize_outer
            )
        else:
            # the signal crosses the wire by itself, unquantised: an 8-bit
            # count could turn the sign its update takes
            signal = ft_allreduce(self.manager, _state_leaves(grads, mask))
            grads = _with_state(grads, mask, [jnp.zeros_like(x) for x in signal])
            grads = _with_state(ft_allreduce(self.manager, grads, should_quantize=True), mask, signal)
        committed = self.manager.should_commit()
        if committed:
            with obs_span("tpuft/step/update"):
                params, opt_state = self._update_step(
                    self.holder["params"], self.holder["opt_state"], grads
                )
            self.holder["params"] = params
            self.holder["opt_state"] = opt_state
        if not _reports(self.model):
            return float(loss), committed
        # ONE transfer a step, as for a model that reports nothing: the loss
        # and this replica's own summary of the step (made before the
        # average) come to the host in one small array
        host = np.asarray(loss)
        if committed:
            self.manager._flight.record(FlightEvent.MOE_ROUTE, **self.model.summary_stats(host[1:]))
        return float(host[0]), committed
