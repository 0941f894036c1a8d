"""Pipeline parallelism: GPipe microbatching over a ``pp`` mesh axis.

The reference composes with torch's ``distributed.pipelining`` (it uses PP
to carve DiLoCo fragments, ``train_diloco.py:159-162``) but ships no
pipeline engine of its own.  Here PP is first-class and TPU-native: no
per-stage processes, no send/recv runtime — ONE SPMD program in which every
device holds its stage's slice of the layer stack and activations hop
stages via ``lax.ppermute`` over ICI.  The schedule is a compiled
``lax.scan`` over ``num_microbatches + pp - 1`` ticks (the classic GPipe
diagram), so XLA sees static control flow and overlaps the permute with the
next tick's math.  Reverse-mode AD differentiates straight through the
scan + ppermute, yielding the mirrored backward pipeline for free — no
hand-written 1F1B runtime, which is the point of doing PP inside the XLA
compilation model rather than translating torch's stage executor.

Composition: the shard_map is *manual only over* ``pp`` (``axis_names``);
``dp``/``fsdp``/``tp`` stay under the SPMD partitioner, so tensor
parallelism and FSDP keep working inside each stage.  The fault-tolerant
replica dimension stays host-side in the Manager, outside this program, as
everywhere else in the framework.

Bubble math: utilization = M / (M + P - 1) for M microbatches over P
stages — pick M >= 4*P for >80%.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from torchft_tpu.models.llama import Llama, LlamaConfig



def _pipeline_local(
    stage_params: Any,
    x_mb: jax.Array,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    axis: str,
    num_stages: int,
    num_microbatches: int,
) -> jax.Array:
    """shard_map body (manual over ``axis`` only).

    ``stage_params``: this stage's slice of the layer stack (leading dim =
    layers_per_stage locally).  ``x_mb``: [M, mb, S, D] microbatched input
    activations, replicated over ``axis``.  Returns outputs with the same
    shape, replicated from the last stage.
    """
    idx = jax.lax.axis_index(axis)
    M, num_ticks = num_microbatches, num_microbatches + num_stages - 1

    state = jnp.zeros(x_mb.shape[1:], x_mb.dtype)  # inbound activation
    outputs = jnp.zeros_like(x_mb)

    # stage j sends to j+1; the last stage's output exits the ring (its
    # ppermute result on stage 0 is zeros, always overwritten by the
    # microbatch feed below)
    perm = [(j, j + 1) for j in range(num_stages - 1)]

    def tick(carry: Tuple[jax.Array, jax.Array], t: jax.Array):
        state, outputs = carry
        feed = jax.lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, M - 1), 0, keepdims=False
        )
        inp = jnp.where(idx == 0, feed, state)
        y = stage_fn(stage_params, inp)

        # the last stage finishes microbatch t-(P-1) at tick t
        out_idx = jnp.clip(t - (num_stages - 1), 0, M - 1)
        write = jnp.logical_and(idx == num_stages - 1, t >= num_stages - 1)
        cur = jax.lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs, jnp.where(write, y, cur), out_idx, 0
        )
        if perm:
            state = jax.lax.ppermute(y, axis, perm)
        return (state, outputs), None

    (_, outputs), _ = jax.lax.scan(tick, (state, outputs), jnp.arange(num_ticks))
    # replicate the finished microbatches from the last stage to all stages
    return jax.lax.psum(
        jnp.where(idx == num_stages - 1, outputs, jnp.zeros_like(outputs)),
        axis,
    )


def pipeline_spmd(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    x: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "pp",
    num_microbatches: int,
    remat: bool = False,
    sp_axis: Optional[str] = None,
) -> jax.Array:
    """Run ``x`` [B, S, D] through a layer stack pipelined over ``axis``.

    ``stacked_params``: pytree whose leaves carry a leading total-layers dim,
    sharded over ``axis`` (each stage sees its contiguous [L/P, ...] slice).
    ``stage_fn(local_stack, h)`` applies one stage's layers to ``h``
    [mb, S, D].  ``remat=True`` wraps the stage in ``jax.checkpoint`` so the
    backward pipeline recomputes stage activations instead of saving one per
    tick (GPipe's activation-memory trade, via XLA rematerialization).

    ``sp_axis``: compose with sequence parallelism — the shard_map goes
    manual over {pp, sp}, activations shard their seq dim over ``sp``, and
    ``stage_fn`` sees seq-local blocks (its attention must use the ring
    collective form over ``sp``; positions need the sp-block offset).
    """
    num_stages = mesh.shape[axis]
    B = x.shape[0]
    if B % num_microbatches:
        raise ValueError(f"batch {B} not divisible by M={num_microbatches}")
    x_mb = x.reshape(num_microbatches, B // num_microbatches, *x.shape[1:])

    fn = jax.checkpoint(stage_fn) if remat else stage_fn
    body = partial(
        _pipeline_local,
        stage_fn=fn,
        axis=axis,
        num_stages=num_stages,
        num_microbatches=num_microbatches,
    )
    manual = frozenset({axis, sp_axis} if sp_axis else {axis})
    # x_mb is [M, mb, S, D]: seq (dim 2) shards over sp inside the manual
    # region; everything else about the schedule is sp-oblivious
    x_spec = P(None, None, sp_axis, None) if sp_axis else P()
    out_mb = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), x_spec),
        out_specs=x_spec,
        axis_names=manual,
        check_vma=False,
    )(stacked_params, x_mb)
    return out_mb.reshape(B, *x.shape[1:])


class PipelinedLlama(Llama):
    """Llama with its scanned layer stack pipelined over the ``pp`` axis.

    Embedding and unembed/loss run outside the pipeline (replicated over
    ``pp``, sharded over ``tp``/``fsdp`` as usual — vocab-dim math is a
    trivial fraction of step FLOPs); the transformer blocks run through
    :func:`pipeline_spmd`.  Because the base model already stacks per-layer
    weights with a leading ``n_layers`` dim, carving stages is purely a
    sharding statement: :meth:`param_specs` puts ``pp`` on that leading dim
    and each stage materializes only its own layers — PP here is *free* at
    the parameter-layout level, composing with FSDP/TP on the other dims.

    pp × sp composes: with ``config.sp_axis`` set, the pipeline's
    shard_map goes manual over {pp, sp}, activations shard their sequence
    dim over ``sp``, and each stage's attention runs the ring collective
    form directly (it is built for callers already inside a manual
    region), with RoPE positions offset by the sp block index.

    Constraints: ``n_layers % pp == 0``; batch divisible by
    ``num_microbatches``; seq divisible by the ``sp`` size when composed.
    """

    def __init__(
        self,
        config: LlamaConfig,
        mesh: Mesh,
        pp_axis: str = "pp",
        num_microbatches: Optional[int] = None,
        remat: bool = False,
    ) -> None:
        super().__init__(config, mesh)
        # ring attention must use its raw collective form inside the
        # pipeline's manual region (its own shard_map cannot nest)
        self._in_manual_sp = config.sp_axis is not None
        # flash dispatch is disabled inside the pipeline's manual region:
        # nesting the sharded variant's shard_map (or a bare pallas_call
        # over auto-sharded dp/tp operands) inside it is unsupported
        self._disable_flash = True
        self.pp_axis = pp_axis
        self.num_stages = mesh.shape[pp_axis]
        if config.n_layers % self.num_stages:
            raise ValueError(
                f"n_layers={config.n_layers} not divisible by "
                f"pp={self.num_stages}"
            )
        # default: 4 microbatches per stage (>= 80% pipeline utilization)
        self.num_microbatches = num_microbatches or 4 * self.num_stages
        self.remat = remat

    def param_specs(self) -> Dict[str, Any]:
        specs = super().param_specs()
        pp = self.pp_axis
        specs["layers"] = {
            name: P(pp, *spec[1:]) for name, spec in specs["layers"].items()
        }
        return specs

    def _stage_fn(self, stage_layers: Dict[str, jax.Array], h: jax.Array):
        """Apply this stage's layer slice to local activations [mb, S, D].
        Under pp × sp, S is the sp-local block and RoPE positions carry
        the block's global offset."""
        B, S, _ = h.shape
        offset = (
            jax.lax.axis_index(self.config.sp_axis) * S
            if self.config.sp_axis is not None
            else 0
        )
        positions = offset + jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        rope = self._rope(positions)

        def scan_body(carry, layer_params):
            return self._layer(carry, layer_params, rope, positions), None

        h, _ = jax.lax.scan(scan_body, h, stage_layers)
        return h

    def apply(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        cfg = self.config
        x = params["embed"][tokens].astype(cfg.dtype)
        x = pipeline_spmd(
            self._stage_fn,
            params["layers"],
            x,
            mesh=self.mesh,
            axis=self.pp_axis,
            num_microbatches=self.num_microbatches,
            remat=self.remat,
            sp_axis=cfg.sp_axis,
        )
        x = self._rms_norm(x, params["final_norm"], cfg.norm_eps)
        return (x @ params["lm_head"]).astype(jnp.float32)
