"""Expert parallelism: a Mixture-of-Experts block sharded over an ``ep`` axis.

Net-new relative to the reference (torchft has no expert parallelism,
SURVEY.md §2.3) but part of torchft_tpu's first-class parallelism surface:
experts are sharded over a mesh axis and tokens route to their expert via
``lax.all_to_all`` over ICI — the TPU-native analog of NCCL alltoall MoE
dispatch.

Design (compiler-friendly, static shapes):

- top-1 switch routing with a fixed per-expert **capacity**; overflow tokens
  pass through the residual (standard Switch-Transformer form — no dynamic
  shapes inside jit).
- dispatch/combine are einsums against a one-hot dispatch mask, so the MXU
  does the data movement math and XLA lays out the ``all_to_all`` over the
  ``ep`` axis.
- runs inside ``shard_map`` over ``ep`` (experts local to each shard); the
  dense reference path (no mesh) computes identical math for testing.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from torchft_tpu.models.decoder import assumed_backend
# ``part`` is the name of ``_held_part``'s own function below
from torchft_tpu.obs.spans import part as device_part


@dataclass(frozen=True)
class MoEConfig:
    dim: int
    ffn_hidden: int
    num_experts: int
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32  # expert weights/compute (bf16 for MXU models)


class MoE:
    """Top-1 switch MoE layer with optional expert parallelism."""

    def __init__(self, config: MoEConfig, mesh: Optional[Mesh] = None, ep_axis: str = "ep") -> None:
        self.config = config
        self.mesh = mesh
        self.ep_axis = ep_axis

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        k_router, k_up, k_down = jax.random.split(key, 3)
        scale_in = 1.0 / np.sqrt(cfg.dim)
        scale_hidden = 1.0 / np.sqrt(cfg.ffn_hidden)
        return {
            # router stays fp32: routing logits are precision-sensitive
            "router": jax.random.normal(k_router, (cfg.dim, cfg.num_experts))
            * scale_in,
            "w_up": (
                jax.random.normal(k_up, (cfg.num_experts, cfg.dim, cfg.ffn_hidden))
                * scale_in
            ).astype(cfg.dtype),
            "w_down": (
                jax.random.normal(k_down, (cfg.num_experts, cfg.ffn_hidden, cfg.dim))
                * scale_hidden
            ).astype(cfg.dtype),
        }

    def param_specs(self) -> Dict[str, Any]:
        """Experts sharded over ``ep`` (leading expert dim); router replicated."""
        return {
            "router": P(None, None),
            "w_up": P(self.ep_axis, None, None),
            "w_down": P(self.ep_axis, None, None),
        }

    # ------------------------------------------------------------------

    def _route(
        self, params: Dict[str, Any], x: jax.Array, capacity: int
    ) -> Tuple[jax.Array, jax.Array]:
        """x [T, D] → (dispatch [E, C, T] one-hot-ish, combine [E, C, T])."""
        cfg = self.config
        logits = x.astype(jnp.float32) @ params["router"]  # [T, E] fp32
        probs = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(probs, axis=-1)  # [T]
        gate = jnp.max(probs, axis=-1)  # [T]

        # position of each token within its expert's capacity buffer
        onehot = jax.nn.one_hot(expert, cfg.num_experts, dtype=jnp.int32)  # [T, E]
        position = jnp.cumsum(onehot, axis=0) * onehot  # 1-based [T, E]
        pos_in_expert = jnp.sum(position, axis=-1) - 1  # [T]
        keep = pos_in_expert < capacity

        dispatch = (
            jax.nn.one_hot(expert, cfg.num_experts, dtype=x.dtype)[:, :, None]
            * jax.nn.one_hot(
                jnp.where(keep, pos_in_expert, capacity), capacity + 1, dtype=x.dtype
            )[:, None, :capacity]
        )  # [T, E, C]
        dispatch = dispatch.transpose(1, 2, 0)  # [E, C, T]
        combine = dispatch * gate[None, None, :]
        return dispatch, combine

    def _expert_ffn(self, w_up: jax.Array, w_down: jax.Array, x: jax.Array) -> jax.Array:
        """x [E, C, D] with per-expert weights [E, D, F] / [E, F, D]."""
        h = jax.nn.relu(jnp.einsum("ecd,edf->ecf", x, w_up))
        return jnp.einsum("ecf,efd->ecd", h, w_down)

    def _apply_dense(self, params: Dict[str, Any], x: jax.Array) -> jax.Array:
        """Reference path: all experts local. x [T, D] → [T, D]."""
        cfg = self.config
        T = x.shape[0]
        capacity = max(1, int(cfg.capacity_factor * T / cfg.num_experts))
        dispatch, combine = self._route(params, x, capacity)
        expert_in = jnp.einsum("ect,td->ecd", dispatch, x)
        expert_out = self._expert_ffn(params["w_up"], params["w_down"], expert_in)
        return jnp.einsum("ect,ecd->td", combine, expert_out)

    def _apply_ep_local(self, params: Dict[str, Any], x: jax.Array) -> jax.Array:
        """shard_map body over ep: x is this shard's token block [T_loc, D];
        w_up/w_down hold the shard's local experts [E_loc, ...]."""
        cfg = self.config
        axis = self.ep_axis
        n = jax.lax.psum(1, axis)
        T_loc = x.shape[0]
        e_loc = params["w_up"].shape[0]
        capacity = max(1, int(cfg.capacity_factor * T_loc / cfg.num_experts))

        dispatch, combine = self._route(params, x, capacity)  # [E, C, T_loc]
        expert_in = jnp.einsum("ect,td->ecd", dispatch, x)  # [E, C, D]

        # ship each expert-shard's token buffers to its owner: [E, C, D] →
        # regroup E = n * e_loc (experts are contiguous per shard) →
        # all_to_all over the ep axis
        expert_in = expert_in.reshape(n, e_loc, capacity, cfg.dim)
        routed = jax.lax.all_to_all(
            expert_in, axis, split_axis=0, concat_axis=0, tiled=False
        )  # [n_src, e_loc, C, D]: every shard's tokens for our local experts
        routed = routed.transpose(1, 0, 2, 3).reshape(
            e_loc, n * capacity, cfg.dim
        )

        out = self._expert_ffn(params["w_up"], params["w_down"], routed)

        # send results back to the token owners (all_to_all is self-inverse)
        out = out.reshape(e_loc, n, capacity, cfg.dim).transpose(1, 0, 2, 3)
        returned = jax.lax.all_to_all(
            out, axis, split_axis=0, concat_axis=0, tiled=False
        ).reshape(n * e_loc, capacity, cfg.dim)  # [E, C, D] back home
        return jnp.einsum("ect,ecd->td", combine, returned)

    def apply(self, params: Dict[str, Any], x: jax.Array) -> jax.Array:
        """x [B, S, D] → [B, S, D] (residual added by the caller)."""
        B, S, D = x.shape
        flat = x.reshape(B * S, D)
        if self.mesh is None:
            out = self._apply_dense(params, flat)
        else:
            fn = jax.shard_map(
                partial(self._apply_ep_local),
                mesh=self.mesh,
                in_specs=(
                    self.param_specs(),
                    P(self.ep_axis, None),  # tokens sharded over ep
                ),
                out_specs=P(self.ep_axis, None),
                check_vma=False,
            )
            out = fn(params, flat)
        return out.reshape(B, S, D)


# ---------------------------------------------------------------------------
# one chip's share of a wide expert layer (DeepSeek-V3-style routing)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RoutedExpertsConfig:
    dim: int
    expert_hidden: int
    num_experts: int  # the router's width: every expert of the layer
    # the contiguous range of experts whose weights are HERE: (first, count)
    experts_held: Tuple[int, int]
    top_k: int
    n_group: int = 1  # 1: no groups, the top_k best of all experts
    topk_group: int = 1
    score_func: str = "sigmoid"  # or "softmax": over all experts, float32
    # a selection bias that no gradient moves (``HSDPTrainer``: state the
    # optimizer does not own); False: no such leaf, the scores alone choose
    selection_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    shared_hidden: int = 0  # width of the shared expert; 0: none
    # the shared expert behind a gate of its own, ``sigmoid(x . shared_sigmoid)``
    # a token (Qwen3-Next); False: no such leaf, the shared expert added bare
    gated_shared: bool = False
    balance_loss_weight: float = 0.0  # sequence-wise, arXiv:2412.19437 eq. 17-20
    # an expert's form, the routed experts' and the shared one's alike:
    # "swiglu", three matrices, ``w_down (silu(w_gate x) * (w_up x))``,
    # "reglu", the same three with a ReLU for the SiLU, ``w_down (relu(w_gate
    # x) * (w_up x))``, or "relu2", two and no gate, ``w_down relu(w_up x)^2``
    expert_form: str = "swiglu"
    dtype: Any = jnp.bfloat16


def swiglu(gate: jax.Array, up: jax.Array, limit: float) -> jax.Array:
    """``silu(gate) * up``; where ``limit`` is not 0 the gate is clamped
    from above and the linear half on both sides (the reading of
    ``expert_swiglu_limit_list`` taken: the clamp gpt-oss publishes)."""
    if limit:
        gate = jnp.minimum(gate, limit)
        up = jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def reglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    """``relu(gate) * up``: a gated expert whose gate is a ReLU, so that a
    hidden unit is exactly off for most tokens (SmallThinker's sparse experts)."""
    return jax.nn.relu(gate) * up


def relu2(up: jax.Array) -> jax.Array:
    """``relu(up)^2``: the activation of an expert of two matrices."""
    return jnp.square(jax.nn.relu(up))


def buffer_size(tokens: int, top_k: int, held: int, num_experts: int) -> int:
    """The static size of the held experts' buffer, in rows: 1.25 times what
    a uniform router sends here (``tokens * top_k * held / num_experts``),
    rounded up to 512 and never over every pair (``tokens * top_k``)."""
    full = tokens * top_k
    return min(full, -(-5 * full * held // (4 * num_experts * 512)) * 512)


# What a kernel may hold in fast memory where its ``pallas_call`` names no
# limit, as ``megablox``'s does not: the compiler's default on a v5e.
SCOPED_VMEM = 16 * 2**20


def grouped_vmem(kind: str, tm: int, tk: int, tn: int) -> int:
    """The scoped memory one grouped product is reckoned to need at tiles
    ``(tm, tk, tn)``, in bytes of bfloat16 operands: two buffers of each of
    its three blocks, a block's last dimension rounded up to the 128 lanes,
    and the float32 accumulator, which is the result's block ([tm, tn] of a
    ``gmm``, [tk, tn] of a ``tgmm``); beside them what the body makes: a
    ``gmm``'s product before it is added, a ``tgmm``'s first block turned.
    Alone, a kernel compiles at the blocks and the accumulator to the byte;
    inside a step's program the compiler asked the turned block as well
    (16.34 MiB where the blocks and the accumulator are 15.38), and at row
    tiles of 512 more for a ``gmm`` (PERF.md section 6, PR 51)."""
    lanes = lambda d: -(-d // 128) * 128  # noqa: E731
    if kind == "tgmm":  # blocks [tm, tk] and [tm, tn] in, [tk, tn] out
        return 4 * (tm * lanes(tk) + tm * lanes(tn) + tk * lanes(tn)) + 4 * tk * lanes(tn) + 2 * tm * lanes(tk)
    matrix = tk * lanes(tn) if kind == "gmm" else tn * lanes(tk)  # stored [n, k] where it is transposed
    return 4 * (tm * lanes(tk) + matrix + tm * lanes(tn)) + 8 * tm * lanes(tn)


def _pieces(d: int) -> List[int]:
    """What a width may be cut into: itself, its equal parts that are
    multiples of 128 (2,688 in thirds of 896; 1,856 has none), and the
    multiples of 128 under it whose last tile is partly empty."""
    equal = [d // p for p in range(1, d // 128 + 1) if d % (128 * p) == 0]
    return sorted({d, *equal, *(t for t in (1024, 512, 256, 128) if t < d)}, reverse=True)


def grouped_tiles(kind: str, m: int, k: int, n: int, held: int) -> Tuple[int, int, int]:
    """The tiles ``(tm, tk, tn)`` of one grouped product, from its shapes:
    ``kind`` "gmm" (rows [m, k] through [held, k, n]), "gmm_t" (the same with
    the matrices stored [held, n, k]) or "tgmm" ([m, k] and [m, n] into
    [held, k, n]: the rows are contracted).  A grid step costs a third of a
    microsecond whatever it holds (PR 29's constant ``(128, 256, 256)`` held
    0.085 us of product), so a row tile should cross k and n in as few steps
    as ``grouped_vmem`` admits.  Of the tiles that fit, in this order:

    - a ``gmm``'s k in whole tiles (a last tile partly past k is masked in
      float32 on every visit);
    - the fewest grid steps a row tile, ``tk`` and ``tn`` from ``_pieces``;
      then the least width computed in vain (a last tile partly past the edge);
    - the larger row tile.  A row tile is visited once for EACH expert with
      rows in it, ``rows / tm + held`` visits of ``tm`` rows' work, so it
      follows the rows an expert gets from a uniform router, ``m / 1.25 /
      held`` (``buffer_size``): 256 where that is 256 or more (512 lost to it
      in every call), and 128 below that or where 256 would cost a step more;
    - for a ``gmm`` the fewest k tiles (each more is a round trip of the
      accumulator), for a ``tgmm`` the squarer result block.

    One answer a call, made when the program is traced; no table of models
    and no option (the sweep it reproduces: ``scripts/gmm_tile_probe.py``,
    PERF.md section 6, PR 51)."""
    uniform = 4 * m // (5 * held)
    # a buffer that no row tile divides (a toy's) is one tile
    row_tiles = [tm for tm in (256, 128) if tm <= max(uniform, 128) and m % tm == 0] or [m]
    tiles = lambda d, t: -(-d // t)  # noqa: E731

    def cost(tile):
        tm, tk, tn = tile
        steps, computed = tiles(k, tk) * tiles(n, tn), tiles(k, tk) * tk * tiles(n, tn) * tn
        ragged_k = kind != "tgmm" and k % tk != 0
        return (ragged_k, steps, computed, -tm, -min(tk, tn) if kind == "tgmm" else tiles(k, tk))

    fit = [
        (tm, tk, tn) for tm in row_tiles for tk in _pieces(k) for tn in _pieces(n)
        if grouped_vmem(kind, tm, tk, tn) <= SCOPED_VMEM
    ]
    if not fit:
        raise ValueError(f"no tiles of a {kind} [{m}, {k}] x [{k}, {n}] fit {SCOPED_VMEM} bytes of scoped memory")
    return min(fit, key=cost)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_product(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array, interpret: bool = False) -> jax.Array:
    """Rows ``lhs`` [m, k], sorted by group, each through its group's matrix
    of ``rhs`` [held, k, n], by ``megablox``'s kernels at the tiles
    ``grouped_tiles`` reads off each call's shapes: the forward ``gmm``, the
    backward ``gmm`` to the rows (the matrices transposed: k and n swap) and
    the ``tgmm`` to the matrices (the rows are the contracted dimension there)
    are asked for separately, which ``megablox``'s own ``custom_vjp`` cannot
    do: it hands all three ONE tiling, and its forward and its ``tgmm`` even
    the same ``(m, k, n)``.  Rows past ``sum(sizes)`` are left unwritten, in
    the gradient to the rows too."""
    from jax.experimental.pallas.ops.tpu.megablox.ops import backend

    (m, k), (held, _, n) = lhs.shape, rhs.shape
    return backend.gmm(lhs, rhs, sizes, lhs.dtype, grouped_tiles("gmm", m, k, n, held), interpret=interpret)


def _grouped_product_fwd(lhs, rhs, sizes, interpret):
    return grouped_product(lhs, rhs, sizes, interpret), (lhs, rhs, sizes)


def _grouped_product_bwd(interpret, kept, grad):
    from jax.experimental.pallas.ops.tpu.megablox.ops import backend

    lhs, rhs, sizes = kept
    (m, k), (held, _, n) = lhs.shape, rhs.shape
    to_rows = backend.gmm(
        grad, rhs, sizes, lhs.dtype, grouped_tiles("gmm_t", m, n, k, held), transpose_rhs=True, interpret=interpret
    )
    to_matrices = backend.tgmm(
        lhs.swapaxes(0, 1), grad, sizes, rhs.dtype, grouped_tiles("tgmm", m, k, n, held), interpret=interpret
    )
    return to_rows, to_matrices, None  # the sizes are integers


grouped_product.defvjp(_grouped_product_fwd, _grouped_product_bwd)


def buffer_passes(size: int, rows: jax.Array) -> jax.Array:
    """How often a buffer of ``size`` rows is filled to take ``rows`` (a
    count, or one a layer) through it, int32: once even for no rows."""
    return jnp.maximum(1, (jnp.asarray(rows).astype(jnp.int32) + (size - 1)) // size)


# a step's summary, one row an expert layer (``RoutedExperts.route_summary``, ``summary_stats``)
ROUTE_FIELDS = ("rows_here", "load_max", "load_mean", "buffer_rows")


def summary_stats(summary: np.ndarray, fields: Tuple[str, ...] = ROUTE_FIELDS) -> Dict[str, List[float]]:
    """A step's summary of one row a layer (``RoutedExperts.route_summary``,
    or a model's own rows of ``fields``) on the host, a list a field: the
    flight event's detail."""
    columns = np.asarray(summary, np.float64).reshape(-1, len(fields)).T
    return {name: column.tolist() for name, column in zip(fields, columns)}


def state_mask(param_specs: Any) -> Any:
    """True for the leaves of a model's tree (``param_specs``: its
    PartitionSpecs) that the optimizer does not own: the routers' selection
    biases, a leaf called "bias" (``HSDPTrainer`` asks a model with such
    state for this, for ``objective``'s signal a leaf and for
    ``advance_state``)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: getattr(path[-1], "key", None) == "bias",
        param_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def advance_state(rate: float, state: List[jax.Array], signal: List[jax.Array]) -> List[jax.Array]:
    """``bias += rate * sign(mean(load) - load)``, a router at a time (the
    last axis is the router's width): after a committed step a selection
    bias goes up for an expert that saw fewer tokens than the mean and down
    for one that saw more (DeepSeek-V3's balancing without an auxiliary
    loss)."""
    return [
        bias + rate * jnp.sign(jnp.mean(load, axis=-1, keepdims=True) - load)
        for bias, load in zip(state, signal)
    ]


class RoutedExperts:
    """An expert layer that is TOLD which experts it holds.

    The router scores all ``num_experts`` in float32 (``score_func``:
    sigmoid, or a softmax over all of them), adds the selection bias if it
    has one, keeps ``topk_group`` of the ``n_group`` groups by the sum of
    each group's two best (one group: no such step) and then the ``top_k``
    best experts inside them; the weights are the UNBIASED scores of the
    chosen, normalised over all ``top_k`` and scaled.  Nothing is dropped: the (token, choice) pairs
    that fall on held experts are sorted by expert into a static buffer and
    go through the experts' grouped products (``expert_form``: a SwiGLU or a
    ReGLU of three matrices or a squared ReLU of two), whose work follows the rows
    really routed here (``megablox.gmm`` on the TPU: ``path`` "gmm"; ``lax.ragged_dot``
    elsewhere).  What the absent experts would have added is left out; the
    shared expert is added once, behind ``sigmoid(x . shared_sigmoid)`` a token
    where ``gated_shared`` asks for that gate.  On one chip there is no exchange, and no
    code stands in for the absent chips.

    The buffer is small and the PASSES through it follow the load:
    ``buffer_size`` rows (1.25 times what a uniform router sends here), filled
    as often as the rows that arrived need (``buffer_passes``: once, as a
    rule), one loop a layer and a step whose trip count the device reads, so
    that no load is ever cut and ONE program serves every load.  Rows go in by
    a gather and come back by a scatter-add, both over the buffer's rows and
    never over all ``tokens * top_k`` pairs; the grouped products' work follows
    the rows, not the buffer.
    """

    def __init__(self, config: RoutedExpertsConfig) -> None:
        self.config = config
        if config.num_experts % config.n_group:
            raise ValueError("num_experts must divide into n_group groups")
        if config.score_func not in ("sigmoid", "softmax"):
            raise ValueError(f"score_func {config.score_func!r} is neither sigmoid nor softmax")
        if config.expert_form not in ("swiglu", "reglu", "relu2"):
            raise ValueError(f"expert_form {config.expert_form!r} is none of swiglu, reglu and relu2")
        if config.gated_shared and not config.shared_hidden:
            raise ValueError("gated_shared gates a shared expert, and shared_hidden is 0")
        first, count = config.experts_held
        if first < 0 or count < 1 or first + count > config.num_experts:
            raise ValueError(f"experts_held {config.experts_held} outside 0..{config.num_experts}")
        # set when the experts are traced: "gmm" or "ragged_dot"
        self.path: Optional[str] = None
        # an expert's matrices in the order they are applied, and the shared one's
        gate = () if config.expert_form == "relu2" else ("gate",)
        self.expert_leaves = tuple(f"w_{n}" for n in (*gate, "up", "down"))
        self.shared_leaves = tuple(f"shared_{n}" for n in (*gate, "up", "down")) if config.shared_hidden else ()

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        held = cfg.experts_held[1]
        keys = jax.random.split(key, 7)

        def normal(k, shape, fan_in, dtype=cfg.dtype):
            return (jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)).astype(dtype)

        params = {
            "router": normal(keys[0], (cfg.dim, cfg.num_experts), cfg.dim, jnp.float32),
            "w_gate": normal(keys[1], (held, cfg.dim, cfg.expert_hidden), cfg.dim),
            "w_up": normal(keys[2], (held, cfg.dim, cfg.expert_hidden), cfg.dim),
            "w_down": normal(keys[3], (held, cfg.expert_hidden, cfg.dim), cfg.expert_hidden),
        }
        if cfg.selection_bias:
            # state the optimizer does not own (``HSDPTrainer``): moved by
            # the load, never by a gradient
            params["bias"] = jnp.zeros((cfg.num_experts,), jnp.float32)
        if cfg.shared_hidden:
            params.update(
                shared_gate=normal(keys[4], (cfg.dim, cfg.shared_hidden), cfg.dim),
                shared_up=normal(keys[5], (cfg.dim, cfg.shared_hidden), cfg.dim),
                shared_down=normal(keys[6], (cfg.shared_hidden, cfg.dim), cfg.shared_hidden),
            )
        if cfg.gated_shared:
            # a key of its own: the seven above give every other leaf what they gave
            params["shared_sigmoid"] = normal(jax.random.fold_in(key, 7), (cfg.dim,), cfg.dim, jnp.float32)
        if cfg.expert_form == "relu2":  # no gate matrix, in either kind of expert
            params = {k: v for k, v in params.items() if not k.endswith("_gate")}
        return params

    def param_specs(self) -> Dict[str, Any]:
        """One chip's share: nothing here is divided further."""
        cfg = self.config
        specs = {"router": P(None, None), **{name: P(None, None, None) for name in self.expert_leaves}}
        if cfg.selection_bias:
            specs["bias"] = P(None)
        specs.update({name: P(None, None) for name in self.shared_leaves})
        if cfg.gated_shared:
            specs["shared_sigmoid"] = P(None)
        return specs

    # ------------------------------------------------------------------

    @device_part("experts_route")
    def route(self, params: Dict[str, Any], x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """x [T, D] → (chosen experts [T, k] int32, their weights [T, k]
        float32, the unbiased scores [T, E] float32)."""
        cfg = self.config
        E, G = cfg.num_experts, cfg.n_group
        logits = jnp.dot(
            x.astype(jnp.float32), params["router"], precision=jax.lax.Precision.HIGHEST
        )
        scores = jax.nn.softmax(logits, axis=-1) if cfg.score_func == "softmax" else jax.nn.sigmoid(logits)
        biased = scores + jax.lax.stop_gradient(params["bias"]) if cfg.selection_bias else scores
        if G > 1:
            grouped = biased.reshape(-1, G, E // G)
            group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # [T, G]
            _, keep = jax.lax.top_k(group_score, cfg.topk_group)
            group_kept = jnp.sum(jax.nn.one_hot(keep, G, dtype=jnp.int32), axis=1) > 0  # [T, G]
            biased = jnp.where(group_kept[:, :, None], grouped, -jnp.inf).reshape(-1, E)
        _, chosen = jax.lax.top_k(biased, cfg.top_k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if cfg.norm_topk_prob:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
        return chosen, weights * cfg.routed_scaling_factor, scores

    def _grouped(self, lhs: jax.Array, rhs: jax.Array, sizes: jax.Array) -> jax.Array:
        """Rows ``lhs`` [m, k], sorted by expert, each through its expert's
        matrix of ``rhs`` [held, k, n]; rows past ``sum(sizes)`` are
        undefined."""
        if assumed_backend() == "tpu":
            self.path = "gmm"
            return grouped_product(lhs, rhs, sizes)
        self.path = "ragged_dot"
        return jax.lax.ragged_dot(lhs, rhs, sizes)

    def _activate(self, hidden: List[jax.Array], limit: float) -> jax.Array:
        """An expert's hidden activation from its matrices' products, by
        ``expert_form``: ``[gate, up]`` or ``[up]``."""
        form = self.config.expert_form
        if form == "swiglu":
            return swiglu(*hidden, limit)
        if limit:
            raise ValueError("a SwiGLU clamp was given to experts " + ("whose gate is a ReLU" if form == "reglu" else "that have no gate"))
        return reglu(*hidden) if form == "reglu" else relu2(*hidden)

    def _through(
        self, size: int, limit: float, at: jax.Array, into: jax.Array,
        x: jax.Array, weights: jax.Array, *rest: jax.Array,
    ) -> jax.Array:
        """``into`` [T, D] float32 and what ONE pass of the buffer adds of
        the held experts' part: the ``size`` (token, choice) pairs from ``at``
        on in ``order`` (by held expert; those of absent experts last).
        ``rest`` is the experts' matrices (``expert_leaves``), ``order``
        (padded to whole passes), ``sizes``."""
        *w_in, w_down, order, sizes = rest
        k = weights.shape[1]
        pair = jax.lax.dynamic_slice(order, (at,), (size,))
        token = pair // k
        # this pass's share of every held expert's rows
        ends = jnp.cumsum(sizes)
        here = jnp.clip(ends, at, at + size) - jnp.clip(ends - sizes, at, at + size)
        routed = at + jnp.arange(size) < ends[-1]
        # rows past the routed ones are masked on BOTH sides of the grouped
        # products: a grouped kernel leaves them unwritten, in the backward
        # pass too, and what lies there (a NaN, sooner or later) must reach
        # neither the result nor x's gradient
        xs = jnp.where(routed[:, None], jnp.take(x, token, axis=0), 0)
        act = self._activate([self._grouped(xs, w, here) for w in w_in], limit)
        ys = self._grouped(act.astype(xs.dtype), w_down, here)
        w_row = jnp.where(routed, jnp.take(weights.reshape(-1), pair), 0.0)
        ys = jnp.where(routed[:, None], ys, 0).astype(jnp.float32) * w_row[:, None]
        return into.at[token].add(ys)

    # the whole of it is the dispatch; the grouped products inside keep their kernels' names
    @device_part("experts_dispatch")
    def _held_part(
        self, params: Dict[str, Any], x: jax.Array, chosen: jax.Array, weights: jax.Array,
        sizes: jax.Array, limit: float,
    ) -> jax.Array:
        """What the held experts add, [T, D] float32.

        The rows go through a buffer of ``buffer_size`` rows in as many
        passes as they need, one loop whose trip count is the step's own:
        one program, whatever arrived.  A loop of that kind has no transpose,
        and the backward pass should keep nothing of a pass but its inputs
        (what jax keeps of a conditional's branches was 60 % of the layer's
        time on the chip, PERF.md section 6, PR 29), so the whole part is
        one ``custom_vjp`` that keeps its inputs and, when its gradient is
        asked for, walks the same passes again, each forward and back, and
        adds up what they give."""
        cfg = self.config
        T, k = chosen.shape
        first, held = cfg.experts_held
        local = chosen - first
        here = (local >= 0) & (local < held)
        size = buffer_size(T, k, held, cfg.num_experts)
        order = jnp.argsort(jnp.where(here, local, held).reshape(-1), stable=True)
        order = jnp.pad(order, (0, -(T * k) % size))  # whole passes: the last one's slice stays inside

        def passes(body, start, sizes):
            """``body(at, carry)`` once a pass the step's rows need; the
            first stands outside the loop (it runs whatever arrived, as the
            one buffer did), so that the usual step's program has no loop's
            carry in it: carried from zeros, the backward pass's sums of the
            experts' matrices' gradients cost 3 GB more by the compiler's
            count, PERF.md section 6, PR 42."""
            more = buffer_passes(size, jnp.sum(sizes))
            return jax.lax.fori_loop(1, more, lambda i, c: body(i * size, c), body(0, start))

        @jax.custom_vjp
        def part(*operands):  # x, weights, the experts' matrices, order, sizes
            into = jnp.zeros((T, operands[0].shape[1]), jnp.float32)
            return passes(lambda at, into: self._through(size, limit, at, into, *operands), into, operands[-1])

        def part_fwd(*operands):
            return part(*operands), operands

        def part_bwd(operands, g):
            *floats, order, sizes = operands

            def grads(at, so_far):
                through = lambda *a: self._through(size, limit, at, jnp.zeros_like(g), *a, order, sizes)  # noqa: E731
                return tuple(a + b for a, b in zip(so_far, jax.vjp(through, *floats)[1](g)))

            # the two integer operands have no cotangent
            return (*passes(grads, tuple(jnp.zeros_like(f) for f in floats), sizes), None, None)

        part.defvjp(part_fwd, part_bwd)
        return part(x, weights, *(params[name] for name in self.expert_leaves), order, sizes)

    def buffer_rows(self, tokens: int, rows: jax.Array) -> jax.Array:
        """The buffer's rows that ``_held_part`` moves for ``rows`` routed
        pairs (one an expert layer) in a step of ``tokens`` tokens, float32:
        its size times its passes, the counter's side of ``buffer_size``."""
        cfg = self.config
        size = buffer_size(tokens, cfg.top_k, cfg.experts_held[1], cfg.num_experts)
        return (size * buffer_passes(size, rows)).astype(jnp.float32)

    def route_summary(self, loads: List[jax.Array], tokens: int) -> jax.Array:
        """Of this replica's step of ``tokens`` tokens, on the device:
        ``[expert layers, 4]`` in the order of ``ROUTE_FIELDS``: the rows
        routed to the held experts, their largest and mean load and the
        rows of the experts' buffer they went through (``buffer_rows``),
        expert layer by expert layer (``loads``: what ``apply`` counted, a
        stacked leaf one row a layer)."""
        first, held = self.config.experts_held
        here = jnp.concatenate([x.reshape(-1, x.shape[-1]) for x in loads])[:, first : first + held]
        rows = here.sum(axis=1)
        return jnp.stack([rows, here.max(axis=1), here.mean(axis=1), self.buffer_rows(tokens, rows)], axis=1)

    def apply(
        self, params: Dict[str, Any], x: jax.Array,
        swiglu_limit: float = 0.0, shared_swiglu_limit: float = 0.0,
        route_from: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """x [B, S, D] → (this chip's part of the layer [B, S, D], the
        tokens every one of the ``num_experts`` was chosen by [E] float32,
        the sequence-wise balance loss, weighted).  The router reads x as it
        is given and the experts read it in the matrices' dtype: a caller
        whose residual stream is float32 hands over float32, so that the
        choice of experts does not turn on bfloat16's rounding of x, and
        gets its part back in float32.  ``route_from`` [B, S, D]: what the
        router reads where that is NOT what the experts read (a model whose
        router stands before its attention and whose experts after it); the
        gradient of the weights then reaches ``route_from`` and never x."""
        cfg = self.config
        B, S, D = x.shape
        with device_part("stream"):
            flat = x.reshape(B * S, D)
            routed_on = flat if route_from is None else route_from.reshape(B * S, D)
        chosen, weights, scores = self.route(params, routed_on)
        with device_part("stream"):
            flat = flat.astype(cfg.dtype)
        with device_part("experts_route"):
            picked = jax.nn.one_hot(chosen, cfg.num_experts, dtype=jnp.float32).sum(axis=1)  # [T, E]
            load = jax.lax.stop_gradient(picked.sum(axis=0))
            first, held = cfg.experts_held
            sizes = load[first : first + held].astype(jnp.int32)
        out = self._held_part(params, flat, chosen, weights, sizes, swiglu_limit)
        if cfg.shared_hidden:
            with device_part("ffn"):
                *w_in, w_down = (params[name] for name in self.shared_leaves)
                act = self._activate([flat @ w for w in w_in], shared_swiglu_limit)
                shared = (act @ w_down).astype(jnp.float32)
                if cfg.gated_shared:  # the gate reads x as the router does: as it is given, in float32
                    gate = jnp.dot(
                        x.reshape(B * S, D).astype(jnp.float32), params["shared_sigmoid"],
                        precision=jax.lax.Precision.HIGHEST,
                    )
                    shared = shared * jax.nn.sigmoid(gate)[:, None]
                out = out + shared
        balance = jnp.zeros((), jnp.float32)
        if cfg.balance_loss_weight:
            # per sequence: f_i the share of choices that fell on expert i
            # (times E / k), P_i the mean normalised score; sum_i f_i P_i
            with device_part("experts_route"):
                f = jax.lax.stop_gradient(picked.reshape(B, S, -1).mean(axis=1)) * (
                    cfg.num_experts / cfg.top_k
                )
                p = (scores / jnp.sum(scores, axis=-1, keepdims=True)).reshape(B, S, -1).mean(axis=1)
                balance = cfg.balance_loss_weight * jnp.mean(jnp.sum(f * p, axis=-1))
        with device_part("stream"):
            return out.astype(x.dtype).reshape(B, S, D), load, balance
