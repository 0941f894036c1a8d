"""What a decoder's model file shares with every other, written once.

A model of ``models/`` is a class of its own (its ``Config``, ``init``'s tree,
its mixers and ``_block``, its ``objective``); what is common to them is here
as plain functions the model calls, never as a base class to override: the
norm, rope and projection every mixer uses, when one chip's kernels apply,
the one-chip parameter shell, the walk over a stacked run of layers with its
rematerialisation, how ``attention_path`` is settled, the head and its
cross-entropy.  This module imports nothing of ``models/``, and no model
imports another (``models/latent.py``, the MLA mixer and the MTP module two
models share, is the one exception; ``tests/test_layering.py`` holds both).
The routers' state and a step's routing summary are the experts' and live
beside ``RoutedExperts`` (``parallel/moe.py``).
"""

from __future__ import annotations

import functools
import itertools
import logging
import os
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from torchft_tpu.obs.spans import part

logger = logging.getLogger(__name__)


# -- what every mixer uses -------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """The RMS norm under ``weight``, float32 statistics, in x's dtype."""
    x32 = x.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 / rms) * weight).astype(x.dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    """LayerNorm under ``weight`` and ``bias``, float32 statistics, in x's dtype."""
    x32 = x.astype(jnp.float32)
    centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps)
    return (centred * scale * weight + bias).astype(x.dtype)


def unit(x: jax.Array) -> jax.Array:
    """x at unit length over its last axis."""
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + 1e-6)).astype(x.dtype)


@part("mixer_proj")
def proj(x: jax.Array, w: jax.Array) -> jax.Array:
    """A product into or out of a mixer, named so inside the mixer's glue
    (``obs/spans.py``: the innermost scope is the operation's part)."""
    return x @ w


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    # x: [B, S, H, D]; rotate pairs (x1, x2) per RoPE
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    ).astype(x.dtype)


def rope_table(seq: int, width: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """``(cos, sin)`` [1, seq, width / 2] of rope over ``width`` channels,
    position = index, float32."""
    half = width // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(seq, dtype=jnp.float32)[None, :, None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def rope_halves(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over ALL of the last axis, channel ``i`` paired with
    ``i + R / 2``; x [B, S, H, R], position = index, float32 arithmetic."""
    return apply_rope(x, *rope_table(x.shape[1], x.shape[-1], theta))


def short_conv_silu(x: jax.Array, w: jax.Array, bias: Optional[jax.Array] = None) -> jax.Array:
    """Causal depthwise convolution (the last tap is the current token's),
    a bias a channel where one is given, and SiLU.  x [B, S, C], w [K, C]."""
    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    acc = sum(padded[:, j : j + S].astype(jnp.float32) * w[j].astype(jnp.float32) for j in range(K))
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    return jax.nn.silu(acc).astype(x.dtype)


# -- when one chip's kernels apply -----------------------------------------


def assumed_backend() -> str:
    """The platform kernel dispatch plans for.  Normally the runtime
    backend; ``TORCHFT_FLASH_PLATFORM`` overrides it so a device-free
    host can trace the TPU program (``parallel/rehearsal.py`` lowers
    the real Mosaic flash kernels for a pod without owning one)."""
    return os.environ.get("TORCHFT_FLASH_PLATFORM") or jax.default_backend()


def flash_blocks(seq: int) -> Tuple[int, int]:
    """(block_q, block_k) for the flash kernel: env-tunable (the bench
    sweeps them when hunting MFU), clamped to the sequence length.
    A malformed or non-positive override falls back to the 512 default
    (the divisibility gate then decides flash vs naive)."""

    def _env(name: str) -> int:
        try:
            v = int(os.environ.get(name, "512"))
        except ValueError:
            return 512
        return v if v > 0 else 512

    return (
        min(seq, _env("TORCHFT_FLASH_BLOCK_Q")),
        min(seq, _env("TORCHFT_FLASH_BLOCK_K")),
    )


def one_chip_refusal(shape_refusal: Optional[str], mesh: Optional[Any]) -> Optional[str]:
    """Why kernels that are one chip's do NOT apply, or None when they
    do, for the models whose groups are one chip (``shape_refusal``:
    what the model's own kernels say of the sequence length).
    ``TORCHFT_FLASH`` = 1 forces them (interpret mode off the TPU), 0
    kills them, unset: on a TPU, one chip a group."""
    env = os.environ.get("TORCHFT_FLASH", "")
    if env == "0":
        return "TORCHFT_FLASH=0"
    if shape_refusal:
        return shape_refusal
    if env == "1":
        return None
    backend = assumed_backend()
    if backend != "tpu":
        return f"backend is {backend}, not tpu"
    mesh_size = 1 if mesh is None else int(np.prod(list(mesh.shape.values())))
    if mesh_size > 1:
        return f"a group of {mesh_size} chips: the kernels are one chip's"
    return None


def kernel_refusal(seq: int, mesh: Optional[Any], chunk: Optional[int] = None) -> Optional[str]:
    """Why the Mosaic kernels do NOT apply, or None when they do, for a
    model whose layers call ``flash_attention`` over the whole sequence and,
    with ``chunk``, a kernel that walks the sequence in chunks of so many
    tokens (a sequence shorter than a chunk is one)."""
    block_q, block_k = flash_blocks(seq)
    chunk = None if chunk is None else min(chunk, seq)
    shape_refusal = None
    if seq < 32 or seq % 8 or seq % block_q or seq % block_k or (chunk and seq % chunk):
        shape_refusal = f"seq={seq} does not divide into the blocks ({block_q}, {block_k})"
        if chunk:
            shape_refusal += f" and chunks of {chunk}"
    return one_chip_refusal(shape_refusal, mesh)


def kernel_path(model: Any, name: str, refusal: Optional[str], moe_path: Optional[str] = None) -> bool:
    """Settles ``model.attention_path`` once the layers are traced and says
    whether the kernels ran: ``name`` if no kernel refused (``refusal`` is
    None) and, on a TPU, the experts took the grouped kernel (``moe_path``:
    ``RoutedExperts.path``, None for a model without experts); ``"plain:
    <why>"`` otherwise.  A change of path is logged."""
    if refusal is None and moe_path not in (None, "gmm") and assumed_backend() == "tpu":
        refusal = f"the experts took {moe_path}"
    path = name if refusal is None else f"plain: {refusal}"
    if path != model.attention_path:
        logger.info("attention path: %s", path)
    model.attention_path = path
    return refusal is None


# -- the one-chip parameter shell ------------------------------------------


def shapes(init: Callable[[jax.Array], Any]) -> Any:
    """What ``init`` would make, as shapes (a model traces it once and keeps
    it: ``_shapes``)."""
    return jax.eval_shape(init, jax.random.PRNGKey(0))


def one_chip_param_specs(shapes: Any) -> Any:
    """One chip's share of a larger job: every leaf whole on the group's
    one chip (the ``fsdp`` axis of such a model's meshes has size 1)."""
    return jax.tree_util.tree_map(lambda s: P(*([None] * len(s.shape))), shapes)


def batch_specs() -> Tuple[Any, Any]:
    """(tokens, targets): the batch over ``(dp, fsdp)``, the sequence whole."""
    spec = P(("dp", "fsdp"), None)
    return spec, spec


def num_params(shapes: Any) -> int:
    return sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))


def seeded(key: jax.Array, shape: Tuple[int, ...], fan_in: float, dtype: Any) -> jax.Array:
    """What a projection starts at: float32 normals over ``sqrt(fan_in)``,
    in ``dtype``."""
    return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)).astype(dtype)


def dense_ffn_init(keys: Sequence[jax.Array], dim: int, hidden: int, dtype: Any) -> Dict[str, jax.Array]:
    """A dense SwiGLU's three matrices from three keys."""
    return {
        "w_gate": seeded(keys[0], (dim, hidden), dim, dtype), "w_up": seeded(keys[1], (dim, hidden), dim, dtype),
        "w_down": seeded(keys[2], (hidden, dim), hidden, dtype),
    }


def embed_and_head(
    k_embed: jax.Array, k_out: jax.Array, vocab: int, dim: int, dtype: Any, embed_std: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """The two leaves every ``init`` makes the same way, ``(embed [vocab,
    dim], lm_head [dim, vocab])``: float32 normals times a standard
    deviation, then the model's dtype.  The embedding's rows are of unit
    variance unless the model says otherwise, so that a token's own
    embedding leads the stream its first routers read (PERF.md section 6, PR
    33); the head's of ``1 / sqrt(dim)``."""

    def normal(k, shape, std):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    return normal(k_embed, (vocab, dim), embed_std), normal(k_out, (dim, vocab), dim ** -0.5)


def runs(kinds: Iterable[Any]) -> List[Tuple[Any, int]]:
    """Runs of contiguous layers of one kind: (kind, how many).  A run's
    layers are stacked on a leading axis and walked by ONE scan."""
    return [(kind, len(list(run))) for kind, run in itertools.groupby(kinds)]


def init_runs(init_layer: Callable[[Any, jax.Array], Any], key: jax.Array, groups: Sequence[Tuple[Any, int]]) -> List[Any]:
    """``init_layer(kind, key)`` of every layer, the layers of run ``n``
    stacked on a leading axis from the keys ``split(fold_in(key, n), depth)``."""
    return [
        jax.vmap(functools.partial(init_layer, kind))(jax.random.split(jax.random.fold_in(key, n), depth))
        for n, (kind, depth) in enumerate(groups)
    ]


# -- the walk over a stacked run of layers ---------------------------------


Kept = Union[Sequence[str], Callable[..., bool]]


def remat(block: Callable[..., Any], depth: int, keep: Kept = (), prevent_cse: Optional[bool] = None) -> Callable[..., Any]:
    """``block``, one layer of a run of ``depth``, rematerialised in the
    backward pass from its input and from what it names among ``keep``
    (``jax.ad_checkpoint.checkpoint_name``: a kernel's output and row
    statistics, so that a dear forward kernel stands once in a step).
    ``keep`` may be a policy of ``jax.checkpoint_policies`` instead of names:
    jax caches a layer's partial evaluation by the policy OBJECT, so runs
    that are handed one object share their private functions in the lowered
    text, and a model whose text was made so hands over one.

    THE rule of ``prevent_cse``, for every model: jax's guard against XLA
    merging the rematerialised forward with the first one stays ON for a run
    of ONE layer, which is no loop once XLA has simplified its scan, and for
    a layer that no scan runs at all (a prediction module's): merged, the two
    keep every intermediate alive, 3.2 GB a state-space layer at the
    published widths (``models/ssm_hybrid_moe.py``, PERF.md section 6, PR
    35).  Inside a real loop the guard is not needed (per jax's docs) and its
    optimisation barriers cost memory and step time, so it is OFF.  A model
    that has passed a constant whatever the depth passes it
    (``prevent_cse``)."""
    if prevent_cse is None:
        prevent_cse = depth == 1
    policy = keep if callable(keep) else jax.checkpoint_policies.save_only_these_names(*keep)
    return jax.checkpoint(block, policy=policy, prevent_cse=prevent_cse)


def scan_run(
    block: Callable[[jax.Array, Any], Tuple[jax.Array, Any]], x: jax.Array, stacked: Any, depth: int,
    keep: Optional[Kept] = (), prevent_cse: Optional[bool] = None,
) -> Tuple[jax.Array, Any]:
    """``x`` through a run of ``depth`` layers of one kind whose leaves are
    ``stacked`` on a leading axis: ONE ``lax.scan`` of ``block(x, w) -> (x,
    what the layer reports)`` under ``tpuft.layers``, each layer
    rematerialised (:func:`remat`: the model says what a layer of this run
    keeps, and nothing else about it).  ``keep`` None: the layers are not
    rematerialised and keep what they made."""
    if keep is not None:
        block = remat(block, depth, keep, prevent_cse)
    with part("layers"):
        return jax.lax.scan(block, x, stacked)


# -- the head and its cross-entropy ----------------------------------------


def head_logits(x: jax.Array, final_norm: jax.Array, lm_head: jax.Array, eps: float, dtype: Any) -> jax.Array:
    """The final norm of the stream in the matrices' ``dtype`` and the head,
    under whatever part the caller stands in; the products' float32 sums as
    they are: a logit is never rounded to the model's dtype."""
    return jnp.dot(rms_norm(x, final_norm, eps).astype(dtype), lm_head, preferred_element_type=jnp.float32)


def token_nll(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """The cross-entropy of ``labels`` [B, S] under ``logits`` [B, S, V]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def blocked_nll(logits_of: Callable[[jax.Array], jax.Array], x: jax.Array, targets: jax.Array, block: int) -> jax.Array:
    """The cross-entropy of every position of ``x`` ``[..., S, D]`` under
    ``logits_of(rows)``, float32 in x's leading shape, ``block`` positions at a
    time (the whole sequence where a block does not divide it): a block's
    logits are made again in the backward pass, never kept, and no ``[S,
    vocab]`` is ever whole.  ``targets`` ``[B, S]`` serve every leading axis
    before the batch's."""
    *lead, S, D = x.shape
    block = block if S % block == 0 else S
    labels = jnp.broadcast_to(targets, (*lead, S)).reshape(-1, block)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def of_block(rows):
        return token_nll(logits_of(rows[0]), rows[1])

    return jax.lax.map(of_block, (x.reshape(-1, block, D), labels)).reshape(*lead, S)


@part("head")
def mean_nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """The mean next-token cross-entropy."""
    return jnp.mean(token_nll(logits, targets))
