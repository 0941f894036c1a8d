"""What the models' test files share of their toys, made once a process.

A toy model's gradient step is 5-10 s to trace and as long again to compile
on a loaded worker, and tier-1 is bound by the cores (ROADMAP.md D13): a
program that two tests or two files need is made by the first and found by
the others.  ``jax.jit`` keeps a compiled program with the WRAPPER it was
called through, so what is shared here is the wrapper.
"""

import contextlib
import dataclasses
import functools

import jax
import numpy as np
import optax
import pytest

from torchft_tpu.parallel.hsdp import HSDPTrainer, fsdp_shardings, make_grad_step, make_update_step
from torchft_tpu.parallel.mesh import make_mesh


# -- a model's three programs and its gradient's jaxpr (the ``test_<model>.py`` files) --


@contextlib.contextmanager
def on_path(path):
    """``TORCHFT_FLASH`` as the ``plain`` or the ``kernels`` path (the Pallas
    kernels in interpret mode) reads it, for what is traced inside."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("TORCHFT_FLASH", "1" if path == "kernels" else "0")
        yield


def program_side(model, params, batch, path):
    """(logits, loss, ((objective, (signal, summary)), gradients)): the
    model's ``apply``, ``loss`` and ``value_and_grad(objective)`` of one batch
    as ONE program traced on ``path`` (three programs compiled the forward
    pass three times).  The model is this call's own: what it traces depends
    on the path."""

    def every(p, b):
        return model.apply(p, b[0]), model.loss(p, b), jax.value_and_grad(model.objective, has_aux=True)(p, b)

    with on_path(path):
        return jax.jit(every)(params, batch)


def gradients_jaxpr(model, params, batch):
    """The text of the jaxpr of the objective's gradient on the kernels'
    path: where a test counts each kernel's launches."""
    with on_path("kernels"):
        return str(jax.make_jaxpr(jax.grad(lambda p: model.objective(p, batch)[0]))(params))


# -- a replica group's trainer (the ``test_<model>_hsdp.py`` files) ----------


def _whoever_calls(program):
    """``program`` (its shardings name its devices), found compiled by a
    replica's thread inside ``jax.default_device(its own)`` and by a test
    outside one: the default device is part of what jit keys a compiled
    program by, and says nothing to these."""

    def call(*args):
        with jax.default_device(None):
            return program(*args)

    return call


@functools.lru_cache(maxsize=None)
def replica_group(make_model, idx):
    """(model, mesh, gradient step) of a replica group on the CPU's device
    ``idx``; ``make_model`` is a function of the test file's own."""
    model = make_model()
    mesh = make_mesh(fsdp=1, devices=[jax.devices()[idx]])
    return model, mesh, _whoever_calls(make_grad_step(model, mesh))


@functools.lru_cache(maxsize=None)
def _optimizer(make_model, idx, adamw):
    model, mesh, _ = replica_group(make_model, idx)
    tx = optax.adamw(**dict(adamw))
    return tx, _whoever_calls(make_update_step(model, tx, mesh))


@functools.lru_cache(maxsize=None)
def _init(make_model):
    """``shard_init``'s program for the group on device 0: a toy's ``init``
    takes as long to compile as its gradient step (one threefry program a
    leaf), and a key gives the same bits on every device of the CPU."""
    model, mesh, _ = replica_group(make_model, 0)
    with mesh:
        return _whoever_calls(jax.jit(model.init, out_shardings=fsdp_shardings(model, mesh)[0]))


def trainer(make_model, idx, manager, key, **adamw):
    """An ``HSDPTrainer`` of the group on device ``idx`` under ``manager``,
    with ``optax.adamw(**adamw)`` and the parameters ``init`` makes of
    ``key``.  ``HSDPTrainer`` builds its two step programs anew, so every
    trainer compiled them: three or four times a file, and a replica's NEW
    life after a kill once more, 40 s on a loaded worker, while the
    survivor's ring waited out its 30 s (ROADMAP.md D13 (b): the flake of
    ``test_ling_hsdp.py``).  Here a trainer takes the group's programs as the
    first one made them: a new life finds them compiled before the kill."""
    model, mesh, grad_step = replica_group(make_model, idx)
    tx, update_step = _optimizer(make_model, idx, tuple(sorted(adamw.items())))
    params = jax.device_put(_init(make_model)(key), fsdp_shardings(model, mesh)[0])
    made = HSDPTrainer(model, tx, mesh, manager, params=params)
    made._grad_step, made._update_step = grad_step, update_step
    return made


# -- a model's gradient step, lowered (``test_device_parts.py``, ``test_lowered_steps.py``) --


def toy(name):
    """(model, sequence length): the sequence is the one the digests of
    ``tests/fixtures/lowered_steps.json`` were written at."""
    if name in ("llama", "llama_remat"):
        from torchft_tpu.models.llama import Llama, llama_debug

        return Llama(dataclasses.replace(llama_debug(), remat=name == "llama_remat")), 128
    if name == "ling_hybrid":
        from torchft_tpu.models.ling_hybrid import LingHybrid, ling_debug

        return LingHybrid(ling_debug()), 128
    if name == "indexed_sparse_moe":
        from torchft_tpu.models.indexed_sparse_moe import IndexedSparseMoE, indexed_sparse_debug

        return IndexedSparseMoE(indexed_sparse_debug()), 32
    if name == "latent_moe":
        from torchft_tpu.models.latent_moe import LatentMoE, latent_moe_debug

        return LatentMoE(latent_moe_debug()), 64
    if name == "windowed_moe":
        from torchft_tpu.models.windowed_moe import WindowedMoE, windowed_moe_debug

        return WindowedMoE(windowed_moe_debug()), 64
    if name == "eva":
        from torchft_tpu.models.eva import Eva, eva_debug

        return Eva(eva_debug()), 64
    if name == "gated_delta_moe":
        from torchft_tpu.models.gated_delta_moe import GatedDeltaMoE, gated_delta_debug

        return GatedDeltaMoE(gated_delta_debug()), 64
    if name == "looped":
        from torchft_tpu.models.looped import Looped, looped_debug

        return Looped(looped_debug()), 64
    if name == "ssm_hybrid_moe":
        from torchft_tpu.models.ssm_hybrid_moe import SsmHybridMoE, ssm_hybrid_debug

        return SsmHybridMoE(ssm_hybrid_debug()), 64
    raise KeyError(name)


@functools.lru_cache(maxsize=None)
def lowered_grad_step(name, path):
    """(model, mesh, the parameters' shapes, the gradient step LOWERED) of
    the toy ``name`` on ``path`` (``plain``, or ``kernels``: the Pallas
    kernels in interpret mode), one row of one sequence: traced once a
    process for the file that reads the lowered text and the one that
    compiles it."""
    with on_path(path):
        model, seq = toy(name)
        mesh = make_mesh(fsdp=1, devices=jax.devices()[:1])
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        tokens = jax.ShapeDtypeStruct((1, seq), np.int32)
        lowered = make_grad_step(model, mesh).lower(params, (tokens, tokens))
        off_kernels = any(word in model.attention_path for word in ("plain", "naive"))
        assert off_kernels == (path == "plain"), model.attention_path
        return model, mesh, params, lowered
