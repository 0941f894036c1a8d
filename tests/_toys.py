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
import hashlib
import importlib
import threading

import jax
import numpy as np
import optax
import pytest

from torchft_tpu import tier as tier_mod
from torchft_tpu.manager import Manager
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


# -- two replica groups under real Managers (the ``test_<model>_hsdp.py`` files) --

# How long a replica waits for the other to stand (its Manager made and
# heartbeating, its trainer built): both first lives before either's first
# quorum, and the killed replica's new life before the survivor's next one.
# Nothing is compiled in it (``trainer``; a first life's ``replica_group``
# traces the toy's ``init`` for its shapes, under a second), so what it
# covers is a dead life's ``Manager.shutdown()`` and a Manager's servers,
# a few seconds beside busy workers
STANDING_WAIT_S = 60.0


class _Killed(Exception):
    pass


def digest(params) -> str:
    """One hash over every leaf's bytes."""
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(params):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def two_replica_walk(make_model, make_batch, total, kill_at=None, quantized=(), record=None):
    """Two replica groups as threads on the CPU's first two devices, a
    lighthouse, a real Manager a life, ``total`` fleet steps.  Each replica
    has a batch of its own (``make_batch(model, mesh, seed)``), so equal
    leaves REQUIRE what crossed the replica dimension to have been averaged.
    The steps in ``quantized`` run the int8 wire.

    Neither asks for its first quorum before BOTH stand: with a lighthouse
    that lets one replica go on alone (the walk with a kill needs that), a
    replica whose Manager came first walked every step to ``kill_at + 1`` by
    itself in half a second and parked there, heartbeating, and the other,
    one of two heartbeating replicas and so no majority, timed out of its
    first quorum (``tests/test_gated_delta_hsdp.py`` in the driver's run, PR
    61: the two start-ups are 0.7 s each and which is longer turned on what
    else the process had imported).

    Without ``kill_at`` both are in every quorum and every step commits.
    With it replica 1 dies once the fleet is at step ``kill_at``, comes back
    with other weights and heals from the survivor, which waits for the new
    life to stand before it asks for the quorum after the kill; steps alone
    may stall, twice at most.  A replica that fails wakes the one waiting
    for it, so that the failure is reported and not the wait's.

    Asserted here: both finish; the digests of every leaf agree at every
    step the two shared; the parameters moved every step; the shared steps
    are all of them (no kill) or lie on both sides of the kill.  Returns
    ``(the shared steps, [replica -> {fleet step -> record(model, manager,
    trainer)}])``: what a file checks of its own, read after a shared step
    committed."""
    devices = jax.devices()[:2]
    tier = tier_mod.default_tier()
    everyone = 1 if kill_at is not None else 2
    lighthouse = tier_mod.make_lighthouse(
        bind="127.0.0.1:0", min_replicas=everyone, join_timeout_ms=200, quorum_tick_ms=20,
        heartbeat_timeout_ms=2000, tier=tier,
    )
    name = make_model.__module__.rpartition(".")[2]
    managers, errors = [], []
    seen, recorded = [{}, {}], [{}, {}]  # replica -> fleet step -> digest of every leaf / the file's record
    standing, rejoined = threading.Barrier(2), threading.Event()

    def replica(idx):
        model, mesh, _ = replica_group(make_model, idx)
        batch = make_batch(model, mesh, 100 + idx)
        life = 0
        while True:
            manager = Manager(
                comm=tier_mod.make_communicator(timeout_s=30.0, tier=tier),
                load_state_dict=None, state_dict=None, min_replica_size=everyone,
                timeout=30.0, quorum_timeout=30.0, connect_timeout=30.0,
                replica_id=f"{name}_{idx}", lighthouse_addr=lighthouse.local_address(),
                server_cls=tier_mod.manager_server_cls(tier),
            )
            managers.append(manager)
            # the new life finds the step's programs compiled (``trainer``): the
            # survivor's ring does not wait out its 30 s while they compile (D13 (b))
            made = trainer(make_model, idx, manager, jax.random.PRNGKey(10 * life + 1), learning_rate=1e-3)
            if life:
                rejoined.set()
            else:
                standing.wait(timeout=STANDING_WAIT_S)
            try:
                stalled = 0
                while (step := manager.current_step()) < total:
                    if kill_at is not None and life == 0 and idx == 1 and step >= kill_at:
                        raise _Killed()
                    if kill_at is not None and idx == 0 and step == kill_at + 1:
                        assert rejoined.wait(timeout=STANDING_WAIT_S), "the killed replica never came back"
                    made.quantize_outer = step in quantized
                    loss, committed = made.train_step(batch)
                    assert np.isfinite(loss)
                    stalled = 0 if committed else stalled + 1
                    assert committed or (kill_at is not None and step >= kill_at and stalled < 3), manager.errored()
                    if committed and manager.num_participants() == 2:
                        seen[idx][manager.current_step()] = digest(made.holder["params"])
                        if record is not None:
                            recorded[idx][manager.current_step()] = record(model, manager, made)
                return
            except _Killed:
                life += 1
                manager.shutdown()
                managers.remove(manager)

    def guarded(idx):
        try:
            with jax.default_device(devices[idx]):
                replica(idx)
        except BaseException as e:  # noqa: BLE001 — raised again below
            errors.append(e)
            standing.abort()
            rejoined.set()

    threads = [threading.Thread(target=guarded, args=(i,), daemon=True) for i in range(2)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300.0)
        assert not errors, errors
        assert not any(t.is_alive() for t in threads)
    finally:
        for m in managers:
            m.shutdown()
        lighthouse.shutdown()
    shared = sorted(set(seen[0]) & set(seen[1]))
    if kill_at is None:
        assert sorted(seen[0]) == sorted(seen[1]) == list(range(1, total + 1)), (seen[0].keys(), seen[1].keys())
    else:
        # steps with both in the quorum: before the kill, and after the heal
        assert any(s <= kill_at for s in shared) and any(s > kill_at + 1 for s in shared), shared
    for step in shared:
        assert seen[0][step] == seen[1][step], f"step {step}"
    assert len({seen[0][step] for step in shared}) == len(shared)  # the parameters moved every step
    return shared, recorded


# -- a model's gradient step, lowered and compiled (``test_device_parts.py``, ``test_lowered_steps.py``) --


# name -> (module of ``torchft_tpu.models``, class, its debug configuration, the
# sequence length the digests of ``tests/fixtures/lowered_steps.json`` were
# written at): the row a new model adds
TOYS = {
    "llama": ("llama", "Llama", "llama_debug", 128),
    "ling_hybrid": ("ling_hybrid", "LingHybrid", "ling_debug", 128),
    "indexed_sparse_moe": ("indexed_sparse_moe", "IndexedSparseMoE", "indexed_sparse_debug", 32),
    "latent_moe": ("latent_moe", "LatentMoE", "latent_moe_debug", 64),
    "windowed_moe": ("windowed_moe", "WindowedMoE", "windowed_moe_debug", 64),
    "eva": ("eva", "Eva", "eva_debug", 64),
    "gated_delta_moe": ("gated_delta_moe", "GatedDeltaMoE", "gated_delta_debug", 64),
    "looped": ("looped", "Looped", "looped_debug", 64),
    "prerouted_moe": ("prerouted_moe", "PreroutedMoE", "prerouted_moe_debug", 64),
    "sambay": ("sambay", "SambaY", "sambay_debug", 64),
    "ssm_hybrid_moe": ("ssm_hybrid_moe", "SsmHybridMoE", "ssm_hybrid_debug", 64),
    "ssm_hybrid_dense": ("ssm_hybrid_dense", "SsmHybridDense", "ssm_hybrid_dense_debug", 64),
}


def toy(name):
    """(model, sequence length) of ``TOYS``; ``llama_remat`` is ``llama``
    with every layer rematerialised."""
    module, cls, debug, seq = TOYS["llama" if name == "llama_remat" else name]
    module = importlib.import_module(f"torchft_tpu.models.{module}")
    config = getattr(module, debug)()
    if name == "llama_remat":
        config = dataclasses.replace(config, remat=True)
    return getattr(module, cls)(config), seq


def _lowered_grad_step(name, path):
    """(model, mesh, the parameters' shapes, the gradient step LOWERED) of
    the toy ``name`` on ``path`` (``plain``, or ``kernels``: the Pallas
    kernels in interpret mode), one row of one sequence."""
    # jax shares a private function between two places of the lowered text
    # where its caches hand both the same jaxpr OBJECT (a rematerialised
    # layer's partial evaluation is cached by the policy's identity, a library
    # function's trace by its shapes), so what a worker traced before decides
    # what is shared: the text is made from the caches as a new process has them
    jax.clear_caches()
    with on_path(path):
        model, seq = toy(name)
        mesh = make_mesh(fsdp=1, devices=jax.devices()[:1])
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        tokens = jax.ShapeDtypeStruct((1, seq), np.int32)
        lowered = make_grad_step(model, mesh).lower(params, (tokens, tokens))
        off_kernels = any(word in model.attention_path for word in ("plain", "naive"))
        assert off_kernels == (path == "plain"), model.attention_path
        return model, mesh, params, lowered


@functools.lru_cache(maxsize=None)
def step_texts(name, path):
    """``dict(lowered, grad, update)``: the text the toy ``name``'s gradient
    step LOWERS to on ``path`` and the texts its two step programs COMPILE to,
    made once a RUN of the tests (``tests/_once.py``) for the file that reads
    the lowered text (``test_lowered_steps.py``) and the one that reads the
    compiled ones (``test_device_parts.py``).  ``--dist load`` hands the two
    files' cases of one toy to different workers five times of six, and each
    then traced the step for itself: 10-25 s a toy and path, the larger half
    of either test (PR 69; ROADMAP.md D13)."""
    from tests._once import once_a_run

    def make():
        model, mesh, params, lowered = _lowered_grad_step(name, path)

        def update():
            tx = optax.adamw(1e-3)
            return make_update_step(model, tx, mesh).lower(params, jax.eval_shape(tx.init, params), params).compile().as_text()

        # the update step knows nothing of the path: one compile a toy
        return dict(lowered=lowered.as_text(), grad=lowered.compile().as_text(), update=once_a_run(f"update-text-{name}", update))

    return once_a_run(f"step-texts-{name}-{path}", make)
