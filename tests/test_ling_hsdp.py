"""The selection bias under ``HSDPTrainer`` and a Manager: state the
optimizer does not own.  It moves only on a committed step, by the step's
per-expert load averaged over the replicas; replicas stay bit-equal; a
healed life has the survivor's.  Toy widths, float32, the CPU's devices."""

from typing import Any, List

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.manager import Manager
from torchft_tpu.models.ling_hybrid import LingHybrid, ling_debug
from torchft_tpu.parallel.hsdp import fsdp_shardings, make_update_step

from tests._toys import replica_group, trainer as group_trainer, two_replica_walk
from tests.test_manager import MemoryTransport, StubClient, _quorum_result

RATE = 1e-3


def ling():
    return LingHybrid(ling_debug())


def _biases(model: LingHybrid, params: Any) -> List[np.ndarray]:
    mask = jax.tree_util.tree_leaves(model.state_mask())
    return [np.asarray(x) for x, is_state in zip(jax.tree_util.tree_leaves(params), mask) if is_state]


def _batch(model, mesh, seed, rows=1, seq=64):
    tokens = np.random.default_rng(seed).integers(0, model.config.vocab_size, (rows, seq)).astype(np.int32)
    batch_sh = fsdp_shardings(model, mesh)[1]
    return tuple(jax.device_put(b, sh) for b, sh in zip((tokens, np.roll(tokens, -1, axis=1)), batch_sh))


def _stub_trainer(steps: int):
    client = StubClient()
    client.quorum_results.extend(_quorum_result() for _ in range(steps))
    manager = Manager(
        comm=DummyCommunicator(), load_state_dict=None, state_dict=None, min_replica_size=1,
        checkpoint_transport=MemoryTransport(), _manager_client=client, rank=0, world_size=1,
    )
    model, mesh, _ = replica_group(ling, 0)
    # weight decay large enough to see, were the optimizer let near a bias
    trainer = group_trainer(ling, 0, manager, jax.random.PRNGKey(0), learning_rate=1e-3, weight_decay=0.5)
    return model, mesh, manager, trainer


def test_committed_step_moves_the_bias_by_the_load_and_nothing_else_does():
    model, mesh, manager, trainer = _stub_trainer(3)
    # start from a bias that is not zero: weight decay would shrink it
    start = jax.tree_util.tree_map(
        lambda p, is_state: p + 0.25 if is_state else p, trainer.holder["params"], model.state_mask()
    )
    trainer.holder["params"] = start
    before = _biases(model, start)
    batch = _batch(model, mesh, 1)
    loss, grads = replica_group(ling, 0)[2](start, batch)
    loads = _biases(model, grads)  # the bias's slot of the gradient tree carries the load
    assert all(float(x.sum(axis=-1).min()) == 64 * 4 for x in loads)  # 64 tokens, 4 experts each
    loss, committed = trainer.train_step(batch)
    assert committed and np.isfinite(loss)
    after = _biases(model, trainer.holder["params"])
    for b0, b1, load in zip(before, after, loads):
        want = b0 + np.float32(RATE) * np.sign(load.mean(axis=-1, keepdims=True) - load)
        np.testing.assert_array_equal(b1, want.astype(np.float32))  # exactly: no decay, no moment
    # the optimizer's moments of those leaves never left zero
    mask = jax.tree_util.tree_leaves(model.state_mask())
    adam = trainer.holder["opt_state"][0]
    for moments in (adam.mu, adam.nu):
        for m, is_state in zip(jax.tree_util.tree_leaves(moments), mask):
            assert not is_state or float(jnp.max(jnp.abs(m))) == 0.0
    # one flight event a committed step, layer by layer
    events = [e for e in manager._flight.snapshot() if e["name"] == "MOE_ROUTE"]
    assert len(events) == 1 and len(events[0]["rows_here"]) == 6  # 4 + 1 + 1 expert layers
    assert events[0]["buffer_rows"] == [64.0 * 4] * 6  # toy: the buffer is every pair, one pass
    assert events[0]["rows_here"] == [float(x) for load in loads for x in load.reshape(-1, 16)[:, 4:8].sum(axis=1)]


def test_uncommitted_step_changes_nothing():
    model, mesh, manager, trainer = _stub_trainer(2)
    batch = _batch(model, mesh, 2)
    before = jax.tree_util.tree_map(np.asarray, trainer.holder)
    manager.should_commit = lambda *a, **k: False  # the fleet votes the step down
    loss, committed = trainer.train_step(batch)
    assert not committed and np.isfinite(loss)
    for a, b in zip(jax.tree_util.tree_leaves(before), jax.tree_util.tree_leaves(trainer.holder)):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert not [e for e in manager._flight.snapshot() if e["name"] == "MOE_ROUTE"]


def test_update_step_keeps_its_signature_and_llama_its_path():
    """``ftbench``'s compile test calls the two builders with these
    arguments for every configuration."""
    from torchft_tpu.models.llama import Llama, llama_debug
    from torchft_tpu.parallel import hsdp

    assert hsdp._state_mask(Llama(llama_debug())) is None
    model, mesh, grad_step = replica_group(ling, 0)  # ``make_grad_step(model, mesh)``, as every test here has it
    tx = optax.adamw(3e-4)
    params = hsdp.shard_init(model, jax.random.PRNGKey(0), mesh)
    batch = _batch(model, mesh, 3)
    loss, grads = grad_step(params, batch)
    assert jax.tree_util.tree_structure(grads) == jax.tree_util.tree_structure(params)
    new, opt_state = make_update_step(model, tx, mesh)(params, tx.init(params), grads)
    assert jax.tree_util.tree_structure(new) == jax.tree_util.tree_structure(grads)


TOTAL, KILL_AT, QUANTIZED_FROM = 9, 5, 3


def test_two_replicas_agree_bit_for_bit_through_a_kill_and_a_live_heal():
    """Two replica groups as threads, a lighthouse, real Managers
    (``tests/_toys.py`` ``two_replica_walk``).  Each has a batch of its own,
    so equal biases REQUIRE the loads to have passed the replica-dimension
    average.  Steps 3 and 4 run the int8 wire (the loads cross it
    unquantised).  Replica 1 dies at step 5, comes back with other weights
    and a zero bias, and heals from the survivor."""
    shared, seen = two_replica_walk(
        ling, _batch, TOTAL, kill_at=KILL_AT, quantized=range(QUANTIZED_FROM, KILL_AT),
        record=lambda model, manager, trainer: _biases(model, trainer.holder["params"]),
    )
    for step in shared:
        for a, b in zip(seen[0][step], seen[1][step]):
            np.testing.assert_array_equal(a, b, err_msg=f"step {step}")
    last = seen[0][shared[-1]]
    assert all(np.abs(b).max() > 0 for b in last)
    # a bias is a sum of +-rate steps: after n commits a multiple of the rate within n of zero
    assert all(np.abs(b).max() <= RATE * TOTAL * (1 + 1e-5) for b in last)
