"""``IndexedSparseMoE`` under ``HSDPTrainer`` and a Manager: a model that
reports its step (loads, the index's loss, keys a query) WITHOUT state the
optimizer does not own.  Steps commit, the summary rides the loss's one
transfer into MOE_ROUTE, replicas stay bit-equal through a kill and a live
heal.  Toy widths, float32, the CPU's devices."""

from typing import Any, Dict, List

import jax
import numpy as np

from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.manager import Manager
from torchft_tpu.models.indexed_sparse_moe import SUMMARY_FIELDS, IndexedSparseMoE, indexed_sparse_debug
from torchft_tpu.parallel import hsdp
from torchft_tpu.parallel.hsdp import fsdp_shardings

from tests._toys import replica_group, trainer as group_trainer, two_replica_walk
from tests.test_manager import MemoryTransport, StubClient, _quorum_result


def toy():
    return IndexedSparseMoE(indexed_sparse_debug())

SEQ = 32  # twice the toy index's 16 keys


def _batch(model, mesh, seed, rows=1):
    tokens = np.random.default_rng(seed).integers(0, model.config.vocab_size, (rows, SEQ)).astype(np.int32)
    batch_sh = fsdp_shardings(model, mesh)[1]
    return tuple(jax.device_put(b, sh) for b, sh in zip((tokens, np.roll(tokens, -1, axis=1)), batch_sh))


def _stub_trainer(steps: int):
    client = StubClient()
    client.quorum_results.extend(_quorum_result() for _ in range(steps))
    manager = Manager(
        comm=DummyCommunicator(), load_state_dict=None, state_dict=None, min_replica_size=1,
        checkpoint_transport=MemoryTransport(), _manager_client=client, rank=0, world_size=1,
    )
    model, mesh, _ = replica_group(toy, 0)
    trainer = group_trainer(toy, 0, manager, jax.random.PRNGKey(0), learning_rate=1e-3)
    return model, mesh, manager, trainer


def _routes(manager) -> List[Dict[str, Any]]:
    return [e for e in manager._flight.snapshot() if e["name"] == "MOE_ROUTE"]


def test_a_model_without_optimizer_free_state_still_reports_its_step():
    from torchft_tpu.models.ling_hybrid import LingHybrid, ling_debug
    from torchft_tpu.models.llama import Llama, llama_debug

    model, mesh, manager, trainer = _stub_trainer(2)
    # what the trainer asks of the three models: Llama nothing, Ling both, this one the report alone
    assert not hsdp._reports(Llama(llama_debug())) and hsdp._state_mask(Llama(llama_debug())) is None
    assert hsdp._reports(LingHybrid(ling_debug())) and hsdp._state_mask(LingHybrid(ling_debug())) is not None
    assert hsdp._reports(model) and trainer._state_mask is None
    batch = _batch(model, mesh, 1)
    before = jax.tree_util.tree_map(np.asarray, trainer.holder["params"])
    report, grads = replica_group(toy, 0)[2](trainer.holder["params"], batch)
    layers = model.config.n_layers
    assert report.shape == (1 + len(SUMMARY_FIELDS) * layers,)  # the loss and the summary, ONE array
    assert jax.tree_util.tree_structure(grads) == jax.tree_util.tree_structure(before)
    loss, committed = trainer.train_step(batch)
    assert committed and loss == float(report[0])
    assert loss > float(model.loss(before, batch))  # the objective: the index's and the balance loss on top
    events = _routes(manager)
    assert len(events) == 1
    stats = model.summary_stats(np.asarray(report[1:]))
    for name in SUMMARY_FIELDS:
        assert events[0][name] == stats[name] and len(events[0][name]) == layers
    assert events[0]["keys_per_query"] == [(16 * 17 / 2 + 16 * 16) / SEQ] * layers
    assert all(kl > 0 for kl in events[0]["index_kl"])
    assert all(rows > 0 for rows in events[0]["rows_here"])
    # every leaf moved, the index's by its own loss
    after = jax.tree_util.tree_map(np.asarray, trainer.holder["params"])
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(before)[0], jax.tree_util.tree_leaves(after)):
        assert np.abs(a - b).max() > 0, jax.tree_util.keystr(path)


def test_uncommitted_step_changes_nothing_and_records_nothing():
    model, mesh, manager, trainer = _stub_trainer(2)
    batch = _batch(model, mesh, 2)
    before = jax.tree_util.tree_map(np.asarray, trainer.holder)
    manager.should_commit = lambda *a, **k: False  # the fleet votes the step down
    loss, committed = trainer.train_step(batch)
    assert not committed and np.isfinite(loss)
    for a, b in zip(jax.tree_util.tree_leaves(before), jax.tree_util.tree_leaves(trainer.holder)):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert not _routes(manager)


TOTAL, KILL_AT, QUANTIZED_FROM = 8, 4, 2


def test_two_replicas_commit_agree_bit_for_bit_and_heal_a_killed_one():
    """Two replica groups as threads, a lighthouse, real Managers, a batch
    each (``tests/_toys.py`` ``two_replica_walk``).  Steps 2 and 3 run the
    int8 wire.  Replica 1 dies at step 4, comes back with other weights and
    heals from the survivor."""
    shared, routes = two_replica_walk(
        toy, _batch, TOTAL, kill_at=KILL_AT, quantized=range(QUANTIZED_FROM, KILL_AT),
        record=lambda model, manager, trainer: len(_routes(manager)),
    )
    # an event a committed step of a life: the survivor's every step, the killed one's first life
    assert routes[0][shared[-1]] >= TOTAL - 1 and routes[1][KILL_AT] >= KILL_AT
