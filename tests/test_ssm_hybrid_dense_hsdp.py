"""``SsmHybridDense`` under ``HSDPTrainer`` and a Manager: no state the
optimizer does not own (a mask that is False everywhere), a step's summary of
one number.  A committed step moves every leaf, the tied embedding, the scans'
``A_log`` and both multiplied branches' matrices among them, and its flight
event carries ``decay_min``; two replica groups as threads, each with a batch
of its own, agree bit for bit in every leaf through every commit they share,
over the plain wire through a kill and a live heal and over the int8 wire
(the bucket plan holds ONE tied leaf).  Toy widths, float32, the CPU's devices."""

import jax
import numpy as np
import pytest

from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.manager import Manager
from torchft_tpu.models.ssm_hybrid_dense import SsmHybridDense, ssm_hybrid_dense_debug
from torchft_tpu.parallel import hsdp

from tests._toys import replica_group, trainer as group_trainer, two_replica_walk
from tests.test_ling_hsdp import _batch
from tests.test_manager import MemoryTransport, StubClient, _quorum_result


def toy():
    return SsmHybridDense(ssm_hybrid_dense_debug())


@pytest.fixture(scope="module")
def committed_step():
    """One committed step of one replica: (model, the leaves before, the
    gradient step's report, train_step's result, the leaves after, the
    step's flight events)."""
    client = StubClient()
    client.quorum_results.extend(_quorum_result() for _ in range(2))
    manager = Manager(
        comm=DummyCommunicator(), load_state_dict=None, state_dict=None, min_replica_size=1,
        checkpoint_transport=MemoryTransport(), _manager_client=client, rank=0, world_size=1,
    )
    model, mesh, grad_step = replica_group(toy, 0)
    trainer = group_trainer(toy, 0, manager, jax.random.PRNGKey(0), learning_rate=1e-3)
    batch = _batch(model, mesh, 1)
    before = jax.tree_util.tree_map(np.asarray, trainer.holder["params"])
    report, _ = grad_step(trainer.holder["params"], batch)
    result = trainer.train_step(batch)
    after = jax.tree_util.tree_map(np.asarray, trainer.holder["params"])
    events = [e for e in manager._flight.snapshot() if e["name"] == "MOE_ROUTE"]
    return model, before, np.asarray(report), result, after, events


def test_a_step_reports_the_scans_decay_in_one_array(committed_step):
    model, _, report, (loss, committed), _, events = committed_step
    # it reports, and its mask names no leaf: the optimizer owns them all
    assert hsdp._reports(model) and not any(hsdp._state_mask(model))
    assert model.advance_state([], []) == []
    assert report.shape == (2,)  # the loss, decay_min
    assert committed and loss == float(report[0])
    (event,) = events
    # seeded weights: a token's logit for itself is of order one, a loss near ln(vocabulary)
    assert abs(loss - np.log(model.config.vocab_size)) < 0.4
    # a step of at most 0.1 (and what the seeded W_in adds under the softplus) against A up to 16
    assert -40.0 < event["decay_min"] < -0.3 and event["decay_min"] == pytest.approx(float(report[1]))


def _paths(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_a_committed_step_moves_every_leaf(committed_step):
    _, before, _, _, after, _ = committed_step
    before, after = _paths(before), _paths(after)
    assert len(before) == 34 and "['embed']" in before and not any("lm_head" in name for name in before)
    for name, a in before.items():
        moved = np.abs(after[name] - a)
        # adamw's first step moves a weight by the rate wherever its gradient is not 0 (and decays it
        # a little): the embedding is the head too, so EVERY row moves, not the batch's tokens alone
        assert moved.max() == pytest.approx(1e-3, rel=0.2), name
        assert (moved > 0).mean() > 0.9, name


@pytest.mark.parametrize("quantize,total,kill_at", [(False, 8, 4), (True, 4, None)], ids=["plain-wire-kill-heal", "int8-wire"])
def test_two_replicas_commit_agree_bit_for_bit_and_heal_a_killed_one(quantize, total, kill_at):
    """Two replica groups as threads, a lighthouse, real Managers
    (``tests/_toys.py`` ``two_replica_walk``).  Each has a batch of its own,
    so equal leaves REQUIRE the averaged gradient: the float32 vectors of 4
    (``A_log``, ``D``, ``dt_bias``) cross ``ddp.allreduce_pytree``'s bucket
    plan beside the ONE tied leaf (over the int8 wire in the second case,
    where the state's signal is an empty list).  On the plain wire replica 1
    dies at step 4, comes back with other weights, and heals from the
    survivor."""

    def a_log(model, manager, trainer):
        event = [e for e in manager._flight.snapshot() if e["name"] == "MOE_ROUTE"][-1]
        assert isinstance(event["decay_min"], float) and event["decay_min"] < 0
        return float(trainer.holder["params"]["groups"][2]["mixer"]["A_log"][3, 0])

    shared, vectors = two_replica_walk(
        toy, _batch, total, kill_at=kill_at, quantized=range(total) if quantize else (), record=a_log
    )
    # a small float32 leaf itself: equal on both replicas and moving every step
    assert all(vectors[0][step] == vectors[1][step] for step in shared)
    assert len({vectors[0][step] for step in shared}) == len(shared)
