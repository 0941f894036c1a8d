"""``PreroutedMoE`` under ``HSDPTrainer`` and a Manager: stacked runs of layers
of two attention kinds, every layer an expert layer whose router read the
layer's input, no state the optimizer does not own (no selection bias), no
auxiliary loss.  A committed step moves every leaf, the routers among them,
and reports its routing; two replica groups as threads, each with a batch of
its own, stay bit-equal in every leaf through every commit they share, over
the plain wire through a kill and a live heal and over the int8 wire.  Toy
widths with the window shorter than the sequence, float32, the CPU's devices."""

import jax
import numpy as np
import pytest

from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.manager import Manager
from torchft_tpu.models.prerouted_moe import PreroutedMoE, prerouted_moe_debug
from torchft_tpu.parallel import hsdp

from tests._toys import replica_group, trainer as group_trainer, two_replica_walk
from tests.test_ling_hsdp import _batch
from tests.test_manager import MemoryTransport, StubClient, _quorum_result


def toy():
    return PreroutedMoE(prerouted_moe_debug())


def test_a_committed_step_moves_every_leaf_and_reports_its_routing():
    client = StubClient()
    client.quorum_results.extend(_quorum_result() for _ in range(2))
    manager = Manager(
        comm=DummyCommunicator(), load_state_dict=None, state_dict=None, min_replica_size=1,
        checkpoint_transport=MemoryTransport(), _manager_client=client, rank=0, world_size=1,
    )
    model, mesh, grad_step = replica_group(toy, 0)
    # the model reports, and its mask names no leaf: the optimizer owns them all
    assert hsdp._reports(model) and not any(hsdp._state_mask(model))
    trainer = group_trainer(toy, 0, manager, jax.random.PRNGKey(0), learning_rate=1e-3)
    batch = _batch(model, mesh, 1)
    before = jax.tree_util.tree_map(np.asarray, trainer.holder["params"])
    report, _ = grad_step(trainer.holder["params"], batch)
    assert report.shape == (1 + 4 * 4,)  # the objective and the summary of four routers, ONE array
    loss, committed = trainer.train_step(batch)
    assert committed and loss == float(report[0])
    after = jax.tree_util.tree_map(np.asarray, trainer.holder["params"])
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(before)[0], jax.tree_util.tree_leaves(after)):
        # adamw's first step moves a weight by the rate wherever its gradient is not 0: the
        # routers' too (their gradient comes through the weights of the chosen experts)
        assert np.abs(b - a).max() == pytest.approx(1e-3, rel=0.2), jax.tree_util.keystr(path)
    events = [e for e in manager._flight.snapshot() if e["name"] == "MOE_ROUTE"]
    assert len(events) == 1
    rows = np.asarray(report[1:]).reshape(4, 4)
    assert events[0]["rows_here"] == rows[:, 0].tolist() and events[0]["load_max"] == rows[:, 1].tolist()
    # 64 tokens x 3 choices, half the experts held: near half the pairs land here, a layer
    assert all(64 * 3 * 0.3 < r < 64 * 3 * 0.7 for r in events[0]["rows_here"])
    assert events[0]["buffer_rows"] == [64.0 * 3] * 4  # toy: the buffer is every pair, one pass


@pytest.mark.parametrize("quantize,total,kill_at", [(False, 8, 4), (True, 4, None)], ids=["plain-wire-kill-heal", "int8-wire"])
def test_two_replicas_commit_agree_bit_for_bit_and_heal_a_killed_one(quantize, total, kill_at):
    """Two replica groups as threads, a lighthouse, real Managers
    (``tests/_toys.py`` ``two_replica_walk``).  Each has a batch of its own,
    so equal leaves REQUIRE the averaged gradient: the float32 routers cross
    ``ddp.allreduce_pytree``'s bucket plan beside the stacked experts (over
    the int8 wire in the second case).  On the plain wire replica 1 dies at
    step 4, comes back with other weights, and heals from the survivor."""

    def a_router(model, manager, trainer):
        event = [e for e in manager._flight.snapshot() if e["name"] == "MOE_ROUTE"][-1]
        assert len(event["rows_here"]) == 4
        return float(trainer.holder["params"]["groups"][1]["ffn"]["router"][2, 0, 0])

    shared, routers = two_replica_walk(
        toy, _batch, total, kill_at=kill_at, quantized=range(total) if quantize else (), record=a_router
    )
    # a float32 router's entry itself: equal on both replicas and moving every step
    assert all(routers[0][step] == routers[1][step] for step in shared)
    assert len({routers[0][step] for step in shared}) == len(shared)
