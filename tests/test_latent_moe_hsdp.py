"""``LatentMoE`` under ``HSDPTrainer`` and a Manager: stacked runs of
latent-attention layers, the multi-token-prediction module IN what a step
differentiates, a selection bias a router that the optimizer does not own,
the module's router among them.  A committed step moves every bias by the
load and reports its routing and the module's loss; two replica groups as
threads, each with a batch of its own, stay bit-equal in every leaf while the
biases move.  Toy widths, float32, the CPU's devices."""


import jax
import numpy as np
import pytest

from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.manager import Manager
from torchft_tpu.models.latent_moe import LatentMoE, latent_moe_debug
from torchft_tpu.parallel import hsdp

from tests.test_ling_hsdp import RATE, _batch, _biases
from tests._toys import replica_group, trainer as group_trainer, two_replica_walk
from tests.test_manager import MemoryTransport, StubClient, _quorum_result


def toy():
    return LatentMoE(latent_moe_debug())

TOTAL = 5


def test_a_committed_step_moves_every_router_s_bias_and_reports_the_modules_loss():
    client = StubClient()
    client.quorum_results.extend(_quorum_result() for _ in range(2))
    manager = Manager(
        comm=DummyCommunicator(), load_state_dict=None, state_dict=None, min_replica_size=1,
        checkpoint_transport=MemoryTransport(), _manager_client=client, rank=0, world_size=1,
    )
    model, mesh, grad_step = replica_group(toy, 0)
    # the run of three expert layers is one leaf, the module's router another
    assert hsdp._reports(model) and sum(jax.tree_util.tree_leaves(hsdp._state_mask(model))) == 2
    trainer = group_trainer(toy, 0, manager, jax.random.PRNGKey(0), learning_rate=1e-3, weight_decay=0.5)
    batch = _batch(model, mesh, 1)
    before = _biases(model, trainer.holder["params"])
    report, grads = grad_step(trainer.holder["params"], batch)
    # a bias's slot of the gradient tree carries its routers' loads: the trunk's run, then the module's
    runs = _biases(model, grads)
    assert [x.shape for x in runs] == [(3, 16), (16,)]
    loads = [load for run in runs for load in run.reshape(-1, 16)]
    assert all(float(x.sum()) == 64 * 4 for x in loads)  # 64 tokens, 4 experts each, in the module's layer too
    assert report.shape == (1 + 4 * 4 + 1,)  # the objective, the summary of four routers, the module's loss: ONE array
    loss, committed = trainer.train_step(batch)
    assert committed and loss == float(report[0])
    after = [b for run in _biases(model, trainer.holder["params"]) for b in run.reshape(-1, 16)]
    for b0, b1, load in zip([b for run in before for b in run.reshape(-1, 16)], after, loads, strict=True):
        np.testing.assert_array_equal(b1, b0 + np.float32(RATE) * np.sign(load.mean() - load))
    events = [e for e in manager._flight.snapshot() if e["name"] == "MOE_ROUTE"]
    assert len(events) == 1
    assert events[0]["rows_here"] == [float(load[4:8].sum()) for load in loads]
    assert events[0]["load_max"] == [float(load[4:8].max()) for load in loads]
    # the module's mean loss of the step, beside the routing fields: a guess over 512 ids at seeded weights
    assert events[0]["mtp_nll"] == float(report[-1]) and events[0]["mtp_nll"] == pytest.approx(np.log(512), abs=1.0)
    # what the step differentiates holds the module: the objective is over the cross-entropy by its weight
    cross_entropy = float(jax.jit(model.loss)(trainer.holder["params"], batch))
    assert loss - cross_entropy > 0.5 * model.config.mtp_loss_weight * events[0]["mtp_nll"]


def test_two_replicas_stay_bit_equal_while_the_biases_move_the_modules_too():
    _, seen = two_replica_walk(
        toy, _batch, TOTAL, quantized=(2,),  # a batch each: equal biases REQUIRE the averaged load; one step on the int8 wire
        record=lambda model, manager, trainer: _biases(model, trainer.holder["params"]),
    )
    last = seen[0][TOTAL]
    # the trunk's run of routers and the module's router: both moved, by whole steps of the rate
    assert [b.shape for b in last] == [(3, 16), (16,)] and all(np.abs(b).max() > 0 for b in last)
    assert all(np.abs(b).max() <= RATE * TOTAL * (1 + 1e-5) for b in last)
