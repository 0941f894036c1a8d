"""``SsmHybridMoE`` under ``HSDPTrainer`` and a Manager: layers that are
dicts of a list (nothing stacked), a selection bias a router that the
optimizer does not own.  A committed step moves every bias by the load and
reports its routing; two replica groups as threads, each with a batch of its
own, stay bit-equal in every leaf while the biases move.  Toy widths,
float32, the CPU's devices."""


import jax
import numpy as np

from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.manager import Manager
from torchft_tpu.models.ssm_hybrid_moe import SsmHybridMoE, ssm_hybrid_debug
from torchft_tpu.parallel import hsdp

from tests.test_ling_hsdp import RATE, _batch, _biases
from tests._toys import replica_group, trainer as group_trainer, two_replica_walk
from tests.test_manager import MemoryTransport, StubClient, _quorum_result


def toy():
    return SsmHybridMoE(ssm_hybrid_debug())

TOTAL = 5


def test_a_committed_step_moves_every_router_s_bias_and_reports_its_routing():
    client = StubClient()
    client.quorum_results.extend(_quorum_result() for _ in range(2))
    manager = Manager(
        comm=DummyCommunicator(), load_state_dict=None, state_dict=None, min_replica_size=1,
        checkpoint_transport=MemoryTransport(), _manager_client=client, rank=0, world_size=1,
    )
    model, mesh, grad_step = replica_group(toy, 0)
    assert hsdp._reports(model) and sum(jax.tree_util.tree_leaves(hsdp._state_mask(model))) == 4
    trainer = group_trainer(toy, 0, manager, jax.random.PRNGKey(0), learning_rate=1e-3, weight_decay=0.5)
    batch = _batch(model, mesh, 1)
    before = _biases(model, trainer.holder["params"])
    report, grads = grad_step(trainer.holder["params"], batch)
    # a bias's slot of the gradient tree carries its router's load (a stacked run of one layer)
    loads = [x[0] for x in _biases(model, grads)]
    assert len(loads) == 4 and all(float(x.sum()) == 64 * 4 for x in loads)  # 64 tokens, 4 experts each
    assert report.shape == (1 + 4 * 4,)  # the objective and the summary, ONE array
    loss, committed = trainer.train_step(batch)
    assert committed and loss == float(report[0])
    for b0, b1, load in zip(before, _biases(model, trainer.holder["params"]), loads):
        np.testing.assert_array_equal(b1[0], b0[0] + np.float32(RATE) * np.sign(load.mean() - load))
    events = [e for e in manager._flight.snapshot() if e["name"] == "MOE_ROUTE"]
    assert len(events) == 1
    assert events[0]["rows_here"] == [float(load[4:8].sum()) for load in loads]
    assert events[0]["load_max"] == [float(load[4:8].max()) for load in loads]
    assert events[0]["buffer_rows"] == [64.0 * 4] * 4  # toy: the buffer is every pair, one pass


def test_two_replicas_stay_bit_equal_while_the_biases_move():
    _, seen = two_replica_walk(
        toy, _batch, TOTAL, quantized=(2,),  # a batch each: equal biases REQUIRE the averaged load; one step on the int8 wire
        record=lambda model, manager, trainer: _biases(model, trainer.holder["params"]),
    )
    last = seen[0][TOTAL]
    assert len(last) == 4 and all(np.abs(b).max() > 0 for b in last)  # and so did every router's bias
    assert all(np.abs(b).max() <= RATE * TOTAL * (1 + 1e-5) for b in last)
