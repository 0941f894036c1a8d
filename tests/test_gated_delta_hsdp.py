"""``GatedDeltaMoE`` under ``HSDPTrainer`` and a Manager: two stacked runs of
layers, no state the optimizer does not own, a step's summary of five numbers a
layer.  A committed step moves every leaf, ``A_log`` and ``dt_bias`` among
them, and reports the most negative log decay; two replica groups as threads,
each with a batch of its own, agree bit for bit in every leaf through every
commit they share, through a kill and a live heal; a group of several chips is
refused the kernels.  Toy widths, float32, the CPU's devices."""

import jax
import numpy as np
import pytest

from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.manager import Manager
from torchft_tpu.models.gated_delta_moe import SUMMARY_FIELDS, GatedDeltaMoE, gated_delta_debug
from torchft_tpu.parallel import hsdp
from torchft_tpu.parallel.mesh import make_mesh

from tests._toys import replica_group, trainer as group_trainer, two_replica_walk
from tests.test_ling_hsdp import _batch
from tests.test_manager import MemoryTransport, StubClient, _quorum_result


def toy():
    return GatedDeltaMoE(gated_delta_debug())


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


def test_a_step_reports_its_routing_and_its_decay_in_one_array(committed_step):
    model, _, report, (loss, committed), _, events = committed_step
    assert hsdp._reports(model) and hsdp._state_mask(model) is None  # no state of its own
    layers = model.config.n_layers
    assert report.shape == (1 + layers * len(SUMMARY_FIELDS),)  # the objective and five numbers a layer
    assert committed and loss == float(report[0])
    (event,) = events
    assert [len(event[name]) for name in SUMMARY_FIELDS] == [layers] * len(SUMMARY_FIELDS)
    assert event["buffer_rows"] == [64.0 * 4] * layers  # toy: the buffer is every pair, one pass
    # three DeltaNet layers, then a full one, which has no decay; the seeded one is far under -5.5
    assert all(d < -5.5 for d in event["decay_min"][:3]) and event["decay_min"][3] == 0.0


@pytest.mark.parametrize(
    "leaf",
    ["embed", "lm_head", "final_norm", "attn_norm", "w_qkvz", "w_ba", "conv", "a_log", "dt_bias", "o_norm", "wo",
     "wq", "q_norm", "k_norm", "router", "w_down", "shared_up", "shared_sigmoid"],
)
def test_a_committed_step_moves_every_leaf(leaf, committed_step):
    _, before, _, _, after, _ = committed_step
    found = {
        getattr(path[-1], "key", None): (a, b)
        for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(before)[0], jax.tree_util.tree_leaves(after))
    }
    a, b = found[leaf]
    moved = np.abs(b - a)
    # adamw's first step moves a weight by the rate wherever its gradient is not 0 (the held experts'
    # rows that no token reached stay; every other leaf moves nearly everywhere)
    assert moved.max() == pytest.approx(1e-3, rel=0.05), leaf
    assert (moved > 0).mean() > (0.3 if leaf == "w_down" else 0.9), leaf


@pytest.mark.parametrize("chips,refused", [(1, False), (2, True)])
def test_a_group_of_several_chips_is_refused_the_kernels(chips, refused, monkeypatch):
    """The kernels are one chip's: on a TPU a group of one takes them, a
    larger group the plain path, by name."""
    monkeypatch.setenv("TORCHFT_FLASH_PLATFORM", "tpu")
    monkeypatch.delenv("TORCHFT_FLASH", raising=False)
    model = GatedDeltaMoE(gated_delta_debug(), mesh=make_mesh(fsdp=chips, devices=jax.devices()[:chips]))
    refusal = model._kernel_refusal(128)
    assert (refusal is not None) == refused
    assert not refused or "a group of 2 chips" in refusal
    # a sequence that holds no whole chunk of 64 is refused on any group
    assert "does not divide" in toy()._kernel_refusal(96)


def test_two_replicas_commit_agree_bit_for_bit_and_heal_a_killed_one():
    """Two replica groups as threads, a lighthouse, real Managers
    (``tests/_toys.py`` ``two_replica_walk``).  Each has a batch of its own,
    so equal leaves REQUIRE the averaged gradient.  Replica 1 dies at step 4,
    comes back with other weights, and heals from the survivor."""
    two_replica_walk(toy, _batch, total=8, kill_at=4)
