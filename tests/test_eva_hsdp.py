"""``Eva`` under ``HSDPTrainer`` and a Manager: one stacked run of layers, no
state the optimizer does not own, a step's summary of ONE number.  A committed
step moves every leaf, the pooling's two learned vectors among them, and
reports ``multibyte_nll``; two replica groups as threads, each with a batch of
its own, agree bit for bit in every leaf through every commit; a group of
several chips is refused the kernels.  Toy widths with two windows in the
sequence, float32, the CPU's devices."""

import math

import jax
import numpy as np
import pytest

from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.manager import Manager
from torchft_tpu.models.eva import Eva, eva_debug
from torchft_tpu.parallel import hsdp
from torchft_tpu.parallel.mesh import make_mesh

from tests.test_ling_hsdp import _batch
from tests._toys import replica_group, trainer as group_trainer, two_replica_walk
from tests.test_manager import MemoryTransport, StubClient, _quorum_result


def toy():
    return Eva(eva_debug())

TOTAL = 4


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


def test_a_step_reports_the_further_slices_loss_in_one_array(committed_step):
    model, _, report, (loss, committed), _, events = committed_step
    assert hsdp._reports(model) and hsdp._state_mask(model) is None  # no state of its own
    assert report.shape == (2,)  # the objective and ONE number, in one array
    assert committed and loss == float(report[0])
    assert len(events) == 1 and events[0]["multibyte_nll"] == float(report[1])
    # seeded weights: every slice near ln(vocabulary), the toy's head a little over it
    assert abs(events[0]["multibyte_nll"] - math.log(model.config.vocab_size)) < 1.5


@pytest.mark.parametrize("leaf", ["embed", "lm_head", "final_norm", "wq", "wo", "w_down", "attn_norm", "phi", "mu"])
def test_a_committed_step_moves_every_leaf(leaf, committed_step):
    _, before, _, _, after, _ = committed_step
    pick = lambda tree: tree[leaf] if leaf in tree else tree["layers"][leaf]  # noqa: E731
    moved = np.abs(pick(after) - pick(before))
    # adamw's first step moves a weight by the rate wherever its gradient is not 0
    assert moved.max() == pytest.approx(1e-3, rel=0.05) and (moved > 0).mean() > 0.9, leaf


@pytest.mark.parametrize("chips,refused", [(1, False), (2, True)])
def test_a_group_of_several_chips_is_refused_the_kernels(chips, refused, monkeypatch):
    """The kernels are one chip's: on a TPU a group of one takes them, a
    larger group the plain path, by name."""
    monkeypatch.setenv("TORCHFT_FLASH_PLATFORM", "tpu")
    monkeypatch.delenv("TORCHFT_FLASH", raising=False)
    model = Eva(eva_debug(), mesh=make_mesh(fsdp=chips, devices=jax.devices()[:chips]))
    refusal = model._kernel_refusal(64)
    assert (refusal is not None) == refused
    assert not refused or "a group of 2 chips" in refusal
    # a sequence that holds no whole window of whole blocks is refused on any group
    assert "does not divide" in Eva(eva_debug())._kernel_refusal(72)


def test_two_replicas_agree_bit_for_bit_through_every_commit():
    _, phi = two_replica_walk(
        toy, _batch, TOTAL, quantized=(2,),  # a batch each: equal leaves REQUIRE the averaged gradient; one step on the int8 wire
        record=lambda model, manager, trainer: np.asarray(trainer.holder["params"]["layers"]["phi"]),
    )
    # the parameters moved every step, the pooling's learned vector among them, which takes gradient from every position
    assert np.abs(phi[0][TOTAL] - phi[0][1]).max() > 0
