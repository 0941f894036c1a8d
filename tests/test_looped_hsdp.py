"""``Looped`` under ``HSDPTrainer`` and a Manager: one stack of layers run
four times a step, no state the optimizer does not own, a step's summary of
``2 T + 1`` numbers.  A committed step moves every leaf, the gate's vector and
its ONE-element bias among them, and its flight event carries ``pass_nll``,
``exit_p`` and ``exit_entropy``; two replica groups as threads, each with a
batch of its own, agree bit for bit in every leaf through every commit they
share, over the plain wire through a kill and a live heal and over the int8
wire; the 1-element leaf goes through ``ddp.allreduce_pytree``'s bucket plan
and the heal as any other.  Toy widths, float32, the CPU's devices."""

import jax
import numpy as np
import pytest

from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.manager import Manager
from torchft_tpu.models.looped import Looped, looped_debug
from torchft_tpu.parallel import hsdp

from tests._toys import replica_group, trainer as group_trainer, two_replica_walk
from tests.test_ling_hsdp import _batch
from tests.test_manager import MemoryTransport, StubClient, _quorum_result


def toy():
    return Looped(looped_debug())


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


def test_a_step_reports_every_passs_loss_and_the_exit_distribution_in_one_array(committed_step):
    model, _, report, (loss, committed), _, events = committed_step
    assert hsdp._reports(model) and hsdp._state_mask(model) is None  # no state of its own
    T = model.config.n_passes
    assert report.shape == (1 + 2 * T + 1,)  # the objective, T losses, T probabilities, the entropy
    assert committed and loss == float(report[0])
    (event,) = events
    assert len(event["pass_nll"]) == len(event["exit_p"]) == T
    # seeded weights: a pass's logits have unit variance (a normed stream through a head of normal /
    # sqrt(dim)), so its loss starts near ln(vocabulary) + 1/2; a distribution, an entropy under ln T
    assert all(abs(nll - np.log(model.config.vocab_size) - 0.5) < 0.35 for nll in event["pass_nll"])
    assert sum(event["exit_p"]) == pytest.approx(1.0, abs=1e-5) and all(0.02 < p < 0.9 for p in event["exit_p"])
    assert 0.5 < event["exit_entropy"] <= np.log(T)
    # what the step differentiates is the expected loss less the entropy term, not the last pass's loss
    expected = sum(event["pass_nll"]) / T  # a bound's middle: the objective lies among the passes' losses
    assert abs(loss - expected) < 0.5 and loss != pytest.approx(event["pass_nll"][-1], abs=1e-4)


@pytest.mark.parametrize(
    "leaf", ["embed", "lm_head", "final_norm", "mixer_in", "mixer_out", "ffn_in", "ffn_out", "wq", "wk", "wv", "wo",
             "w_gate", "w_up", "w_down", "w", "b"],
)
def test_a_committed_step_moves_every_leaf(leaf, committed_step):
    _, before, _, _, after, _ = committed_step
    found = {
        getattr(path[-1], "key", None): (a, b)
        for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(before)[0], jax.tree_util.tree_leaves(after))
    }
    a, b = found[leaf]
    moved = np.abs(b - a)
    # adamw's first step moves a weight by the rate wherever its gradient is not 0: the embedding's
    # rows of the tokens the batch holds, every other leaf everywhere, the gate's ONE-element bias too
    assert moved.max() == pytest.approx(1e-3, rel=0.05), leaf
    assert (moved > 0).mean() > (0.3 if leaf == "embed" else 0.9), leaf
    assert leaf != "b" or moved.shape == (1,)


@pytest.mark.parametrize("quantize,total,kill_at", [(False, 8, 4), (True, 4, None)], ids=["plain-wire-kill-heal", "int8-wire"])
def test_two_replicas_commit_agree_bit_for_bit_and_heal_a_killed_one(quantize, total, kill_at):
    """Two replica groups as threads, a lighthouse, real Managers
    (``tests/_toys.py`` ``two_replica_walk``).  Each has a batch of its own,
    so equal leaves REQUIRE the averaged gradient: the gate's vector and its
    1-element bias cross ``ddp.allreduce_pytree``'s bucket plan beside leaves
    ten thousand times their size (over the int8 wire in the second case,
    ``should_quantize=True``).  On the plain wire replica 1 dies at step 4,
    comes back with other weights, and heals from the survivor, the 1-element
    leaf with the rest."""

    def gate_bias(model, manager, trainer):
        event = [e for e in manager._flight.snapshot() if e["name"] == "MOE_ROUTE"][-1]
        assert sorted(k for k in event if k in ("pass_nll", "exit_p", "exit_entropy")) == ["exit_entropy", "exit_p", "pass_nll"]
        return float(trainer.holder["params"]["gate"]["b"][0])

    shared, biases = two_replica_walk(
        toy, _batch, total, kill_at=kill_at, quantized=range(total) if quantize else (), record=gate_bias
    )
    # the 1-element leaf itself: equal on both replicas, moved from its start of 0 by the averaged gradient
    assert all(biases[0][step] == biases[1][step] != 0.0 for step in shared)
    assert len({biases[0][step] for step in shared}) == len(shared)
