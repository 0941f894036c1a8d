"""``Looped`` under ``HSDPTrainer`` and a Manager: one stack of layers run
four times a step, no state the optimizer does not own, a step's summary of
``2 T + 1`` numbers.  A committed step moves every leaf, the gate's vector and
its ONE-element bias among them, and its flight event carries ``pass_nll``,
``exit_p`` and ``exit_entropy``; two replica groups as threads, each with a
batch of its own, agree bit for bit in every leaf through every commit they
share, over the plain wire through a kill and a live heal and over the int8
wire; the 1-element leaf goes through ``ddp.allreduce_pytree``'s bucket plan
and the heal as any other.  Toy widths, float32, the CPU's devices."""

import hashlib
import threading
from typing import Dict, List

import jax
import numpy as np
import pytest

from torchft_tpu import tier as tier_mod
from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.manager import Manager
from torchft_tpu.models.looped import Looped, looped_debug
from torchft_tpu.parallel import hsdp

from tests._toys import replica_group, trainer as group_trainer
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


class _Killed(Exception):
    pass


@pytest.mark.parametrize("quantize,total,kill_at", [(False, 8, 4), (True, 4, None)], ids=["plain-wire-kill-heal", "int8-wire"])
def test_two_replicas_commit_agree_bit_for_bit_and_heal_a_killed_one(quantize, total, kill_at):
    """Two replica groups as threads, a lighthouse, real Managers.  Each has a
    batch of its own, so equal leaves REQUIRE the averaged gradient: the
    gate's vector and its 1-element bias cross ``ddp.allreduce_pytree``'s
    bucket plan beside leaves ten thousand times their size (over the int8
    wire in the second case, ``should_quantize=True``).  On the plain wire
    replica 1 dies at step 4, comes back with other weights, and heals from
    the survivor, the 1-element leaf with the rest."""
    devices = jax.devices()[:2]
    tier = tier_mod.default_tier()
    lighthouse = tier_mod.make_lighthouse(
        bind="127.0.0.1:0", min_replicas=2 if kill_at is None else 1, join_timeout_ms=200, quorum_tick_ms=20,
        heartbeat_timeout_ms=2000, tier=tier,
    )
    managers: List[Manager] = []
    errors: List[BaseException] = []
    seen: List[Dict[int, str]] = [{}, {}]  # replica -> fleet step -> digest of every leaf
    biases: List[Dict[int, float]] = [{}, {}]
    rejoined = threading.Event()

    def digest(params) -> str:
        h = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(params):
            h.update(np.asarray(leaf).tobytes())
        return h.hexdigest()

    def replica(idx: int) -> None:
        model, mesh, _ = replica_group(toy, idx)
        batch = _batch(model, mesh, 100 + idx)
        life = 0
        while True:
            manager = Manager(
                comm=tier_mod.make_communicator(timeout_s=30.0, tier=tier),
                load_state_dict=None, state_dict=None, min_replica_size=1,
                timeout=30.0, quorum_timeout=30.0, connect_timeout=30.0,
                replica_id=f"loop_{idx}", lighthouse_addr=lighthouse.local_address(),
                server_cls=tier_mod.manager_server_cls(tier),
            )
            managers.append(manager)
            # the new life finds the step's programs compiled (``tests/_toys.py``)
            trainer = group_trainer(toy, idx, manager, jax.random.PRNGKey(10 * life + 1), learning_rate=1e-3)
            trainer.quantize_outer = quantize
            if life:
                rejoined.set()
            try:
                stalled = 0
                while (step := manager.current_step()) < total:
                    if kill_at is not None and life == 0 and idx == 1 and step >= kill_at:
                        raise _Killed()
                    if kill_at is not None and idx == 0 and step == kill_at + 1:
                        # 120 s: beside five busy workers the dead life's shutdown and the new
                        # one's Manager have taken over the 60 s its siblings allow (D13 (c))
                        assert rejoined.wait(timeout=120.0), "the killed replica never came back"
                    loss, committed = trainer.train_step(batch)
                    assert np.isfinite(loss)
                    stalled = 0 if committed else stalled + 1
                    assert committed or (kill_at is not None and step >= kill_at and stalled < 3), manager.errored()
                    if committed and manager.num_participants() == 2:
                        seen[idx][manager.current_step()] = digest(trainer.holder["params"])
                        biases[idx][manager.current_step()] = float(trainer.holder["params"]["gate"]["b"][0])
                        event = [e for e in manager._flight.snapshot() if e["name"] == "MOE_ROUTE"][-1]
                        assert sorted(k for k in event if k in ("pass_nll", "exit_p", "exit_entropy")) == ["exit_entropy", "exit_p", "pass_nll"]
                return
            except _Killed:
                life += 1
                manager.shutdown()
                managers.remove(manager)

    def guarded(idx: int) -> None:
        try:
            with jax.default_device(devices[idx]):
                replica(idx)
        except BaseException as e:  # noqa: BLE001 — raised again below
            errors.append(e)

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
        assert len(shared) >= total - 1, shared
    else:
        # steps with both in the quorum: before the kill, and after the heal
        assert any(s <= kill_at for s in shared) and any(s > kill_at + 1 for s in shared), shared
    for step in shared:
        assert seen[0][step] == seen[1][step], f"step {step}"
    assert len({seen[0][step] for step in shared}) == len(shared)  # the parameters moved every step
    # the 1-element leaf itself: equal on both replicas, moved from its start of 0 by the averaged gradient
    assert all(biases[0][step] == biases[1][step] != 0.0 for step in shared)
    assert len({biases[0][step] for step in shared}) == len(shared)
