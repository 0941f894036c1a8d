"""``GatedDeltaMoE`` under ``HSDPTrainer`` and a Manager: two stacked runs of
layers, no state the optimizer does not own, a step's summary of five numbers a
layer.  A committed step moves every leaf, ``A_log`` and ``dt_bias`` among
them, and reports the most negative log decay; two replica groups as threads,
each with a batch of its own, agree bit for bit in every leaf through every
commit they share, through a kill and a live heal; a group of several chips is
refused the kernels.  Toy widths, float32, the CPU's devices."""

import hashlib
import threading
from typing import Dict, List

import jax
import numpy as np
import pytest

from torchft_tpu import tier as tier_mod
from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.manager import Manager
from torchft_tpu.models.gated_delta_moe import SUMMARY_FIELDS, GatedDeltaMoE, gated_delta_debug
from torchft_tpu.parallel import hsdp
from torchft_tpu.parallel.mesh import make_mesh

from tests._toys import replica_group, trainer as group_trainer
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


TOTAL, KILL_AT = 8, 4


class _Killed(Exception):
    pass


def test_two_replicas_commit_agree_bit_for_bit_and_heal_a_killed_one():
    """Two replica groups as threads, a lighthouse, real Managers.  Each has a
    batch of its own, so equal leaves REQUIRE the averaged gradient.  Replica 1
    dies at step 4, comes back with other weights, and heals from the
    survivor."""
    devices = jax.devices()[:2]
    tier = tier_mod.default_tier()
    lighthouse = tier_mod.make_lighthouse(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200, quorum_tick_ms=20,
        heartbeat_timeout_ms=2000, tier=tier,
    )
    managers: List[Manager] = []
    errors: List[BaseException] = []
    seen: List[Dict[int, str]] = [{}, {}]  # replica -> fleet step -> digest of every leaf
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
                replica_id=f"gdn_{idx}", lighthouse_addr=lighthouse.local_address(),
                server_cls=tier_mod.manager_server_cls(tier),
            )
            managers.append(manager)
            # the new life finds the step's programs compiled (``tests/_toys.py``)
            trainer = group_trainer(toy, idx, manager, jax.random.PRNGKey(10 * life + 1), learning_rate=1e-3)
            if life:
                rejoined.set()
            try:
                stalled = 0
                while (step := manager.current_step()) < TOTAL:
                    if life == 0 and idx == 1 and step >= KILL_AT:
                        raise _Killed()
                    if idx == 0 and step == KILL_AT + 1:
                        # 120 s: beside five busy workers the dead life's shutdown and the new
                        # one's Manager have taken over the 60 s its siblings allow (D13 (c))
                        assert rejoined.wait(timeout=120.0), "the killed replica never came back"
                    loss, committed = trainer.train_step(batch)
                    assert np.isfinite(loss)
                    stalled = 0 if committed else stalled + 1
                    assert committed or (step >= KILL_AT and stalled < 3), manager.errored()
                    if committed and manager.num_participants() == 2:
                        seen[idx][manager.current_step()] = digest(trainer.holder["params"])
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
    # steps with both in the quorum: before the kill, and after the heal
    assert any(s <= KILL_AT for s in shared) and any(s > KILL_AT + 1 for s in shared), shared
    for step in shared:
        assert seen[0][step] == seen[1][step], f"step {step}"
    assert len({seen[0][step] for step in shared}) == len(shared)  # the parameters moved every step
