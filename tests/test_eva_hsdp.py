"""``Eva`` under ``HSDPTrainer`` and a Manager: one stacked run of layers, no
state the optimizer does not own, a step's summary of ONE number.  A committed
step moves every leaf, the pooling's two learned vectors among them, and
reports ``multibyte_nll``; two replica groups as threads, each with a batch of
its own, agree bit for bit in every leaf through every commit; a group of
several chips is refused the kernels.  Toy widths with two windows in the
sequence, float32, the CPU's devices."""

import hashlib
import math
import threading
from typing import Any, Dict, List

import jax
import numpy as np
import pytest

from torchft_tpu import tier as tier_mod
from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.manager import Manager
from torchft_tpu.models.eva import Eva, eva_debug
from torchft_tpu.parallel import hsdp
from torchft_tpu.parallel.mesh import make_mesh

from tests.test_ling_hsdp import _batch
from tests._toys import replica_group, trainer as group_trainer
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
    devices = jax.devices()[:2]
    tier = tier_mod.default_tier()
    lighthouse = tier_mod.make_lighthouse(
        bind="127.0.0.1:0", min_replicas=2, join_timeout_ms=200, quorum_tick_ms=20,
        heartbeat_timeout_ms=2000, tier=tier,
    )
    managers: List[Manager] = []
    errors: List[BaseException] = []
    seen: List[Dict[int, Any]] = [{}, {}]  # replica -> fleet step -> (digest of every leaf, phi)

    def digest(params) -> str:
        h = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(params):
            h.update(np.asarray(leaf).tobytes())
        return h.hexdigest()

    def replica(idx: int) -> None:
        model, mesh, _ = replica_group(toy, idx)
        batch = _batch(model, mesh, 100 + idx)  # a batch each: equal leaves REQUIRE the averaged gradient
        manager = Manager(
            comm=tier_mod.make_communicator(timeout_s=30.0, tier=tier),
            load_state_dict=None, state_dict=None, min_replica_size=2,
            timeout=30.0, quorum_timeout=30.0, connect_timeout=30.0,
            replica_id=f"eva_{idx}", lighthouse_addr=lighthouse.local_address(),
            server_cls=tier_mod.manager_server_cls(tier),
        )
        managers.append(manager)
        trainer = group_trainer(toy, idx, manager, jax.random.PRNGKey(1), learning_rate=1e-3)
        while manager.current_step() < TOTAL:
            trainer.quantize_outer = manager.current_step() == 2  # one step on the int8 wire
            loss, committed = trainer.train_step(batch)
            assert np.isfinite(loss) and committed, manager.errored()
            assert manager.num_participants() == 2
            params = trainer.holder["params"]
            seen[idx][manager.current_step()] = (digest(params), np.asarray(params["layers"]["phi"]))

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
    assert sorted(seen[0]) == sorted(seen[1]) == list(range(1, TOTAL + 1))
    for step in seen[0]:
        assert seen[0][step][0] == seen[1][step][0], f"step {step}"
    digests = [seen[0][step][0] for step in sorted(seen[0])]
    assert len(set(digests)) == TOTAL  # the parameters moved every step
    # ... the pooling's learned vector among them, which takes gradient from every position
    assert np.abs(seen[0][TOTAL][1] - seen[0][1][1]).max() > 0
