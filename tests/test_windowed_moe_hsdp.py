"""``WindowedMoE`` under ``HSDPTrainer`` and a Manager: stacked runs of
layers of two attention kinds, a selection bias a router that the optimizer
does not own, no auxiliary loss.  A committed step moves every bias by the
load and reports its routing; two replica groups as threads, each with a
batch of its own, stay bit-equal in every leaf while the biases move.  Toy
widths with the window shorter than the sequence, float32, the CPU's
devices."""

import hashlib
import threading
from typing import Any, Dict, List

import jax
import numpy as np

from torchft_tpu import tier as tier_mod
from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.manager import Manager
from torchft_tpu.models.windowed_moe import WindowedMoE, windowed_moe_debug
from torchft_tpu.parallel import hsdp

from tests.test_ling_hsdp import RATE, _batch, _biases
from tests._toys import replica_group, trainer as group_trainer
from tests.test_manager import MemoryTransport, StubClient, _quorum_result


def toy():
    return WindowedMoE(windowed_moe_debug())

TOTAL = 5


def test_a_committed_step_moves_every_router_s_bias_and_reports_its_routing():
    client = StubClient()
    client.quorum_results.extend(_quorum_result() for _ in range(2))
    manager = Manager(
        comm=DummyCommunicator(), load_state_dict=None, state_dict=None, min_replica_size=1,
        checkpoint_transport=MemoryTransport(), _manager_client=client, rank=0, world_size=1,
    )
    model, mesh, grad_step = replica_group(toy, 0)
    assert hsdp._reports(model) and sum(jax.tree_util.tree_leaves(hsdp._state_mask(model))) == 4  # a stacked run a leaf
    trainer = group_trainer(toy, 0, manager, jax.random.PRNGKey(0), learning_rate=1e-3, weight_decay=0.5)
    batch = _batch(model, mesh, 1)
    before = _biases(model, trainer.holder["params"])
    report, grads = grad_step(trainer.holder["params"], batch)
    # a bias's slot of the gradient tree carries its routers' loads (a stacked run: 2, 1, 3 and 1 layers)
    runs = _biases(model, grads)
    assert [len(x) for x in runs] == [2, 1, 3, 1]
    loads = [load for run in runs for load in run]
    assert all(float(x.sum()) == 64 * 4 for x in loads)  # 64 tokens, 4 experts each
    assert report.shape == (1 + 4 * 7,)  # the objective and the summary of seven routers, ONE array
    loss, committed = trainer.train_step(batch)
    assert committed and loss == float(report[0])
    after = [b for run in _biases(model, trainer.holder["params"]) for b in run]
    for b0, b1, load in zip([b for run in before for b in run], after, loads, strict=True):
        np.testing.assert_array_equal(b1, b0 + np.float32(RATE) * np.sign(load.mean() - load))
    events = [e for e in manager._flight.snapshot() if e["name"] == "MOE_ROUTE"]
    assert len(events) == 1
    assert events[0]["rows_here"] == [float(load[4:8].sum()) for load in loads]
    assert events[0]["load_max"] == [float(load[4:8].max()) for load in loads]
    assert events[0]["buffer_rows"] == [64.0 * 4] * 7  # toy: the buffer is every pair, one pass


def test_two_replicas_stay_bit_equal_while_the_biases_move():
    devices = jax.devices()[:2]
    tier = tier_mod.default_tier()
    lighthouse = tier_mod.make_lighthouse(
        bind="127.0.0.1:0", min_replicas=2, join_timeout_ms=200, quorum_tick_ms=20,
        heartbeat_timeout_ms=2000, tier=tier,
    )
    managers: List[Manager] = []
    errors: List[BaseException] = []
    seen: List[Dict[int, Any]] = [{}, {}]  # replica -> fleet step -> (digest of every leaf, biases)

    def digest(params) -> str:
        h = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(params):
            h.update(np.asarray(leaf).tobytes())
        return h.hexdigest()

    def replica(idx: int) -> None:
        model, mesh, _ = replica_group(toy, idx)
        batch = _batch(model, mesh, 100 + idx)  # a batch each: equal biases REQUIRE the averaged load
        manager = Manager(
            comm=tier_mod.make_communicator(timeout_s=30.0, tier=tier),
            load_state_dict=None, state_dict=None, min_replica_size=2,
            timeout=30.0, quorum_timeout=30.0, connect_timeout=30.0,
            replica_id=f"swa_{idx}", lighthouse_addr=lighthouse.local_address(),
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
            seen[idx][manager.current_step()] = (digest(params), _biases(model, params))

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
    last = seen[0][TOTAL][1]
    assert len(last) == 4 and all(np.abs(b).max() > 0 for b in last)  # and so did every router's bias
    assert all(np.abs(b).max() <= RATE * TOTAL * (1 + 1e-5) for b in last)
