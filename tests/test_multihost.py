"""Multi-host replica groups, end to end: 2 groups x 2 "hosts" each.

Each replica group is a real 2-process ``jax.distributed`` job over a
4-device CPU mesh, so arrays are genuinely non-fully-addressable — the
code path a v5p-64 replica group exercises.
Covers: shard-local gradient rings per host, whole-group SIGKILL-class
death, respawn, rank-to-rank heal of ``ShardedHostArray`` bundles, and
rank-wise state equality across groups at the end.
"""

import os
import pickle
import socket
import subprocess
import sys
import time
from pathlib import Path
from typing import Dict, List

import numpy as np
import pytest

from torchft_tpu.lighthouse import LighthouseServer
from torchft_tpu.store import StoreServer

HERE = Path(__file__).parent


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_group(
    group: int,
    lighthouse_addr: str,
    store_port: int,
    results: Dict[int, Path],
    num_steps: int,
    die_at: int = -1,
    wait_flag: str = "",
    wait_at: int = 4,
    wait_flag2: str = "",
    wait_at2: int = -1,
) -> List[subprocess.Popen]:
    coord = _free_port()
    procs = []
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    for rank in range(2):
        procs.append(
            subprocess.Popen(
                [
                    sys.executable,
                    str(HERE / "multihost_worker.py"),
                    "--group", str(group),
                    "--rank", str(rank),
                    "--coord-port", str(coord),
                    "--lighthouse", lighthouse_addr,
                    "--store-port", str(store_port),
                    "--num-steps", str(num_steps),
                    "--die-at", str(die_at),
                    "--result-file", str(results[rank]),
                    "--wait-flag", wait_flag,
                    "--wait-at", str(wait_at),
                    "--wait-flag2", wait_flag2,
                    "--wait-at2", str(wait_at2),
                ],
                env=env,
            )
        )
    return procs


def _await_groups_registered(
    lighthouse, names, procs, deadline_s: float = 120.0
):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        dead = [(p.args, p.poll()) for p in procs if p.poll() is not None]
        if dead:
            # a crashed worker can never register: fail NOW with its exit
            # code instead of burning the deadline and blaming registration
            pytest.fail(f"worker(s) died during startup: {dead}")
        beats = lighthouse._status().get("heartbeats", {})
        if set(names) <= {rid.split(":")[0] for rid in beats}:
            return
        time.sleep(0.2)
    # never release the start gate on a partial fleet: solo steps diverge
    # params with no heal to reconcile — fail HERE with the real cause
    pytest.fail(
        f"groups {names} never all registered within {deadline_s}s "
        f"(heartbeats: {sorted(lighthouse._status().get('heartbeats', {}))})"
    )


def _make_lighthouse() -> LighthouseServer:
    return LighthouseServer(
        bind="127.0.0.1:0",
        min_replicas=1,
        join_timeout_ms=200,
        quorum_tick_ms=20,
        heartbeat_timeout_ms=1500,
    )


def _assert_rankwise_equal(views, exact: bool) -> None:
    """Host r of group 0 vs host r of group 1 hold identical shards for
    every leaf (``exact`` = bitwise, the quantized-wire invariant)."""
    for r in range(2):
        a, b = views[0][r]["params"], views[1][r]["params"]
        assert a.keys() == b.keys()
        for leaf_name in a:
            assert a[leaf_name].keys() == b[leaf_name].keys(), leaf_name
            for key in a[leaf_name]:
                if exact:
                    np.testing.assert_array_equal(
                        a[leaf_name][key], b[leaf_name][key],
                        err_msg=f"{leaf_name}[{key}] rank {r}",
                    )
                else:
                    np.testing.assert_allclose(
                        a[leaf_name][key], b[leaf_name][key],
                        rtol=1e-5, atol=1e-6,
                        err_msg=f"{leaf_name}[{key}] rank {r}",
                    )


def _teardown(all_procs, stores, lighthouse) -> None:
    for p in all_procs:
        if p.poll() is None:
            p.kill()
    for s in stores:
        try:
            s.shutdown()
        except Exception:  # noqa: BLE001 — teardown must reach the lighthouse
            pass
    lighthouse.shutdown()


def test_multihost_quantized_wire(tmp_path, monkeypatch) -> None:
    """The int8 ring over multi-host sharded leaves: a healthy 2-group
    fleet syncs quantized shard-local contributions and ends rank-wise
    bitwise-equal (every group applies the same requantized stream).
    Kill/heal choreography is covered by the float-wire test below — this
    one stays lightweight on purpose (the spawned-fleet timing budget is
    load-sensitive, and the wire format is the coverage being added)."""
    monkeypatch.setenv("MH_QUANTIZE", "1")
    lighthouse = _make_lighthouse()
    stores: List[StoreServer] = []
    all_procs: List[subprocess.Popen] = []
    try:
        num_steps = 6
        results = {
            g: {r: tmp_path / f"g{g}r{r}.pkl" for r in range(2)} for g in range(2)
        }
        # both groups park BEFORE their first step until both are
        # registered: solo steps on per-group data would diverge params
        # with no heal to reconcile them (the per-step FT contract only
        # guarantees equality from the first JOINT quorum onward)
        flag = tmp_path / "both_registered"
        for g in range(2):
            store = StoreServer("127.0.0.1:0")
            stores.append(store)
            all_procs += _spawn_group(
                g, lighthouse.local_address(), store.port, results[g],
                num_steps, wait_flag=str(flag), wait_at=0,
            )
        _await_groups_registered(
            lighthouse, ["mh_group_0", "mh_group_1"], all_procs
        )
        flag.touch()
        deadline = time.monotonic() + 300
        for p in all_procs:
            rc = p.wait(timeout=max(1.0, deadline - time.monotonic()))
            assert rc == 0, f"worker exited rc={rc}"
        views = {
            g: {r: pickle.loads(results[g][r].read_bytes()) for r in range(2)}
            for g in range(2)
        }
        # bitwise: every group applies the identical requantized stream
        _assert_rankwise_equal(views, exact=True)
    finally:
        _teardown(all_procs, stores, lighthouse)


def test_multihost_groups_kill_heal(tmp_path, monkeypatch) -> None:
    monkeypatch.setenv("MH_QUANTIZE", "0")
    lighthouse = _make_lighthouse()
    stores: List[StoreServer] = []
    all_procs: List[subprocess.Popen] = []
    try:
        num_steps = 10
        results = {
            g: {r: tmp_path / f"g{g}r{r}.pkl" for r in range(2)} for g in range(2)
        }
        # two rendezvous gates: BOTH groups park at step 0 until both are
        # registered (without this, group 0 can sprint to its park point
        # before group 1 ever joins; group 1 then faces endless comm
        # timeouts against the parked peer and never reaches die_at —
        # deadlock); group 0 additionally parks at step 4 until the
        # respawned group 1 is initializing, so it cannot burn through its
        # remaining steps during the respawn window
        start_flag = tmp_path / "fleet_registered"
        flag = tmp_path / "group1_respawned"

        store0 = StoreServer("127.0.0.1:0")
        stores.append(store0)
        group0 = _spawn_group(
            0, lighthouse.local_address(), store0.port, results[0], num_steps,
            wait_flag=str(start_flag), wait_at=0,
            wait_flag2=str(flag), wait_at2=4,
        )
        all_procs += group0

        store1 = StoreServer("127.0.0.1:0")
        stores.append(store1)
        group1 = _spawn_group(
            1, lighthouse.local_address(), store1.port, results[1], num_steps,
            die_at=2, wait_flag=str(start_flag), wait_at=0,
        )
        all_procs += group1
        _await_groups_registered(
            lighthouse, ["mh_group_0", "mh_group_1"], all_procs
        )
        start_flag.touch()

        # group 1 dies whole (both hosts) at step 2.  Only the first rank to
        # reach die_at reliably exits 9: its death makes the OTHER rank's
        # jax.distributed coordination service terminate that process with
        # its own fatal exit code (or, if the peer dies mid-barrier, a
        # manager-timeout exit) — exactly how a whole-host failure cascades
        # on a real multi-host job.  Assert the group died, not the codes.
        # must exceed the worst-case surviving-rank exit path: a failed
        # collective (comm timeout) followed by a quorum RPC against the
        # dead rank-0 manager server riding the full quorum_timeout
        # (150 s) — cycles of which can pass 240 s on a loaded machine
        rcs = [p.wait(timeout=400) for p in group1]
        assert 9 in rcs, f"group 1 should die at step 2 (rcs={rcs})"
        assert all(rc != 0 for rc in rcs), f"group 1 should die whole (rcs={rcs})"

        # ids seen so far — the dead life's heartbeat may still look fresh
        dead_ids = set(lighthouse._status().get("heartbeats", {}))

        # respawn it: fresh store + fresh jax.distributed job, heals from
        # group 0 rank-to-rank
        store1b = StoreServer("127.0.0.1:0")
        stores.append(store1b)
        group1b = _spawn_group(
            1, lighthouse.local_address(), store1b.port, results[1], num_steps
        )
        all_procs += group1b
        # release group 0 only once the respawned group is actually alive
        # (fresh heartbeat from a NEW mh_group_1 uuid on the lighthouse)
        release_deadline = time.monotonic() + 120
        while time.monotonic() < release_deadline:
            beats = lighthouse._status().get("heartbeats", {})
            if any(
                rid.startswith("mh_group_1") and rid not in dead_ids
                for rid in beats
            ):
                break
            time.sleep(0.2)
        flag.touch()  # release group 0

        deadline = time.monotonic() + 300
        for p in group0 + group1b:
            rc = p.wait(timeout=max(1.0, deadline - time.monotonic()))
            assert rc == 0, f"worker exited rc={rc}"

        views = {
            g: {r: pickle.loads(results[g][r].read_bytes()) for r in range(2)}
            for g in range(2)
        }
        for g in range(2):
            for r in range(2):
                assert views[g][r]["step"] == num_steps

        _assert_rankwise_equal(views, exact=False)
        # training moved the params away from init
        full_w = np.linspace(-1.0, 1.0, 24, dtype=np.float32).reshape(8, 3)
        w_name = next(n for n in views[0][0]["params"] if "w" in n)
        moved = False
        for key, shard in views[0][0]["params"][w_name].items():
            init = full_w[tuple(slice(*t) for t in key)]
            if not np.allclose(shard, init):
                moved = True
        assert moved, "training did not change the sharded weights"
    finally:
        _teardown(all_procs, stores, lighthouse)
