"""Tests for auxiliary subsystems: launcher, punisher, observability,
parameter server, coordination exports."""

import json
import logging
import sys
import time

import numpy as np
import pytest

from torchft_tpu.launcher import ReplicaSpec, ReplicaSupervisor
from torchft_tpu.lighthouse import LighthouseClient, LighthouseServer
from torchft_tpu.observability import _JsonLinesFormatter
from torchft_tpu.parameter_server import ParameterServer, ParameterServerClient


def test_coordination_exports() -> None:
    from torchft_tpu import coordination

    for name in [
        "LighthouseClient",
        "LighthouseServer",
        "ManagerClient",
        "ManagerServer",
        "Quorum",
        "QuorumMember",
        "compute_quorum_results",
    ]:
        assert hasattr(coordination, name)


class TestObservability:
    def test_json_formatter_includes_attrs(self) -> None:
        record = logging.LogRecord(
            "torchft_commits", logging.INFO, "", 0, "", (), None
        )
        record.replica_id = "r0"
        record.quorum_id = 3
        record.step = 7
        record.commit_result = True
        out = json.loads(_JsonLinesFormatter().format(record))
        assert out["event"] == "torchft_commits"
        assert out["replica_id"] == "r0"
        assert out["commit_result"] is True

    def test_structured_logging_to_dir(self, tmp_path, monkeypatch) -> None:
        import torchft_tpu.observability as obs

        monkeypatch.setattr(obs, "_initialized", False)
        monkeypatch.setenv(obs.LOG_DIR_ENV, str(tmp_path))
        assert obs.init_structured_logging()
        logging.getLogger("torchft_quorums").info(
            "", extra={"replica_id": "x", "quorum_id": 1, "step": 0}
        )
        for handler in logging.getLogger("torchft_quorums").handlers:
            handler.flush()
        content = (tmp_path / "torchft_quorums.jsonl").read_text()
        event = json.loads(content.strip().splitlines()[-1])
        assert event["quorum_id"] == 1
        # cleanup: detach handlers so later tests aren't redirected
        for name in obs.STRUCTURED_LOGGERS:
            logging.getLogger(name).handlers.clear()
            logging.getLogger(name).propagate = True
        monkeypatch.setattr(obs, "_initialized", False)

    def test_span_is_the_one_protocol_phase_annotation(self) -> None:
        """What ``record_function`` / ``traced`` were for: a protocol phase
        is one ``obs.spans.span``, usable bare (nobody bound, no buffer, no
        trace) around any code, and it times what it wraps."""
        from torchft_tpu.obs import spans

        spans.bind(None)
        with spans.span("tpuft/test/span") as sp:
            time.sleep(0.002)
        assert sp.attrs == {"r": ""} and sp.duration_s >= 0.002

        def fn(x):
            with spans.span("tpuft/test/fn", x=x):
                return x + 1

        assert fn(1) == 2
        import torchft_tpu.observability as obs

        # the old stack is gone, not aliased
        for gone in ("record_function", "traced", "QuorumTracer", "TRACE_DIR_ENV"):
            assert not hasattr(obs, gone), gone


class TestLauncher:
    def test_supervisor_restarts_crashed_replica(self, tmp_path) -> None:
        marker = tmp_path / "count"
        script = (
            "import os, sys, pathlib\n"
            f"p = pathlib.Path({str(marker)!r})\n"
            "n = int(p.read_text()) if p.exists() else 0\n"
            "p.write_text(str(n + 1))\n"
            "sys.exit(1 if n == 0 else 0)\n"  # crash once, then succeed
        )
        spec = ReplicaSpec(replica_group_id=0, cmd=[sys.executable, "-c", script])
        supervisor = ReplicaSupervisor(
            [spec], lighthouse_addr="127.0.0.1:1", max_restarts=3, restart_delay_s=0.1
        )
        rc = supervisor.run()
        assert rc == 0
        assert marker.read_text() == "2"

    def test_supervisor_gives_up_after_max_restarts(self) -> None:
        spec = ReplicaSpec(
            replica_group_id=0, cmd=[sys.executable, "-c", "import sys; sys.exit(3)"]
        )
        supervisor = ReplicaSupervisor(
            [spec], lighthouse_addr="127.0.0.1:1", max_restarts=1, restart_delay_s=0.05
        )
        rc = supervisor.run()
        assert rc == 3

    def test_standby_retired_when_group_leaves_fleet(self) -> None:
        """A parked spare must not outlive its group: on clean exit (and on
        give-up) the supervisor terminates the standby instead of leaking a
        process that pins TPU/compile resources."""
        import threading

        script = (
            "import os, sys, time\n"
            "if os.environ.get('TPUFT_STANDBY_GATE'):\n"
            "    time.sleep(600)\n"  # parked spare: wait forever
            "time.sleep(0.5)\n"
            "sys.exit(0)\n"
        )
        spec = ReplicaSpec(
            replica_group_id=0,
            cmd=[sys.executable, "-c", script],
            standby=True,
        )
        supervisor = ReplicaSupervisor(
            [spec], lighthouse_addr="127.0.0.1:1", restart_delay_s=0.05
        )
        runner = threading.Thread(target=supervisor.run, daemon=True)
        runner.start()
        # grab the parked spare while the active process is still running
        deadline = time.time() + 5.0
        while time.time() < deadline and 0 not in supervisor._standbys:
            time.sleep(0.02)
        spare = supervisor._standbys[0][0]
        assert spare.poll() is None
        # margin note: the active + spare boot concurrently — under
        # full-suite load the supervision round trip can exceed 10 s
        # without anything being wrong
        runner.join(timeout=30.0)
        assert not runner.is_alive()  # clean exit ended supervision
        assert not supervisor._standbys
        assert spare.wait(timeout=10.0) is not None  # spare terminated

    def test_env_contract(self, tmp_path) -> None:
        out = tmp_path / "env.json"
        script = (
            "import os, json, sys\n"
            f"json.dump({{k: os.environ.get(k) for k in "
            f"['TORCHFT_LIGHTHOUSE','REPLICA_GROUP_ID','NUM_REPLICA_GROUPS']}}, "
            f"open({str(out)!r}, 'w'))\n"
        )
        spec = ReplicaSpec(replica_group_id=1, cmd=[sys.executable, "-c", script])
        supervisor = ReplicaSupervisor(
            [spec, ReplicaSpec(2, [sys.executable, "-c", "pass"])],
            lighthouse_addr="lh:123",
        )
        supervisor.run()
        env = json.loads(out.read_text())
        assert env["TORCHFT_LIGHTHOUSE"] == "lh:123"
        assert env["REPLICA_GROUP_ID"] == "1"
        assert env["NUM_REPLICA_GROUPS"] == "2"


class TestPunisher:
    def test_kill_one_via_lighthouse(self) -> None:
        """punisher reads membership from the lighthouse and delivers a kill
        rpc to the victim's manager (here: a stub that records it)."""
        import random
        import threading

        from torchft_tpu import punisher
        from torchft_tpu.manager_server import ManagerServer

        lighthouse = LighthouseServer(
            bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=50, quorum_tick_ms=20
        )
        killed = []
        mgr = ManagerServer(
            replica_id="victim",
            lighthouse_addr=lighthouse.local_address(),
            hostname="127.0.0.1",
            bind="127.0.0.1:0",
            store_addr="s",
            world_size=1,
            kill_fn=lambda msg: killed.append(msg),
        )
        try:
            from torchft_tpu.manager_server import ManagerClient

            client = ManagerClient(f"127.0.0.1:{mgr.port}")
            client._quorum(
                group_rank=0, step=0, checkpoint_metadata="", shrink_only=False, timeout=10.0
            )
            client.close()

            lh_client = LighthouseClient(lighthouse.local_address(), connect_timeout=5.0)
            victim = punisher.kill_one(lh_client, random.Random(0))
            assert victim == "victim"
            time.sleep(0.2)
            assert killed == ["killed by punisher"]
            lh_client.close()
        finally:
            mgr.shutdown()
            lighthouse.shutdown()


class TestParameterServer:
    def test_fetch_and_push(self) -> None:
        ps = ParameterServer({"w": np.arange(4, dtype=np.float32)})
        try:
            client = ParameterServerClient(ps.address(), timeout_s=15.0)
            params = client.get_params({"w": np.zeros(4)})
            np.testing.assert_allclose(params["w"], np.arange(4))
            client.push_grads({"w": np.full(4, 2.0, dtype=np.float32)})
            client.close()
            time.sleep(0.3)  # session thread applies the push
            np.testing.assert_allclose(
                ps.params()["w"], np.arange(4) + 2.0
            )
        finally:
            ps.shutdown()


class TestDualStack:
    """IPv6/dual-stack binding (reference: torchft/http.py:11-13)."""

    def test_create_listener_dual_stack_accepts_v4(self) -> None:
        import socket as s

        from torchft_tpu.wire import create_listener

        # probe v4 availability independently, so a dual-stack listener
        # refusing v4 (the regression this test guards) still FAILS rather
        # than reading as "no IPv4 loopback"
        try:
            probe = s.socket(s.AF_INET, s.SOCK_STREAM)
            probe.bind(("127.0.0.1", 0))
            probe.close()
        except OSError:
            import pytest

            pytest.skip("no IPv4 loopback")

        sock = create_listener("0.0.0.0:0")
        port = sock.getsockname()[1]
        try:
            with s.create_connection(("127.0.0.1", port), timeout=5.0):
                pass
            if sock.family == s.AF_INET6:
                with s.create_connection(("::1", port), timeout=5.0):
                    pass
        finally:
            sock.close()

    def test_create_listener_ipv6_literal(self) -> None:
        import socket as s

        from torchft_tpu.wire import create_listener

        try:
            sock = create_listener("[::1]:0")
        except OSError:
            import pytest

            pytest.skip("no IPv6 loopback")
        port = sock.getsockname()[1]
        try:
            with s.create_connection(("::1", port), timeout=5.0):
                pass
        finally:
            sock.close()

    def test_lighthouse_on_ipv6(self) -> None:
        from torchft_tpu.lighthouse import LighthouseClient, LighthouseServer

        try:
            server = LighthouseServer(
                bind="[::1]:0", min_replicas=1, join_timeout_ms=50
            )
        except OSError:
            import pytest

            pytest.skip("no IPv6 loopback")
        try:
            client = LighthouseClient(f"[::1]:{server.port}", connect_timeout=5.0)
            client.heartbeat("r0")
            client.close()
        finally:
            server.shutdown()

    def test_http_transport_dual_stack(self) -> None:
        import numpy as np

        from torchft_tpu.checkpointing.http_transport import HTTPTransport

        import socket as s

        import pytest

        sender = HTTPTransport(timeout=10.0)
        receiver = HTTPTransport(timeout=10.0)
        if sender._server.socket.family != s.AF_INET6:
            sender.shutdown()
            receiver.shutdown()
            pytest.skip("no IPv6: transport bound v4-only")
        state = {"x": np.arange(10, dtype=np.float32)}
        try:
            sender.send_checkpoint([1], step=3, state_dict=state, timeout=5.0)
            for host in ("127.0.0.1", "[::1]"):
                out = receiver.recv_checkpoint(
                    src_rank=0,
                    metadata=f"http://{host}:{sender.port}",
                    step=3,
                    timeout=10.0,
                )
                np.testing.assert_array_equal(out["x"], state["x"])
        finally:
            sender.shutdown()
            receiver.shutdown()
