"""ftlint (torchft_tpu.analysis) — seeded-bad fixtures per checker + a
clean-tree smoke run.

Each checker is fed a minimal snippet containing exactly the bug class it
exists for (the ones past reviews caught by hand) and must flag it; the
matching good twin must stay quiet.  The smoke test runs the full suite
over the real repo and asserts it is clean — the analyzers are only
credible if the tree they gate passes them.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from torchft_tpu.analysis import (
    concurrency,
    core,
    knobcheck,
    nativelocks,
    nativemirror,
    threads,
    wireproto,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# thread-safety
# ---------------------------------------------------------------------------


def _thread_findings(snippet: str):
    return threads.check_source(textwrap.dedent(snippet), "fixture.py")


class TestThreadSafety:
    BAD = """
    import threading

    class Server:
        def __init__(self):
            self._inflight_ops = 0
            self._lock = threading.Lock()

        def start(self):
            threading.Thread(target=self._loop, daemon=True).start()

        def _loop(self):
            self._inflight_ops += 1

        def submit_op(self):
            self._inflight_ops += 1
    """

    def test_unlocked_cross_thread_augassign_flagged(self):
        findings = _thread_findings(self.BAD)
        assert len(findings) == 2  # both unlocked sites
        assert all("_inflight_ops" in f.message for f in findings)
        assert {"Server._loop._inflight_ops", "Server.submit_op._inflight_ops"} == {
            f.symbol for f in findings
        }

    def test_locked_sites_pass(self):
        findings = _thread_findings(
            """
            import threading

            class Server:
                def __init__(self):
                    self._inflight_ops = 0
                    self._lock = threading.Lock()

                def start(self):
                    threading.Thread(target=self._loop, daemon=True).start()

                def _loop(self):
                    with self._lock:
                        self._inflight_ops += 1

                def submit_op(self):
                    with self._lock:
                        self._inflight_ops += 1
            """
        )
        assert findings == []

    def test_single_context_mutation_passes(self):
        # no thread entry points -> nothing can race, even unlocked
        findings = _thread_findings(
            """
            class Counter:
                def bump(self):
                    self._n += 1
            """
        )
        assert findings == []

    def test_executor_submit_is_an_entry_point(self):
        findings = _thread_findings(
            """
            class Worker:
                def kick(self):
                    self._pool.submit(self._work)

                def _work(self):
                    self._done += 1

                def reset(self):
                    self._done = 0
            """
        )
        assert any(f.symbol == "Worker._work._done" for f in findings)

    def test_rpc_handler_reached_through_accept_loop(self):
        # the accept loop is the Thread target; the handler it dispatches
        # (transitively, via self-calls) inherits the spawned context
        findings = _thread_findings(
            """
            import threading

            class Rpc:
                def start(self):
                    threading.Thread(target=self._serve).start()

                def _serve(self):
                    while True:
                        self._handle_quorum()

                def _handle_quorum(self):
                    self._rounds += 1

                def status(self):
                    self._rounds += 1
            """
        )
        assert {f.symbol for f in findings} == {
            "Rpc._handle_quorum._rounds",
            "Rpc.status._rounds",
        }

    def test_closure_thread_target_is_an_entry_point(self):
        # the dominant spawn idiom in this codebase: a nested def passed as
        # the Thread target — its mutations run in the spawned thread, not
        # the defining method's context
        findings = _thread_findings(
            """
            import threading

            class C:
                def start(self):
                    def _loop():
                        self._n += 1
                    threading.Thread(target=_loop, daemon=True).start()

                def bump(self):
                    self._n += 1
            """
        )
        assert {f.symbol for f in findings} == {
            "C.start._loop._n",
            "C.bump._n",
        }

    def test_closure_target_does_not_inherit_parent_lock(self):
        # a nested def DEFINED under `with lock` does not EXECUTE under it
        findings = _thread_findings(
            """
            import threading

            class C:
                def start(self):
                    with self._lock:
                        def _loop():
                            self._n += 1
                        threading.Thread(target=_loop).start()

                def bump(self):
                    with self._lock:
                        self._n += 1
            """
        )
        assert {f.symbol for f in findings} == {"C.start._loop._n"}

    def test_container_mutation_in_value_position_flagged(self):
        findings = _thread_findings(
            """
            import threading

            class Q:
                def start(self):
                    threading.Thread(target=self._drain).start()

                def _drain(self):
                    item = self._pending.pop(0)
                    return item

                def push(self, x):
                    self._pending.append(x)
            """
        )
        assert len(findings) == 2

    def test_condition_variable_counts_as_lock(self):
        findings = _thread_findings(
            """
            import threading

            class Q:
                def start(self):
                    threading.Thread(target=self._drain).start()

                def _drain(self):
                    with self._cv:
                        item = self._pending.pop(0)
                    return item

                def push(self, x):
                    with self._cv:
                        self._pending.append(x)
            """
        )
        assert findings == []

    def test_pragma_suppresses(self):
        source = textwrap.dedent(self.BAD).replace(
            "    def _loop(self):\n        self._inflight_ops += 1",
            "    def _loop(self):\n"
            "        # ftlint: ignore[thread-safety] — test pragma\n"
            "        self._inflight_ops += 1",
        )
        assert "ftlint: ignore" in source
        findings = threads.check_source(source, "fixture.py")
        pragmas = core.pragma_lines(source)
        live = [f for f in findings if not core.is_suppressed(f, pragmas)]
        assert len(findings) == 2 and len(live) == 1


# ---------------------------------------------------------------------------
# lock-order
# ---------------------------------------------------------------------------


def _conc(snippet: str, checker: str):
    return concurrency.check_source(
        textwrap.dedent(snippet), "fixture.py", (checker,)
    )


class TestLockOrder:
    def test_ab_ba_cycle_flagged(self):
        findings = _conc(
            """
            class S:
                def a_then_b(self):
                    with self._a_lock:
                        with self._b_lock:
                            self._x = 1

                def b_then_a(self):
                    with self._b_lock:
                        with self._a_lock:
                            self._x = 2
            """,
            "lock-order",
        )
        assert len(findings) == 1
        assert "conflicting orders" in findings[0].message
        assert "_a_lock" in findings[0].symbol and "_b_lock" in findings[0].symbol

    def test_cycle_through_method_call_flagged(self):
        # the cross-method shape: A held, self._helper() acquires B; another
        # path takes B then A — invisible to a single-scope scan
        findings = _conc(
            """
            class S:
                def outer(self):
                    with self._a_lock:
                        self._helper()

                def _helper(self):
                    with self._b_lock:
                        self._x = 1

                def other(self):
                    with self._b_lock:
                        with self._a_lock:
                            self._x = 2
            """,
            "lock-order",
        )
        assert len(findings) == 1
        assert "conflicting orders" in findings[0].message

    def test_consistent_order_passes(self):
        findings = _conc(
            """
            class S:
                def one(self):
                    with self._a_lock:
                        with self._b_lock:
                            self._x = 1

                def two(self):
                    with self._a_lock:
                        with self._b_lock:
                            self._x = 2
            """,
            "lock-order",
        )
        assert findings == []

    def test_plain_lock_reentry_flagged(self):
        findings = _conc(
            """
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.Lock()

                def outer(self):
                    with self._lock:
                        self._inner()

                def _inner(self):
                    with self._lock:
                        self._x = 1
            """,
            "lock-order",
        )
        assert len(findings) == 1
        assert "not reentrant" in findings[0].message

    def test_rlock_and_condition_reentry_pass(self):
        findings = _conc(
            """
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.RLock()
                    self._cv = threading.Condition()

                def outer(self):
                    with self._lock:
                        self._inner()
                    with self._cv:
                        self._notify()

                def _inner(self):
                    with self._lock:
                        self._x = 1

                def _notify(self):
                    with self._cv:
                        self._cv.notify_all()
            """,
            "lock-order",
        )
        assert findings == []

    def test_unknown_ctor_reentry_stays_quiet(self):
        # lock type unseen (injected) — conservative: no self-deadlock claim
        findings = _conc(
            """
            class S:
                def outer(self):
                    with self._lock:
                        self._inner()

                def _inner(self):
                    with self._lock:
                        self._x = 1
            """,
            "lock-order",
        )
        assert findings == []


# ---------------------------------------------------------------------------
# blocking-under-lock
# ---------------------------------------------------------------------------


class TestBlockingUnderLock:
    def test_sleep_under_lock_flagged(self):
        findings = _conc(
            """
            import time

            class S:
                def poll(self):
                    with self._lock:
                        time.sleep(0.5)
            """,
            "blocking-under-lock",
        )
        assert len(findings) == 1
        assert "time.sleep()" in findings[0].message

    def test_rpc_through_helper_under_lock_flagged(self):
        # the quorum-wedge shape: the lock is held across a helper whose
        # closure does the actual client round-trip
        findings = _conc(
            """
            class S:
                def run(self):
                    with self._client_lock:
                        self._fetch()

                def _fetch(self):
                    return self._lh_client.quorum(timeout=1.0)
            """,
            "blocking-under-lock",
        )
        assert len(findings) == 1
        assert "self._fetch()" in findings[0].message
        assert "RPC" in findings[0].message

    def test_future_result_and_event_wait_under_lock_flagged(self):
        findings = _conc(
            """
            class S:
                def a(self):
                    with self._lock:
                        return self._fut.result()

                def b(self):
                    with self._lock:
                        self._done_event.wait(1.0)
            """,
            "blocking-under-lock",
        )
        descs = {f.message for f in findings}
        assert len(findings) == 2
        assert any("Future.result()" in d for d in descs)
        assert any("wait()" in d for d in descs)

    def test_cv_wait_on_held_lock_passes(self):
        # cv.wait RELEASES the lock it waits on — the one blocking call
        # that is correct under its own lock
        findings = _conc(
            """
            class S:
                def park(self):
                    with self._lock:
                        while not self._ready:
                            self._lock.wait(0.1)
            """,
            "blocking-under-lock",
        )
        assert findings == []

    def test_blocking_outside_lock_passes(self):
        findings = _conc(
            """
            import time

            class S:
                def run(self):
                    with self._lock:
                        self._n += 1
                    time.sleep(0.5)
                    self._sock.recv(1024)
            """,
            "blocking-under-lock",
        )
        assert findings == []

    def test_str_join_not_confused_with_thread_join(self):
        findings = _conc(
            """
            class S:
                def render(self):
                    with self._lock:
                        return ", ".join(self._parts)

                def reap(self):
                    with self._lock:
                        self._thread.join()
            """,
            "blocking-under-lock",
        )
        assert len(findings) == 1
        assert findings[0].symbol.endswith("join()")
        assert "reap" in findings[0].symbol


# ---------------------------------------------------------------------------
# executor-starvation
# ---------------------------------------------------------------------------


class TestExecutorStarvation:
    def test_submit_from_executor_context_flagged(self):
        findings = _conc(
            """
            import concurrent.futures

            class S:
                def __init__(self):
                    self._executor = concurrent.futures.ThreadPoolExecutor(
                        max_workers=1
                    )

                def kick(self):
                    self._executor.submit(self._task)

                def _task(self):
                    self._executor.submit(self._cleanup).result()

                def _cleanup(self):
                    pass
            """,
            "executor-starvation",
        )
        assert len(findings) == 1
        assert findings[0].symbol == "S._task._executor"

    def test_transitive_submit_flagged(self):
        # the submit hides one call deeper: _task -> _stage -> submit
        findings = _conc(
            """
            import concurrent.futures

            class S:
                def __init__(self):
                    self._executor = concurrent.futures.ThreadPoolExecutor(
                        max_workers=1
                    )

                def kick(self):
                    self._executor.submit(self._task)

                def _task(self):
                    self._stage()

                def _stage(self):
                    self._executor.submit(self._cleanup)

                def _cleanup(self):
                    pass
            """,
            "executor-starvation",
        )
        assert len(findings) == 1
        assert findings[0].symbol == "S._stage._executor"

    def test_submit_from_caller_context_passes(self):
        # the manager.py shape: the train thread submits the quorum AND the
        # warm staging; neither submitted task submits again
        findings = _conc(
            """
            import concurrent.futures

            class S:
                def __init__(self):
                    self._executor = concurrent.futures.ThreadPoolExecutor(
                        max_workers=1
                    )

                def start_round(self):
                    self._executor.submit(self._async_quorum)
                    self._maybe_stage()

                def _maybe_stage(self):
                    self._executor.submit(self._stage_now)

                def _async_quorum(self):
                    self._n += 1

                def _stage_now(self):
                    self._m += 1
            """,
            "executor-starvation",
        )
        assert findings == []

    def test_multi_worker_executor_passes(self):
        findings = _conc(
            """
            import concurrent.futures

            class S:
                def __init__(self):
                    self._pool = concurrent.futures.ThreadPoolExecutor(
                        max_workers=4
                    )

                def kick(self):
                    self._pool.submit(self._task)

                def _task(self):
                    self._pool.submit(self._cleanup)

                def _cleanup(self):
                    pass
            """,
            "executor-starvation",
        )
        assert findings == []


# ---------------------------------------------------------------------------
# native-locks
# ---------------------------------------------------------------------------


class TestNativeLocks:
    GUARDED_BAD = (
        "class C {\n"
        " public:\n"
        "  void unlocked_touch() { peers_.clear(); }\n"
        "  void locked_elsewhere() {\n"
        "    std::lock_guard<std::mutex> lock(state_mu_);\n"
        "  }\n"
        " private:\n"
        "  // guards peers_\n"
        "  std::mutex state_mu_;\n"
        "  std::map<int, int> peers_;\n"
        "};\n"
    )

    def test_guarded_member_use_without_lock_flagged(self):
        findings = nativelocks.check_text(self.GUARDED_BAD, "native/c.h")
        assert len(findings) == 1
        assert findings[0].symbol == "guards.peers_"

    def test_guarded_member_use_under_lock_passes(self):
        good = self.GUARDED_BAD.replace(
            "  void unlocked_touch() { peers_.clear(); }\n",
            "  void locked_touch() {\n"
            "    std::lock_guard<std::mutex> lock(state_mu_);\n"
            "    peers_.clear();\n"
            "  }\n",
        )
        assert nativelocks.check_text(good, "native/c.h") == []

    def test_locked_suffix_function_exempt(self):
        good = self.GUARDED_BAD.replace(
            "  void unlocked_touch() { peers_.clear(); }\n",
            "  void touch_locked() { peers_.clear(); }\n",
        )
        assert nativelocks.check_text(good, "native/c.h") == []

    def test_raw_snapshot_deref_flagged(self):
        text = (
            "class C {\n"
            "  IoPtr io_snapshot() {\n"
            "    std::lock_guard<std::mutex> lock(mu_);\n"
            "    return io_;\n"
            "  }\n"
            "  void op() { io_->gate(); }\n"
            "  std::mutex mu_;\n"
            "  IoPtr io_;\n"
            "};\n"
        )
        findings = nativelocks.check_text(text, "native/c.h")
        assert any(f.symbol == "snapshot.io_" for f in findings)

    def test_snapshot_copy_under_lock_passes(self):
        text = (
            "class C {\n"
            "  IoPtr io_snapshot() {\n"
            "    std::lock_guard<std::mutex> lock(mu_);\n"
            "    return io_;\n"
            "  }\n"
            "  void op() { IoPtr io = io_snapshot(); io->gate(); }\n"
            "  std::mutex mu_;\n"
            "  IoPtr io_;\n"
            "};\n"
        )
        assert nativelocks.check_text(text, "native/c.h") == []

    def test_dead_mutex_flagged(self):
        findings = nativelocks.check_text(
            "class C {\n  std::mutex dead_mu_;\n  int x_ = 0;\n};\n",
            "native/c.h",
        )
        assert [f.symbol for f in findings] == ["mutex.dead_mu_"]

    def test_cv_wait_keeps_mutex_live(self):
        text = (
            "class C {\n"
            "  void park() {\n"
            "    std::unique_lock<std::mutex> lock(mu_);\n"
            "    cv_.wait(lock);\n"
            "  }\n"
            "  std::mutex mu_;\n"
            "  std::condition_variable cv_;\n"
            "};\n"
        )
        assert nativelocks.check_text(text, "native/c.h") == []

    def test_atomic_memcpy_flagged(self):
        text = (
            "struct B {\n"
            "  std::atomic<uint64_t> ctr_{0};\n"
            "  void snap(void* dst) { std::memcpy(dst, &ctr_, 8); }\n"
            "  std::mutex mu_;\n"
            "  void ok() { std::lock_guard<std::mutex> l(mu_); }\n"
            "};\n"
        )
        findings = nativelocks.check_text(text, "native/c.h")
        assert [f.symbol for f in findings] == ["atomic.ctr_"]

    def test_atomic_plain_shadow_flagged(self):
        text = (
            "struct B {\n"
            "  std::atomic<bool> stop_{false};\n"
            "  bool stop_ = false;\n"
            "  std::mutex mu_;\n"
            "  void ok() { std::lock_guard<std::mutex> l(mu_); }\n"
            "};\n"
        )
        findings = nativelocks.check_text(text, "native/c.h")
        assert any(
            f.symbol == "atomic.stop_" and "shadow" in f.message
            for f in findings
        )

    def test_multiline_guards_annotation_fully_parsed(self):
        # members wrapped onto // continuation lines must stay enforced —
        # a first-line-only parse would silently drop them
        text = (
            "class C {\n"
            "  void bad() { wrapped_member_ = 1; }\n"
            "  void ok() { std::lock_guard<std::mutex> l(mu_); }\n"
            "  // guards first_member_/\n"
            "  // wrapped_member_\n"
            "  std::mutex mu_;\n"
            "  int first_member_ = 0;\n"
            "  int wrapped_member_ = 0;\n"
            "};\n"
        )
        assert nativelocks._guard_map(text) == {
            "first_member_": "mu_",
            "wrapped_member_": "mu_",
        }
        findings = nativelocks.check_text(text, "native/c.h")
        assert [f.symbol for f in findings] == ["guards.wrapped_member_"]

    def test_cpp_pragma_suppresses(self):
        source = self.GUARDED_BAD.replace(
            "  void unlocked_touch() { peers_.clear(); }\n",
            "  // ftlint: ignore[native-locks] — test pragma\n"
            "  void unlocked_touch() { peers_.clear(); }\n",
        )
        findings = nativelocks.check_text(source, "native/c.h")
        pragmas = core.pragma_lines(source)
        assert len(findings) == 1
        assert core.is_suppressed(findings[0], pragmas)

    def test_real_native_headers_clean(self):
        findings = nativelocks.check(REPO)
        assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# wire-protocol
# ---------------------------------------------------------------------------


class TestWireProtocol:
    def test_duplicate_tag_allocation_flagged(self):
        findings = wireproto.check_allocations(
            {"A": (100, 10), "B": (105, 10)}, {}
        )
        assert len(findings) == 1 and "collide" in findings[0].message

    def test_disjoint_allocations_pass(self):
        assert (
            wireproto.check_allocations({"A": (100, 10), "B": (200, 10)}, {})
            == []
        )

    def test_user_tags_crossing_wire_offsets_flagged(self):
        findings = wireproto.check_allocations(
            {"A": (100, 5000)}, {"ALLTOALL": 4000, "ALLGATHER": 5000}
        )
        assert any("alias" in f.message for f in findings)

    def test_unregistered_tag_literal_flagged(self):
        src = "def f(comm):\n    comm.allgather(x, tag=666)\n"
        findings = wireproto.check_tag_literals(src, "fixture.py", {103: "Q"})
        assert len(findings) == 1 and "666" in findings[0].message

    def test_registered_and_adhoc_literals_pass(self):
        src = (
            "def f(comm):\n"
            "    comm.allgather(x, tag=103)\n"
            "    comm.send_bytes(b, dst, tag=1)\n"
        )
        assert wireproto.check_tag_literals(src, "fixture.py", {103: "Q"}) == []

    ONE_SIDED = """
    def manager_quorum_wire_version():
        return 2

    class Msg:
        def encode(self, w):
            w.i64(self.step)
            if manager_quorum_wire_version() >= 2:
                w.u64(self.extra)

        @staticmethod
        def decode(r):
            out = Msg()
            out.step = r.i64()
            out.extra = r.u64()
            return out
    """

    def test_one_sided_version_gate_flagged(self):
        findings = wireproto.check_codec_source(
            textwrap.dedent(self.ONE_SIDED), "fixture.py"
        )
        # asymmetric at BOTH levels: v2 field read ungated
        assert findings
        assert any("version gate" in f.message or "asymmetric" in f.message
                   for f in findings)

    def test_symmetric_version_gate_passes(self):
        findings = wireproto.check_codec_source(
            textwrap.dedent(
                """
                def manager_quorum_wire_version():
                    return 2

                class Msg:
                    def encode(self, w):
                        w.i64(self.step)
                        if manager_quorum_wire_version() >= 2:
                            w.u32(2)
                            w.u64(self.extra)

                    @staticmethod
                    def decode(r):
                        out = Msg()
                        out.step = r.i64()
                        if not r.done():
                            tail_version = r.u32()
                            if tail_version >= 2:
                                out.extra = r.u64()
                        return out
                """
            ),
            "fixture.py",
        )
        assert findings == []

    # the wire-v5 degraded-capacity tail shape: a DERIVED boolean guard
    # (`has_capacity_tail = wire_version >= 5 and <degraded>`) gating a
    # count + f64 loop — the checker must attribute the emits to level 5
    # through the variable and still demand the symmetric read gate
    V5_CAPACITY_ONE_SIDED = """
    def manager_quorum_wire_version():
        return 5

    class Msg:
        def encode(self, w):
            w.i64(self.step)
            wire_version = manager_quorum_wire_version()
            has_capacity_tail = wire_version >= 5 and any(
                c != 1.0 for c in self.capacities
            )
            if has_capacity_tail:
                w.u32(5)
                w.u32(len(self.capacities))
                for c in self.capacities:
                    w.f64(c)

        @staticmethod
        def decode(r):
            out = Msg()
            out.step = r.i64()
            out.capacities = [r.f64() for _ in range(r.u32())]
            return out
    """

    def test_v5_capacity_tail_one_sided_gate_flagged(self):
        findings = wireproto.check_codec_source(
            textwrap.dedent(self.V5_CAPACITY_ONE_SIDED), "fixture.py"
        )
        assert findings
        assert any("5" in f.message for f in findings)

    def test_v5_capacity_tail_symmetric_gate_passes(self):
        findings = wireproto.check_codec_source(
            textwrap.dedent(
                """
                def manager_quorum_wire_version():
                    return 5

                class Msg:
                    def encode(self, w):
                        w.i64(self.step)
                        wire_version = manager_quorum_wire_version()
                        has_capacity_tail = wire_version >= 5 and any(
                            c != 1.0 for c in self.capacities
                        )
                        if has_capacity_tail:
                            w.u32(5)
                            w.u32(len(self.capacities))
                            for c in self.capacities:
                                w.f64(c)

                    @staticmethod
                    def decode(r):
                        out = Msg()
                        out.step = r.i64()
                        if not r.done() and r.u32() >= 5:
                            out.capacities = [
                                r.f64() for _ in range(r.u32())
                            ]
                        return out
                """
            ),
            "fixture.py",
        )
        assert findings == []

    def test_field_order_drift_flagged(self):
        findings = wireproto.check_codec_source(
            textwrap.dedent(
                """
                class Msg:
                    def encode(self, w):
                        w.i64(self.a)
                        w.string(self.b)

                    @staticmethod
                    def decode(r):
                        out = Msg()
                        out.b = r.string()
                        out.a = r.i64()
                        return out
                """
            ),
            "fixture.py",
        )
        assert len(findings) == 1

    def test_real_wire_module_is_symmetric(self):
        import torchft_tpu.wire as wire_mod

        with open(wire_mod.__file__) as f:
            findings = wireproto.check_codec_source(f.read(), "wire.py")
        assert findings == []

    def test_real_registry_has_no_collisions(self):
        import torchft_tpu.wire as wire_mod

        assert (
            wireproto.check_allocations(
                wire_mod.USER_TAG_ALLOCATIONS, wire_mod.WIRE_TAG_OFFSETS
            )
            == []
        )

    # -- ISSUE-15 STREAM_OUTER rotating fragment windows --------------------

    def test_stream_window_overlapping_legacy_flagged(self):
        """Seeded-bad twin: a STREAM_OUTER span stretched into QUANT_RING
        territory must read as a collision — the whole point of the
        registry is that a streamed fragment sync can never alias the
        quantized ring's frames."""
        import torchft_tpu.wire as wire_mod

        bad = dict(wire_mod.USER_TAG_ALLOCATIONS)
        base = wire_mod.STREAM_OUTER_TAG_BASE
        bad["STREAM_OUTER"] = (base, wire_mod.QUANT_RING_TAG - base + 1)
        findings = wireproto.check_allocations(bad, wire_mod.WIRE_TAG_OFFSETS)
        assert any(
            "STREAM_OUTER" in f.symbol and "collide" in f.message
            for f in findings
        ), [f.render() for f in findings]

    def test_stream_windows_partition_declared_span(self):
        """Good twin: the rotating per-fragment windows tile exactly the
        registered STREAM_OUTER allocation — disjoint, in-span, and each
        wide enough for the collectives pipeline's 2-tags-per-chunk
        framing."""
        import torchft_tpu.wire as wire_mod

        windows = [
            wire_mod.stream_frag_tag_window(f)
            for f in range(wire_mod.STREAM_FRAG_WINDOWS)
        ]
        lo = wire_mod.STREAM_OUTER_TAG_BASE
        hi = lo + wire_mod.STREAM_OUTER_TAG_SPAN
        covered = set()
        for base, span in windows:
            assert lo <= base and base + span <= hi
            assert span >= 2  # at least one 2-tag pipeline chunk
            rng = set(range(base, base + span))
            assert not (rng & covered), "fragment windows overlap"
            covered |= rng
        assert covered == set(range(lo, hi)), (
            "windows must tile the declared span exactly"
        )
        # and the rotation is total: any fragment index lands in-span
        for frag in (wire_mod.STREAM_FRAG_WINDOWS, 7, 123):
            base, span = wire_mod.stream_frag_tag_window(frag)
            assert lo <= base and base + span <= hi

    def test_unregistered_stream_range_literal_flagged(self):
        """Seeded-bad twin: a hand-written literal inside the STREAM_OUTER
        window must be flagged when the registry lacks STREAM_OUTER — the
        named helper, not arithmetic on magic numbers, is the sanctioned
        way into the window.  The whole allocation must sit ABOVE the
        ad-hoc literal ceiling, or a lint-legal small literal could alias
        window 0's frames unflagged."""
        import torchft_tpu.wire as wire_mod

        assert wire_mod.STREAM_OUTER_TAG_BASE > wireproto._ADHOC_TAG_MAX, (
            "STREAM_OUTER overlaps the ad-hoc tag range: literals there "
            "pass ftlint and would alias streamed frames"
        )
        base0 = wire_mod.stream_frag_tag_window(0)[0]
        src = f"def f(comm):\n    comm.alltoall(parts, tag={base0})\n"
        findings = wireproto.check_tag_literals(src, "fixture.py", {})
        assert len(findings) == 1 and str(base0) in findings[0].message

    def test_stream_helper_call_sites_pass(self):
        """Good twin: the real collectives idiom — tag math over a value
        returned by the helper, no literals — stays quiet."""
        src = (
            "from torchft_tpu import wire\n"
            "def f(group, ci, frag):\n"
            "    tag_base, _span = wire.stream_frag_tag_window(frag)\n"
            "    group.alltoall(parts, tag=tag_base + 2 * ci)\n"
        )
        assert wireproto.check_tag_literals(src, "fixture.py", {}) == []


# ---------------------------------------------------------------------------
# knob-registry
# ---------------------------------------------------------------------------


class TestKnobRegistry:
    def test_unregistered_knob_read_flagged(self):
        src = 'import os\nx = os.environ.get("TORCHFT_NOT_A_REAL_KNOB", "")\n'
        findings = knobcheck.check_source_tokens(src, "fixture.py", {})
        assert len(findings) == 1
        assert findings[0].symbol == "TORCHFT_NOT_A_REAL_KNOB"

    def test_registered_and_indirect_reads_pass(self):
        registry = {"TORCHFT_RING_LANES": object()}
        src = (
            'LANES_ENV = "TORCHFT_RING_LANES"\n'
            "import os\n"
            "lanes = os.environ.get(LANES_ENV)\n"
        )
        assert knobcheck.check_source_tokens(src, "fixture.py", registry) == []

    def test_family_prefix_is_not_a_knob(self):
        registry = {"TPUFT_BENCH_STEPS": object()}
        src = 'keys = [k for k in env if k.startswith("TPUFT_BENCH_")]\n'
        assert knobcheck.check_source_tokens(src, "fixture.py", registry) == []

    def test_comments_are_not_reads(self):
        # AST string scan: a commented-out knob is not a mention
        src = "# os.environ.get('TORCHFT_GHOST_KNOB')\nx = 1\n"
        assert knobcheck.check_source_tokens(src, "fixture.py", {}) == []

    def test_docs_drift_both_directions(self):
        registry = {"TORCHFT_A": object(), "TORCHFT_B": object()}
        doc = "| `TORCHFT_A` | ... |\n| `TORCHFT_STALE` | gone |\n"
        findings = knobcheck.check_docs(doc, registry)
        symbols = {f.symbol for f in findings}
        assert symbols == {"TORCHFT_STALE", "TORCHFT_B"}

    def test_every_package_knob_is_registered_and_documented(self):
        from torchft_tpu import knobs

        findings = knobcheck.check(REPO)
        assert findings == [], "\n".join(f.render() for f in findings)
        # and the registry itself is non-trivial
        assert len(knobs.REGISTRY) >= 45

    def test_accessors_read_env_live(self, monkeypatch):
        from torchft_tpu import knobs

        monkeypatch.setenv("TORCHFT_RING_LANES", "4")
        assert knobs.get_int("TORCHFT_RING_LANES", 1) == 4
        monkeypatch.delenv("TORCHFT_RING_LANES")
        assert knobs.get_int("TORCHFT_RING_LANES", 1) == 1
        with pytest.raises(KeyError):
            knobs.get_int("TORCHFT_NOT_DECLARED", 1)
        monkeypatch.setenv("TORCHFT_RING_LANES", "zap")
        with pytest.raises(ValueError, match="TORCHFT_RING_LANES"):
            knobs.get_int("TORCHFT_RING_LANES", 1)


# ---------------------------------------------------------------------------
# native-mirror
# ---------------------------------------------------------------------------


class TestNativeMirror:
    def test_drifted_hello_flag_flagged(self):
        text = "constexpr uint64_t kLaneHelloFlag = uint64_t(1) << 62;\n"
        findings = nativemirror.check_comm_header(text, "native/comm.h")
        assert any(f.symbol == "kLaneHelloFlag" and "62" in f.message
                   for f in findings)

    def test_drifted_alignment_flagged(self):
        text = (
            "std::vector<std::pair<size_t, size_t>> lane_parts(size_t nbytes) {\n"
            "  size_t cut = (i * nbytes / k) / 32 * 32;\n"
            "}\n"
        )
        findings = nativemirror.check_comm_header(text, "native/comm.h")
        assert any(f.symbol == "lane_parts.align" for f in findings)

    def test_missing_mirror_symbol_flagged(self):
        findings = nativemirror.check_comm_header("// empty\n", "native/comm.h")
        assert {"HostTopology", "lane_parts", "outer_shard_parts"} <= {
            f.symbol for f in findings
        }

    def test_drifted_enum_value_flagged(self):
        text = "  MGR_QUORUM_REQ = 0x99,\n"
        findings = nativemirror.check_wire_header(text, "native/wire.h")
        assert any(f.symbol == "MGR_QUORUM_REQ" for f in findings)

    def test_drifted_frame_cap_flagged(self):
        text = "constexpr uint64_t kMaxFrameBytes = 32ull * 1024 * 1024;\n"
        findings = nativemirror.check_wire_header(text, "native/wire.h")
        assert any(f.symbol == "kMaxFrameBytes" for f in findings)

    def test_drifted_iovec_cap_flagged(self):
        text = "constexpr size_t kMaxIovSegs = 8;\n"
        findings = nativemirror.check_comm_header(text, "native/comm.h")
        assert any(
            f.symbol == "kMaxIovSegs" and "8" in f.message for f in findings
        )

    @pytest.mark.parametrize(
        "const,py_name",
        [("kMaxAutoLanes", "_MAX_AUTO_LANES"), ("kUnshapedAutoLanes", "_UNSHAPED_AUTO_LANES")],
    )
    def test_drifted_auto_lane_count_flagged(self, const, py_name):
        """What ``auto`` resolves to, under a profile and (PR 47) without:
        a drift is named, and so is a header that lacks the constant."""
        from torchft_tpu import communicator as pycomm

        ours = getattr(pycomm, py_name)
        text = f"constexpr size_t {const} =\n    {ours + 1};  // mirror\n"
        findings = nativemirror.check_comm_header(text, "native/comm.h")
        assert any(f.symbol == const and str(ours + 1) in f.message for f in findings)
        text = f"constexpr size_t {const} = {ours};\n"
        findings = nativemirror.check_comm_header(text, "native/comm.h")
        assert not any(f.symbol == const for f in findings)
        findings = nativemirror.check_comm_header("// empty\n", "native/comm.h")
        assert any(f.symbol == const and "not found" in f.message for f in findings)

    def test_missing_iovec_cap_flagged(self):
        findings = nativemirror.check_comm_header("// empty\n", "native/comm.h")
        assert any(f.symbol == "kMaxIovSegs" for f in findings)

    @pytest.mark.parametrize(
        "const", ["kRingReduceTagBase", "kRingAvgTagBase", "kRingBufferTagStride"]
    )
    def test_drifted_ring_reduce_tag_base_flagged(self, const):
        """The rings' tag windows (PR 40 added the averaging ring's and the
        stride between a call's dtype groups): drifted, and missing."""
        text = f"constexpr uint64_t {const} = 40000;\n"
        findings = nativemirror.check_comm_header(text, "native/comm.h")
        assert any(
            f.symbol == const and "40000" in f.message for f in findings
        )
        findings = nativemirror.check_comm_header("// empty\n", "native/comm.h")
        assert any(f.symbol == const and "not found" in f.message for f in findings)

    def test_missing_pacer_knob_flagged(self):
        # references three of the four _NetEmu knobs: the missing one fires
        text = (
            'std::getenv("TORCHFT_NET_EMU");\n'
            'std::getenv("TORCHFT_NET_GBPS");\n'
            'std::getenv("TORCHFT_NET_RTT_MS");\n'
        )
        findings = nativemirror.check_comm_header(text, "native/comm.h")
        symbols = {f.symbol for f in findings}
        assert "pacer.TORCHFT_NET_CWND_KB" in symbols
        assert "pacer.TORCHFT_NET_EMU" not in symbols

    def test_drifted_pacer_profile_flagged(self):
        text = (
            "constexpr NetProfile kNetEmuProfiles[] = {\n"
            '    {"wan_1g", 2.0, 10.0},\n'  # drifted gbps
            '    {"wan_1g_10ms", 1.0, 10.0},\n'
            '    {"dcn_10g", 10.0, 2.0},\n'
            '    {"dcn_10g_2ms", 10.0, 2.0},\n'
            '    {"loopback", 0.0, 0.0},\n'
            "};\n"
        )
        findings = nativemirror.check_comm_header(text, "native/comm.h")
        assert any(
            f.symbol == "pacer.profile.wan_1g" and "2.0" in f.message
            for f in findings
        )

    def test_unknown_native_profile_flagged(self):
        text = (
            "constexpr NetProfile kNetEmuProfiles[] = {\n"
            '    {"wan_1g", 1.0, 10.0},\n'
            '    {"wan_1g_10ms", 1.0, 10.0},\n'
            '    {"dcn_10g", 10.0, 2.0},\n'
            '    {"dcn_10g_2ms", 10.0, 2.0},\n'
            '    {"loopback", 0.0, 0.0},\n'
            '    {"moon_link", 0.001, 2500.0},\n'
            "};\n"
        )
        findings = nativemirror.check_comm_header(text, "native/comm.h")
        assert any(
            f.symbol == "pacer.profile.moon_link" for f in findings
        )

    def test_missing_lane_counter_flagged(self):
        text = "uint64_t lane_tx_bytes_[4];\nuint64_t lane_rx_bytes_[4];\n"
        findings = nativemirror.check_comm_header(text, "native/comm.h")
        symbols = {f.symbol for f in findings}
        assert "counter.lane_stalls" in symbols
        assert "counter.lane_tx_bytes" not in symbols

    def test_binding_missing_lane_stats_key_flagged(self):
        text = (
            "_MAX_IOV_SEGS = 64\n"
            'stats = {"lanes": 1, "stripe_floor_bytes": 2,\n'
            ' "lane_tx_bytes": [], "lane_rx_bytes": []}\n'
        )
        findings = nativemirror.check_binding(text, "torchft_tpu/native.py")
        symbols = {f.symbol for f in findings}
        assert "lane_stats.lane_stalls" in symbols
        assert "lane_stats.lanes" not in symbols

    def test_binding_is_held_to_the_ring_s_seven_keys_of_seconds(self):
        """The checker lists the keys itself (it reads text and imports no
        runtime module at load); the list is the Python tier's constant, and
        a binding that lacks one of the seven is named."""
        from torchft_tpu.communicator import RING_TIME_KEYS

        assert len(RING_TIME_KEYS) == 7
        assert set(RING_TIME_KEYS) <= set(nativemirror._LANE_STAT_KEYS)
        text = "_MAX_IOV_SEGS = 64\n" + " ".join(
            f'"{k}"' for k in nativemirror._LANE_STAT_KEYS if k != "ring_tail_s"
        )
        symbols = {f.symbol for f in nativemirror.check_binding(text, "torchft_tpu/native.py")}
        assert {s for s in symbols if s.startswith("lane_stats.")} == {"lane_stats.ring_tail_s"}

    def test_binding_missing_iov_constant_flagged(self):
        findings = nativemirror.check_binding(
            '"lanes" "stripe_floor_bytes" "lane_tx_bytes" '
            '"lane_rx_bytes" "lane_stalls"\n',
            "torchft_tpu/native.py",
        )
        assert any(f.symbol == "_MAX_IOV_SEGS" for f in findings)

    def test_real_headers_mirror_python(self):
        findings = nativemirror.check(REPO)
        assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# infrastructure + clean-tree smoke
# ---------------------------------------------------------------------------


class TestInfrastructure:
    def test_fingerprint_stable_across_line_drift(self):
        a = core.Finding("c", "f.py", 10, "S.m.x", "msg")
        b = core.Finding("c", "f.py", 99, "S.m.x", "msg")
        assert a.fingerprint == b.fingerprint

    def test_baseline_roundtrip_and_staleness(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        finding = core.Finding("c", "f.py", 1, "s", "m")
        core.save_baseline(path, [finding])
        assert core.load_baseline(path) == [finding.fingerprint]
        data = json.load(open(path))
        assert data["suppressions"][0]["note"] == "m"

    def test_baseline_accepts_bare_fingerprint_list(self, tmp_path):
        path = tmp_path / "baseline.json"
        path.write_text('["c:f.py:s:abc123"]')
        assert core.load_baseline(str(path)) == ["c:f.py:s:abc123"]

    def test_json_format_emits_full_run(self, capsys, monkeypatch):
        from torchft_tpu.analysis import __main__ as cli

        new = core.Finding("c", "f.py", 2, "sym", "fresh")
        supp = core.Finding("c", "f.py", 9, "other", "excused")
        result = core.RunResult(new=[new], suppressed=[supp])
        monkeypatch.setattr(cli, "run_checkers", lambda **kw: result)
        rc = cli.main(["--format", "json"])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert payload["counts"] == {"new": 1, "suppressed": 1, "baselined": 0}
        by_disp = {row["disposition"]: row for row in payload["findings"]}
        assert by_disp["new"]["fingerprint"] == new.fingerprint
        assert by_disp["suppressed"]["symbol"] == "other"

    def test_github_format_annotates_new_findings_only(
        self, capsys, monkeypatch
    ):
        from torchft_tpu.analysis import __main__ as cli

        new = core.Finding("lock-order", "a.py", 7, "s", "cycle here")
        supp = core.Finding("lock-order", "a.py", 9, "t", "excused")
        result = core.RunResult(new=[new], suppressed=[supp])
        monkeypatch.setattr(cli, "run_checkers", lambda **kw: result)
        rc = cli.main(["--format", "github"])
        out = capsys.readouterr().out
        assert rc == 1
        assert out.splitlines() == [
            "::error file=a.py,line=7,title=ftlint lock-order::cycle here"
        ]

    def test_github_format_clean_run_is_silent_and_zero(
        self, capsys, monkeypatch
    ):
        from torchft_tpu.analysis import __main__ as cli

        monkeypatch.setattr(cli, "run_checkers", lambda **kw: core.RunResult())
        rc = cli.main(["--format", "github"])
        assert rc == 0
        assert capsys.readouterr().out == ""

    def test_write_baseline_preserves_still_firing_entries(
        self, tmp_path, monkeypatch
    ):
        from torchft_tpu.analysis import __main__ as cli

        old = core.Finding("c", "f.py", 1, "old", "grandfathered")
        new = core.Finding("c", "f.py", 2, "new", "fresh")
        result = core.RunResult(new=[new], baselined=[old])
        monkeypatch.setattr(cli, "run_checkers", lambda **kw: result)
        path = tmp_path / "baseline.json"
        rc = cli.main(["--write-baseline", "--baseline", str(path)])
        assert rc == 0
        assert set(core.load_baseline(str(path))) == {
            old.fingerprint,
            new.fingerprint,
        }


class TestNativeMirrorFlightEvents:
    GOOD = (
        "constexpr uint32_t kFlightCommConfigure = 20;\n"
        "constexpr uint32_t kFlightCommAbort = 21;\n"
        "size_t flight_drain(uint64_t* s, double* t, uint32_t* e,\n"
        "                    int64_t* a, int64_t* b, size_t cap) {}\n"
        "void x() { flight_record(kFlightCommConfigure, rank, world_size); }\n"
        "void y() { flight_record(kFlightCommAbort, 0, 0); }\n"
    )

    def test_good_twin_quiet(self):
        findings = nativemirror.check_flight_events(self.GOOD, "native/comm.h")
        assert findings == [], [f.render() for f in findings]

    def test_drifted_event_id_flagged(self):
        bad = self.GOOD.replace(
            "kFlightCommAbort = 21", "kFlightCommAbort = 99"
        )
        findings = nativemirror.check_flight_events(bad, "native/comm.h")
        assert any(
            f.symbol == "kFlightCommAbort" and "99" in f.message
            for f in findings
        )

    def test_unknown_native_event_flagged(self):
        bad = self.GOOD + "constexpr uint32_t kFlightMadeUp = 77;\n"
        findings = nativemirror.check_flight_events(bad, "native/comm.h")
        assert any(
            f.symbol == "kFlightMadeUp" and "no Python counterpart" in f.message
            for f in findings
        )

    def test_missing_ring_flagged(self):
        findings = nativemirror.check_flight_events("// empty\n", "native/comm.h")
        symbols = {f.symbol for f in findings}
        assert "kFlightEvents" in symbols
        assert "flight_drain" in symbols
        assert "flight_record.configure" in symbols

    def test_ring_slot_value_drift_flagged(self):
        comm = "constexpr size_t kFlightRingSlots = 512;\n"
        binding = (
            "def flight_drain(self):\n"
            "    cap = 256  # mirror of comm.h kFlightRingSlots\n"
        )
        findings = nativemirror.check_flight_ring_slots(comm, binding)
        assert any(
            f.symbol == "flight_drain.cap" and "512" in f.message
            for f in findings
        )
        good = binding.replace("256", "512")
        assert nativemirror.check_flight_ring_slots(comm, good) == []


class TestMetricsRegistry:
    GOOD_REGISTRY = '''
_m("torchft_lh_quorum_id", "gauge", "Current quorum id")
_m("torchft_mgr_comm_stalls_total", "counter", "Cumulative stalls")
'''

    def test_good_declarations_quiet(self):
        from torchft_tpu.analysis import metricscheck

        findings = metricscheck.check_declarations(
            self.GOOD_REGISTRY, "torchft_tpu/obs/metrics.py"
        )
        assert findings == [], [f.render() for f in findings]

    def test_duplicate_declaration_flagged(self):
        from torchft_tpu.analysis import metricscheck

        bad = self.GOOD_REGISTRY + '_m("torchft_lh_quorum_id", "gauge", "dup")\n'
        findings = metricscheck.check_declarations(bad, "metrics.py")
        assert any(
            f.symbol == "torchft_lh_quorum_id" and "twice" in f.message
            for f in findings
        )

    def test_counter_without_total_flagged(self):
        from torchft_tpu.analysis import metricscheck

        bad = '_m("torchft_mgr_stalls", "counter", "missing suffix")\n'
        findings = metricscheck.check_declarations(bad, "metrics.py")
        assert any("_total" in f.message for f in findings)

    def test_illegal_name_flagged(self):
        from torchft_tpu.analysis import metricscheck

        # the extraction regex requires the torchft prefix shape, so seed
        # an uppercase-bearing name through the declaration parser directly
        decls = metricscheck.parse_declarations(
            '_m("torchft_lh_BadName", "gauge", "x")\n'
        )
        assert decls  # parsed…
        findings = metricscheck.check_declarations(
            '_m("torchft_lh_BadName", "gauge", "x")\n', "metrics.py"
        )
        assert any("not a legal" in f.message for f in findings)

    def test_undeclared_serving_site_flagged(self):
        from torchft_tpu.analysis import metricscheck

        source = 'sample = metric_sample("torchft_mgr_not_declared_total", 1)\n'
        findings = metricscheck.check_serving_sites(
            source, "torchft_tpu/x.py", {"torchft_mgr_comm_stalls_total": "counter"}
        )
        assert any(
            f.symbol == "torchft_mgr_not_declared_total" for f in findings
        )

    def test_declared_serving_site_quiet(self):
        from torchft_tpu.analysis import metricscheck

        source = 'metric_sample("torchft_mgr_comm_stalls_total", 1)\n'
        findings = metricscheck.check_serving_sites(
            source, "torchft_tpu/x.py", {"torchft_mgr_comm_stalls_total": "counter"}
        )
        assert findings == []

    def test_docs_drift_both_directions(self):
        from torchft_tpu.analysis import metricscheck

        declared = {"torchft_lh_quorum_id": "gauge"}
        doc = "the doc mentions `torchft_lh_stale_metric` only\n"
        findings = metricscheck.check_docs(doc, declared, "docs/operations.md")
        symbols = {f.symbol for f in findings}
        assert "torchft_lh_stale_metric" in symbols  # doc'd but undeclared
        assert "torchft_lh_quorum_id" in symbols  # declared but undoc'd


class TestCleanTree:
    def test_full_suite_clean_on_repo(self):
        result = core.run_checkers(root=REPO)
        assert result.new == [], "\n".join(f.render() for f in result.new)
        assert result.stale_baseline == []

    @pytest.mark.slow
    def test_cli_exits_zero(self):
        proc = subprocess.run(
            [sys.executable, "-m", "torchft_tpu.analysis", "-q"],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=300,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
